"""Reader ``reply_occupancy``: how full the scorer's padded batches were, in
percent: rows scored over bucket rows dispatched. Every reply names its
dispatch's ``batch_size`` and ``bucket``; a dispatch of n rows shows in n
replies, so the bucket rows dispatched are the sum over replies of
bucket / batch_size.
"""

from __future__ import annotations

import numpy as np


def read(ctx):
    replies = ctx.rec.data.get("replies")
    if replies is None or len(replies.get("bucket", ())) == 0:
        return None
    size = np.asarray(replies["batch_size"], np.float64)
    bucket = np.asarray(replies["bucket"], np.float64)
    ok = size > 0
    return 100.0 * float(ok.sum()) / float((bucket[ok] / size[ok]).sum())
