"""Named model-size presets — the single registry behind ``--preset``.

Every entrypoint that sizes a model (train, federated, infer-serve)
resolves the name here, so adding a scale point is one registry entry
instead of an if-chain edit per CLI. ``bert-large`` (~335 M params,
~1.3 GB fp32) trains on one 16 GB v5e chip at batch 64 with its Adam
state beside it (`peak_hbm_gb` 14.27, cell `bertlarge-client-fit`,
ledger PR 29); it is also the demonstration scale for ``train --fsdp``
and the sharded scorer (``infer-serve --data-parallel N --fsdp``), where
params live split per-leaf across the mesh and are gathered at use.

A preset named after a published model keeps the published vocabulary
table: the CLI hands every preset its tokenizer's size (148 ids for the
domain WordPiece), and sizing ``distilbert`` by it used to build a 43 M
model under the name of a 66 M one. Only the ``tiny`` presets, which stand
for no published model, are sized by the tokenizer.

| preset | class | what it is |
| --- | --- | --- |
| ``tiny`` / ``distilbert`` / ``bert`` / ``bert-large`` | ``models/distilbert.py`` | the BERT encoder ladder; published weights load through ``models/hf_convert.py`` |
| ``kimi-linear-tiny`` / ``kimi-linear-ep32`` | ``models/kimi_linear.py`` | KDA + NoPE latent attention + experts; ``ep32``: one of 32 chips' share of Kimi-Linear-48B-A3B, windows of 28 flows to 4,096 tokens |
| ``laguna-xs2-tiny`` / ``laguna-xs2-ep8`` | ``models/laguna.py`` | window-512 and full attention with rotary positions, grouped heads, a gate a head, experts; ``ep8``: one of 8 chips' share of Laguna-XS.2, windows of 56 flows to 8,192 tokens |
| ``qwen3-next-tiny`` / ``qwen3-next-ep16`` | ``models/qwen3_next.py`` | Gated DeltaNet and gated 256-wide grouped attention three to one, softmax-routed experts with a gated shared expert; ``ep16``: one of 16 chips' share of Qwen3-Next-80B-A3B, windows of 112 flows to 16,384 tokens |

None of the decoder classes has a converter from its published tensor names yet:
their weights are random from the seed.
"""

from __future__ import annotations

from typing import Any, Callable

from ..config import KimiLinearConfig, LagunaConfig, ModelConfig, Qwen3NextConfig

#: name -> config factory. Ordered small -> large so help strings
#: and error messages read as the scale ladder.
PRESETS: dict[str, Callable[..., Any]] = {
    "tiny": ModelConfig.tiny,
    "distilbert": ModelConfig.distilbert_base,
    "bert": ModelConfig.bert_base,
    "bert-large": ModelConfig.bert_large,
    "kimi-linear-tiny": KimiLinearConfig.tiny,
    "kimi-linear-ep32": KimiLinearConfig.ep32_cut,
    "laguna-xs2-tiny": LagunaConfig.tiny,
    "laguna-xs2-ep8": LagunaConfig.ep8_cut,
    "qwen3-next-tiny": Qwen3NextConfig.tiny,
    "qwen3-next-ep16": Qwen3NextConfig.ep16_cut,
}

#: Presets that stand for no published model: their table is the tokenizer's.
TOKENIZER_SIZED = ("tiny", "kimi-linear-tiny", "laguna-xs2-tiny", "qwen3-next-tiny")

#: DataConfig fields a preset's rows need (``cli/common.py::resolve_config``):
#: a long-context preset reads windows of consecutive flows, not single flows.
PRESET_DATA: dict[str, dict[str, Any]] = {
    "kimi-linear-tiny": {"window_flows": 2},
    "kimi-linear-ep32": {"window_flows": 28},
    "laguna-xs2-tiny": {"window_flows": 2},
    "laguna-xs2-ep8": {"window_flows": 56},
    "qwen3-next-tiny": {"window_flows": 2},
    "qwen3-next-ep16": {"window_flows": 112},
}


def preset_names() -> tuple[str, ...]:
    """The registry's names in ladder order (for help/error strings)."""
    return tuple(PRESETS)


def model_preset(name: str, **kw: Any):
    """Resolve a preset name to its model configuration (ValueError on
    unknown — CLI callers wrap it into their SystemExit idiom). A
    ``vocab_size`` is the tokenizer's: it sizes the ``tiny`` presets' table,
    and must fit inside a published preset's own."""
    try:
        factory = PRESETS[name]
    except KeyError:
        raise ValueError(
            f"unknown model preset {name!r} "
            f"(one of: {'|'.join(PRESETS)})"
        ) from None
    if name not in TOKENIZER_SIZED and "vocab_size" in kw:
        published = factory().vocab_size
        if kw["vocab_size"] > published:
            raise ValueError(
                f"preset {name!r} has a {published}-row vocabulary table; the "
                f"tokenizer needs {kw['vocab_size']}"
            )
        kw = {**kw, "vocab_size": published}
    return factory(**kw)
