from .textualize import (  # noqa: F401
    CICIDS_TEMPLATE,
    FLOW_TEXT_COLUMNS,
    flow_to_text,
    render_template,
    texts_from_dataframe,
)
from .datasets import (  # noqa: F401
    DATASETS,
    Corpus,
    DatasetSpec,
    UNSW_TEMPLATE,
    concat_corpora,
    corpus_from_frame,
    detect_dataset,
    get_dataset,
    load_mixed_corpus,
    parse_source_arg,
)
from .cicids import (  # noqa: F401
    ClientSplits,
    SplitArrays,
    load_client_frame,
    load_flow_csv,
    make_all_client_splits,
    make_all_client_splits_from_corpus,
    make_client_splits,
    train_val_test_split,
)
from .partition import (  # noqa: F401
    PARTITION_SCHEMES,
    dirichlet_label_indices,
    log_manifest,
    partition_indices,
    partition_manifest,
    quantity_skew_indices,
    save_manifest,
)
from .synthetic import (  # noqa: F401
    make_synthetic,
    make_synthetic_ddos2019,
    make_synthetic_flows,
    make_synthetic_unsw,
    write_synthetic_csv,
)
from .tokenizer import (  # noqa: F401
    WordPieceTokenizer,
    basic_tokenize,
    build_domain_vocab,
    default_tokenizer,
)
from .windows import render_windows, window_client, window_split  # noqa: F401
from .streaming import (  # noqa: F401
    stream_client_tokens,
    stream_client_tokens_for,
)
from .pipeline import (  # noqa: F401
    TokenizedClient,
    TokenizedSplit,
    batch_iterator,
    num_batches,
    pad_split_to_batch,
    StackedClients,
    stack_clients,
    stack_clients_ragged,
    tokenize_client,
    tokenize_split,
)
