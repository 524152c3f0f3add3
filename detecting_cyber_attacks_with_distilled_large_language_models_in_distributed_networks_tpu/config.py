"""Typed configuration system.

The reference has no config system at all — module-level constants and literals
scattered through three scripts (reference client1.py:22-23, server.py:10-13;
bs=16 / max_len=128 / lr=2e-5 / epochs=3 at client1.py:27,365-372,379-380), and
scaling to N clients means copy-pasting ``clientN.py`` with a new hard-coded
seed.  Here every knob is a dataclass field and per-client identity is derived
(``client_id -> seed``), never copy-pasted.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence


@dataclass(frozen=True)
class ModelConfig:
    """Transformer encoder + classification head.

    Defaults reproduce DistilBERT-base-uncased (6 layers, 768 hidden, 12 heads,
    3072 FFN, learned positions, post-LayerNorm, exact GELU) which the reference
    loads via HF ``DistilBertModel.from_pretrained`` (reference client1.py:56),
    plus the reference's classifier head: CLS pooling -> Dropout(0.3) ->
    Linear(768, 2) (reference client1.py:57-58,62-64).
    """

    vocab_size: int = 30522
    max_len: int = 128
    max_position_embeddings: int = 512  # HF DistilBERT position-table size
    dim: int = 768
    n_layers: int = 6
    n_heads: int = 12
    hidden_dim: int = 3072
    dropout: float = 0.1
    attention_dropout: float = 0.1
    head_dropout: float = 0.3
    n_classes: int = 2
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    pad_token_id: int = 0
    # "bf16" activations keep the MXU fed; params/optimizer stay fp32.
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # FFN activation: "tanh" is the GPT-2-style tanh GELU — measured ~20%
    # faster per train step than the erf form on TPU v5e (the erf chain is
    # VPU-transcendental-bound), deviating from it by at most a few bf16
    # ulps (<0.8% relative), i.e. on the order of bf16 rounding itself.
    # "exact" is HF DistilBERT's erf GELU (reference client1.py:56 via HF);
    # use it for fp32 logit-parity comparisons (ModelConfig.tiny defaults
    # to it alongside fp32 compute).
    gelu: str = "tanh"
    # "dot" (XLA fused attention), "flash" (Pallas kernel), "ring"
    # (sequence-parallel ring attention over a mesh axis).
    attention_impl: str = "dot"
    # Compute Q/K/V with ONE [D, 3D] matmul over kernels concatenated at
    # apply time (the parameter tree keeps the separate q/k/v layout, so
    # checkpoints and HF conversion are unaffected). Same math, fewer
    # larger MXU dispatches. No cell turns it on: not measured on the chip.
    fused_qkv: bool = False
    # Mesh axis the sequence dimension is sharded over when attention_impl
    # is "ring" (the forward must run inside shard_map with this axis bound).
    ring_axis: str = "seq"
    # Mesh axis the batch dimension shards over inside the same shard_map
    # (fedseq): hash-dropout masks offset their row coordinate by this
    # axis's shard index so data shards draw independent masks.
    data_axis: str = "data"
    remat: bool = False

    def __post_init__(self) -> None:
        if self.n_layers < 1:
            raise ValueError(f"n_layers={self.n_layers} must be >= 1")
        if self.max_len > self.max_position_embeddings:
            raise ValueError(
                f"max_len={self.max_len} exceeds the position-embedding table "
                f"(max_position_embeddings={self.max_position_embeddings}); "
                "XLA would silently clamp position indices"
            )
        if self.attention_impl not in ("dot", "flash", "ring"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.gelu not in ("exact", "tanh"):
            raise ValueError(f"unknown gelu {self.gelu!r} (exact|tanh)")
        # attention_impl='ring' supports attention dropout since the ring
        # gained global-coordinate hash masks (parallel/ring_attention.py);
        # no impl/dropout combination is invalid anymore.

    @property
    def head_dim(self) -> int:
        if self.dim % self.n_heads:
            raise ValueError(f"dim={self.dim} not divisible by n_heads={self.n_heads}")
        return self.dim // self.n_heads

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def distilbert_base(cls, **kw: Any) -> "ModelConfig":
        return cls(**kw)

    @classmethod
    def bert_base(cls, **kw: Any) -> "ModelConfig":
        """BERT-base-sized scale-up encoder (BASELINE.json config 4)."""
        kw.setdefault("n_layers", 12)
        return cls(**kw)

    @classmethod
    def bert_large(cls, **kw: Any) -> "ModelConfig":
        """BERT-large-sized encoder (24L/1024/16H/4096, ~335 M params) —
        the capacity ceiling for single-chip federated fine-tuning here;
        larger models shard over the mesh's data axis."""
        kw.setdefault("n_layers", 24)
        kw.setdefault("dim", 1024)
        kw.setdefault("n_heads", 16)
        kw.setdefault("hidden_dim", 4096)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw: Any) -> "ModelConfig":
        """Small config for tests / CI on CPU."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_len", 32)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("dim", 32)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 2)
        kw.setdefault("hidden_dim", 64)
        kw.setdefault("compute_dtype", "float32")
        kw.setdefault("gelu", "exact")  # fp32 tests compare against HF erf
        return cls(**kw)


@dataclass(frozen=True)
class KimiLinearConfig:
    """Hybrid linear-attention / latent-attention decoder with a sparse
    expert FFN, and the paper's classification head on each row's LAST REAL
    token (``models/kimi_linear.py``).

    Defaults are the published ``config.json`` of
    moonshotai/Kimi-Linear-48B-A3B-Instruct: 27 pre-norm (RMSNorm) layers of
    width 2304; the mixer of layer ``i`` (1-indexed) is full latent attention
    without positions (MLA, ``mla_use_nope``) where ``i`` is in
    ``full_attn_layers`` and Kimi Delta Attention (KDA, a gated delta-rule
    linear attention) elsewhere; the FFN is a dense SwiGLU in the first
    ``first_dense_layers`` layers and, after them, ``n_experts`` routed
    SwiGLU experts (sigmoid router, top ``experts_per_token`` by score +
    selection bias, renormalised, times ``routed_scale``) plus
    ``n_shared_experts`` shared ones. No position encoding anywhere.

    ``experts_held`` / ``expert_offset`` say which experts THIS chip holds
    (expert parallelism's share): the router keeps its ``n_experts`` outputs
    and the layer computes its own experts' part of the result for the
    tokens routed to them; what the absent experts would add is left out.
    The held experts share one row buffer (``ops/moe.py``); slots beyond it
    are COUNTED (the step's and the evaluation's ``overflow``), never
    silently dropped.

    Frozen and hashable: the engine memoises its compiled steps on it
    (``train/engine.py::_cached_engine_steps``).
    """

    #: The type's key in :data:`MODEL_CONFIG_TYPES`; in a serialised
    #: section it says which type to make again.
    family: str = "kimi_linear"
    vocab_size: int = 163840
    max_len: int = 4096
    dim: int = 2304
    n_layers: int = 27
    full_attn_layers: tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    first_dense_layers: int = 1
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    #: Rank of the two low-rank gates (decay and output); the source's
    #: config does not give it: the family's convention is the head width.
    gate_rank: int = 128
    # MLA
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # FFN
    hidden_dim: int = 9216
    expert_dim: int = 1024
    n_experts: int = 256
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.446
    experts_held: int = 256
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    n_classes: int = 2
    pad_token_id: int = 0
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: Per-layer recomputation: each block's forward is replayed in the
    #: backward pass instead of keeping its intermediates.
    remat: bool = False

    def __post_init__(self) -> None:
        if self.n_layers < 1:
            raise ValueError(f"n_layers={self.n_layers} must be >= 1")
        if not 0 < self.experts_held <= self.n_experts - self.expert_offset:
            raise ValueError(
                f"experts_held={self.experts_held} at expert_offset="
                f"{self.expert_offset} is not a share of n_experts={self.n_experts}"
            )
        if self.experts_per_token > self.n_experts:
            raise ValueError("experts_per_token exceeds n_experts")

    def mixer(self, layer: int) -> str:
        """``"mla"`` or ``"kda"`` for the 0-indexed ``layer``."""
        return "mla" if layer + 1 in self.full_attn_layers else "kda"

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_dense_layers

    @property
    def shared_dim(self) -> int:
        """Width of the shared experts, which run as one SwiGLU."""
        return self.n_shared_experts * self.expert_dim

    def replace(self, **kw: Any) -> "KimiLinearConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def ep32_cut(cls, **kw: Any) -> "KimiLinearConfig":
        """One chip's share of a deployment in which 32 chips share each
        layer: layers 1-5 (the leading dense layer and one whole period of
        three KDA to one MLA), 8 of the 256 experts, an eighth of the
        vocabulary; every width as published (~555 M parameters)."""
        kw.setdefault("n_layers", 5)
        kw.setdefault("full_attn_layers", (4,))
        kw.setdefault("experts_held", 8)
        kw.setdefault("vocab_size", 20480)
        kw.setdefault("remat", True)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw: Any) -> "KimiLinearConfig":
        """Small config for tests / CI on CPU, fp32: three layers hold
        every kind of part (KDA + dense FFN, MLA + experts, KDA + experts)."""
        defaults = dict(
            vocab_size=256, max_len=96, dim=32, n_layers=3,
            full_attn_layers=(2,), kda_heads=2, kda_head_dim=16, gate_rank=8,
            n_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, hidden_dim=64,
            expert_dim=24, n_experts=16, experts_per_token=4, experts_held=4,
            compute_dtype="float32",
        )
        return cls(**{**defaults, **kw})


#: One period of Laguna's layer pattern: a full-attention layer, then three
#: with a sliding window, and the query heads of each.
_LAGUNA_PERIOD = (("full", 48), ("sliding", 64), ("sliding", 64), ("sliding", 64))


@dataclass(frozen=True)
class LagunaConfig:
    """Pre-norm decoder whose attention is, by layer, full or cut to a
    sliding window, with rotary positions of two kinds, grouped key/value
    heads under two counts of query heads, a per-head output gate and a
    sparse expert FFN, and the paper's classification head on each row's
    LAST REAL token (``models/laguna.py``).

    Defaults are the published ``config.json`` of poolside/Laguna-XS.2: 40
    RMSNorm layers of width 2048, ``head_dim`` 128, 8 key/value heads; layer
    ``i``'s attention is ``layer_types[i]`` (``"full"`` or ``"sliding"``,
    window ``sliding_window``) with ``heads_per_layer[i]`` query heads (48
    where full, 64 where sliding); sliding layers rotate every dimension of a
    head at ``sliding_rope_theta``, full layers the first
    ``full_rotary_share`` of them at ``full_rope_theta`` under YaRN
    (``ops/rope.py``); the FFN of layer ``i`` is ``ffn_types[i]``: a dense
    SwiGLU of ``hidden_dim`` or ``n_experts`` routed SwiGLU experts of
    ``expert_dim`` (sigmoid router, top ``experts_per_token`` by score,
    renormalised, times ``routed_scale``) plus one shared expert of
    ``shared_dim``.

    ``experts_held`` / ``expert_offset``: the experts THIS chip holds, as
    :class:`KimiLinearConfig` has them. Frozen and hashable, for the same
    reason.
    """

    family: str = "laguna"
    vocab_size: int = 100352
    max_len: int = 8192
    dim: int = 2048
    layer_types: tuple[str, ...] = tuple(kind for kind, _ in _LAGUNA_PERIOD) * 10
    heads_per_layer: tuple[int, ...] = tuple(heads for _, heads in _LAGUNA_PERIOD) * 10
    ffn_types: tuple[str, ...] = ("dense",) + ("sparse",) * 39
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # Rotary positions of the sliding layers (the default kind)
    sliding_rope_theta: float = 10000.0
    sliding_rotary_share: float = 1.0
    # and of the full layers (YaRN; a factor of 1 is the default kind)
    full_rope_theta: float = 500000.0
    full_rotary_share: float = 0.5
    full_rope_factor: float = 64.0
    full_rope_original_len: int = 4096
    full_rope_beta_fast: float = 64.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.4158883083359672
    # FFN
    hidden_dim: int = 8192
    expert_dim: int = 512
    shared_dim: int = 512
    n_experts: int = 256
    experts_per_token: int = 8
    routed_scale: float = 2.5
    experts_held: int = 256
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    n_classes: int = 2
    pad_token_id: int = 0
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: Per-layer recomputation, as :class:`KimiLinearConfig` has it.
    remat: bool = False

    def __post_init__(self) -> None:
        n = len(self.layer_types)
        if n < 1 or not len(self.heads_per_layer) == len(self.ffn_types) == n:
            raise ValueError(
                f"layer_types ({n}), heads_per_layer ({len(self.heads_per_layer)}) and "
                f"ffn_types ({len(self.ffn_types)}) must name the same layers, at least one"
            )
        if set(self.layer_types) - {"full", "sliding"} or set(self.ffn_types) - {"dense", "sparse"}:
            raise ValueError("layer_types holds full|sliding and ffn_types dense|sparse")
        if any(h % self.n_kv_heads for h in self.heads_per_layer):
            raise ValueError(
                f"heads_per_layer={self.heads_per_layer}: every count must be a multiple of "
                f"n_kv_heads={self.n_kv_heads}"
            )
        if not 0 < self.experts_held <= self.n_experts - self.expert_offset:
            raise ValueError(
                f"experts_held={self.experts_held} at expert_offset="
                f"{self.expert_offset} is not a share of n_experts={self.n_experts}"
            )
        if self.experts_per_token > self.n_experts:
            raise ValueError("experts_per_token exceeds n_experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def is_moe(self, layer: int) -> bool:
        return self.ffn_types[layer] == "sparse"

    def replace(self, **kw: Any) -> "LagunaConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def ep8_cut(cls, **kw: Any) -> "LagunaConfig":
        """One chip's share of a deployment in which 8 chips share each
        layer: layers 0-4 (the leading dense layer and the whole period after
        it), 32 of the 256 experts, an eighth of the vocabulary; every width
        as published (~666 M parameters)."""
        kw.setdefault("layer_types", ("full", "sliding", "sliding", "sliding", "full"))
        kw.setdefault("heads_per_layer", (48, 64, 64, 64, 48))
        kw.setdefault("ffn_types", ("dense", "sparse", "sparse", "sparse", "sparse"))
        kw.setdefault("experts_held", 32)
        kw.setdefault("vocab_size", 12544)
        kw.setdefault("remat", True)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw: Any) -> "LagunaConfig":
        """Small config for tests / CI on CPU, fp32: three layers hold every
        kind of part (full + dense FFN, sliding + experts, full + experts),
        with both head counts and a window shorter than a row."""
        defaults = dict(
            vocab_size=256, max_len=96, dim=32,
            layer_types=("full", "sliding", "full"), heads_per_layer=(4, 6, 4),
            ffn_types=("dense", "sparse", "sparse"), n_kv_heads=2, head_dim=8,
            sliding_window=24, full_rope_original_len=32, full_rope_factor=4.0,
            full_rope_attention_factor=1.1386294361119891, hidden_dim=64,
            expert_dim=24, shared_dim=24, n_experts=16, experts_per_token=4,
            experts_held=4, compute_dtype="float32",
        )
        return cls(**{**defaults, **kw})


@dataclass(frozen=True)
class Qwen3NextConfig:
    """Pre-norm decoder whose mixer is, by layer, a Gated DeltaNet (the delta
    rule with ONE decay a value head and token, a short convolution over q, k
    and v together, key heads grouped under twice as many value heads, a
    normed output gated by ``silu(z)``) or a gated softmax attention (grouped
    heads of 256 with query/key norms, a quarter of each head rotated, an
    element-wise sigmoid gate), every layer with a sparse expert FFN (softmax
    router, the top ``experts_per_token`` renormalised, a shared expert with a
    gate of its own), and the paper's classification head on each row's LAST
    REAL token (``models/qwen3_next.py``).

    Defaults are the published ``config.json`` of
    Qwen/Qwen3-Next-80B-A3B-Instruct: 48 layers of width 2048 under the
    family's zero-centred RMSNorm (``x / rms(x) * (1 + w)``); layer ``i`` is
    gated attention where ``(i + 1) % full_attention_interval == 0`` and
    Gated DeltaNet elsewhere.

    ``experts_held`` / ``expert_offset``: the experts THIS chip holds, as
    :class:`KimiLinearConfig` has them. Frozen and hashable, for the same
    reason.
    """

    family: str = "qwen3_next"
    vocab_size: int = 151936
    max_len: int = 16384
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    # Gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_share: float = 0.25
    rope_theta: float = 10000000.0
    # FFN
    expert_dim: int = 512
    shared_dim: int = 512
    n_experts: int = 512
    experts_per_token: int = 10
    experts_held: int = 512
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    n_classes: int = 2
    pad_token_id: int = 0
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: Per-layer recomputation, as :class:`KimiLinearConfig` has it.
    remat: bool = False

    def __post_init__(self) -> None:
        if self.n_layers < 1:
            raise ValueError(f"n_layers={self.n_layers} must be >= 1")
        if self.linear_value_heads % self.linear_key_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"linear_value_heads={self.linear_value_heads} must be a multiple of linear_key_heads="
                f"{self.linear_key_heads}, and n_heads={self.n_heads} of n_kv_heads={self.n_kv_heads}"
            )
        if not 0 < self.experts_held <= self.n_experts - self.expert_offset:
            raise ValueError(
                f"experts_held={self.experts_held} at expert_offset="
                f"{self.expert_offset} is not a share of n_experts={self.n_experts}"
            )
        if self.experts_per_token > self.n_experts:
            raise ValueError("experts_per_token exceeds n_experts")

    def mixer(self, layer: int) -> str:
        """``"full"`` or ``"linear"`` for the 0-indexed ``layer``."""
        return "full" if (layer + 1) % self.full_attention_interval == 0 else "linear"

    def is_moe(self, layer: int) -> bool:
        return True  # decoder_sparse_step 1, no mlp_only_layers

    @property
    def routed_scale(self) -> float:
        """The chosen experts' renormalised weights are used as they are."""
        return 1.0

    def replace(self, **kw: Any) -> "Qwen3NextConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def ep16_cut(cls, **kw: Any) -> "Qwen3NextConfig":
        """One chip's share of a deployment in which 16 chips share each
        layer: layers 0-3 (one whole period: linear, linear, linear, full),
        32 of the 512 experts, an eighth of the vocabulary; every width as
        published (~587 M parameters)."""
        kw.setdefault("n_layers", 4)
        kw.setdefault("experts_held", 32)
        kw.setdefault("vocab_size", 18992)
        kw.setdefault("remat", True)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw: Any) -> "Qwen3NextConfig":
        """Small config for tests / CI on CPU, fp32: one period (three Gated
        DeltaNet layers and a gated attention layer), key heads grouped under
        twice as many value heads, a quarter of each head rotated."""
        defaults = dict(
            vocab_size=256, max_len=96, dim=32, n_layers=4, linear_key_heads=2,
            linear_value_heads=4, linear_key_dim=16, linear_value_dim=16, n_heads=4,
            n_kv_heads=2, head_dim=16, expert_dim=24, shared_dim=24, n_experts=16,
            experts_per_token=4, experts_held=4, compute_dtype="float32",
        )
        return cls(**{**defaults, **kw})


#: THE registry of the model families beside the BERT encoder: ``family``
#: value -> configuration type (a ``ModelConfig`` has no ``family`` key).
#: ``ExperimentConfig.from_dict`` picks a section's type by it, and
#: ``models.family_module`` the module ``models/<family>.py`` that holds the
#: family's ``Classifier`` and ``forward_flops``.
MODEL_CONFIG_TYPES: dict[str, type] = {
    "kimi_linear": KimiLinearConfig, "laguna": LagunaConfig, "qwen3_next": Qwen3NextConfig,
}


@dataclass(frozen=True)
class DataConfig:
    """CICIDS2017-style flow CSV -> text -> token arrays.

    Mirrors reference semantics: ``±inf -> NaN -> column-mean`` imputation and a
    ``frac`` sample with a per-client seed (reference client1.py:84-93, seed 42;
    client2.py:79-88, seed 43), 60/20/20 split via two chained train_test_split
    calls (reference client1.py:365-366), label map ``'DDoS' -> 1 else 0``
    (reference client1.py:91).
    """

    csv_path: str = "CICIDS2017.csv"
    # Registered dataset schema: cicids2017 | cicddos2019 | unswnb15
    # (data/datasets.py). Governs the text template + binary-label semantics.
    dataset: str = "cicids2017"
    data_fraction: float = 0.1
    seed_base: int = 42  # client i uses seed_base + i  (42, 43, ... — matches reference)
    val_fraction: float = 0.2
    test_fraction: float = 0.2
    label_column: str = "Label"
    positive_label: str = "DDoS"
    max_len: int = 128
    batch_size: int = 16
    eval_batch_size: int = 16
    # "sample"  — reference behavior: independent frac-sample per client seed
    #             (overlap between clients possible, as in the reference).
    # "disjoint" — equal disjoint shards.
    # "dirichlet" — non-IID label-skew partition (BASELINE.json config 3).
    # "quantity" — quantity skew: disjoint IID-content shards with
    #             Dirichlet(alpha) sizes (data/partition.py).
    partition: str = "sample"
    # Concentration for BOTH skewed schemes: dirichlet (label skew) and
    # quantity (size skew); smaller = more skewed.
    dirichlet_alpha: float = 0.5
    vocab_path: str | None = None
    # Training batches: True (default) drops the final short batch of each
    # epoch so every step compiles once at one shape; False trains it at
    # its own (smaller) shape — the reference DataLoader's drop_last=False
    # (client1.py:370) at the cost of one extra XLA compilation. Eval is
    # unaffected (it always counts every example via row masks).
    drop_remainder: bool = True
    # Rows of a long-context model (data/windows.py): 0 = one flow a row
    # (the reference's); k > 0 = k consecutive flows of one source joined
    # into one document, labelled by whether the window holds an attack.
    window_flows: int = 0

    def __post_init__(self) -> None:
        if self.window_flows < 0:
            raise ValueError(f"window_flows={self.window_flows} must be >= 0")
        if self.dirichlet_alpha <= 0.0:
            # numpy 2.x draws an all-zero Dirichlet for alpha=0 silently,
            # which would hand every sample to the last client.
            raise ValueError(
                f"dirichlet_alpha={self.dirichlet_alpha} must be > 0"
            )
        if self.partition not in ("sample", "disjoint", "dirichlet", "quantity"):
            # Fail at config time, not mid-partition: a typo'd scheme on
            # the TCP tier would otherwise surface only after the model
            # loaded (data/partition.py PARTITION_SCHEMES).
            raise ValueError(
                f"unknown partition scheme {self.partition!r} "
                "(sample|disjoint|dirichlet|quantity)"
            )

    def client_seed(self, client_id: int) -> int:
        return self.seed_base + client_id


@dataclass(frozen=True)
class TrainConfig:
    """Local-training hyperparameters (reference client1.py:370,379-380)."""

    learning_rate: float = 2e-5
    # Linear LR warmup over this many steps (0 = constant, the reference's
    # schedule). Larger per-client batches than the reference's 16 (the TPU
    # MFU sweet spot is 128, SURVEY.md §7c) train more stably with warmup.
    warmup_steps: int = 0
    epochs_per_round: int = 3
    weight_decay: float = 0.0
    grad_accum_steps: int = 1
    max_grad_norm: float | None = None
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    seed: int = 0
    # Per-step telemetry cadence: every N train steps the fit loops log
    # step, loss, and samples/s (the reference's tqdm per-batch loss line,
    # client1.py:101,112). Each log point syncs the device once; 0 disables
    # (per-epoch averages only).
    log_every: int = 100
    # Dropout-key PRNG implementation. "rbg" (counter-based, the standard
    # TPU choice for dropout masks) is cheaper on the chip than
    # "threefry2x32" (no cell runs the latter); both are valid JAX key impls.
    prng_impl: str = "rbg"
    # Which parameters the optimizer updates. "all" (default) is normal
    # training; "head" freezes the encoder and trains only the classifier
    # head (updates zeroed via optax.multi_transform) — the FedPer-style
    # personalization scope, also usable standalone for linear probing of
    # a pretrained encoder.
    trainable: str = "all"
    # FedProx proximal term for the TCP-tier client loop (strategies/):
    # local loss += mu/2 * ||w - w_round_start||^2 against the round's
    # adopted aggregate. 0 = plain local SGD. The SPMD mesh tier carries
    # the same knob as FedConfig.prox_mu (train/fedsteps.py); this one
    # reaches the per-client train-step builders in train/engine.py.
    prox_mu: float = 0.0

    def __post_init__(self) -> None:
        if self.prng_impl not in ("rbg", "threefry2x32", "unsafe_rbg"):
            raise ValueError(f"unknown prng_impl {self.prng_impl!r}")
        if self.trainable not in ("all", "head"):
            raise ValueError(
                f"trainable={self.trainable!r} must be 'all' or 'head'"
            )
        if self.prox_mu < 0.0:
            raise ValueError(f"prox_mu={self.prox_mu} must be >= 0")


@dataclass(frozen=True)
class FedConfig:
    """Federated-round structure.

    The reference runs exactly one FedAvg round per invocation with exactly
    ``NUM_CLIENTS=2`` clients and an unweighted mean (reference server.py:13,
    67-79); multi-round is re-running with warm start (client1.py:375-377).
    Here rounds and client count are first-class, aggregation may be weighted
    by client sample counts, and dropped clients are masked out of the mean
    instead of hanging the round (reference behavior: accept-loop hangs until
    timeout, server.py:69-71,124-132).
    """

    num_clients: int = 2
    rounds: int = 1
    # FedAvg weighting. None (default) = auto: weight by true per-client
    # sample count whenever the counts are known (the ragged stacked path
    # carries them) and DP is off — matching the reference's *semantics*
    # (each client's rows influence the fleet equally) for unequal fleets
    # while reproducing its unweighted mean exactly for equal ones.
    # True = require sample-count weights; False = force the uniform mean
    # (the reference's literal server.py:73-76 arithmetic).
    weighted: bool | None = None
    # FedProx (Li et al.): local loss += mu/2 * ||w - w_round_start||^2,
    # anchoring client drift under non-IID partitions (the dirichlet knob,
    # BASELINE.json config 3). 0 = plain FedAvg, the reference's algorithm.
    prox_mu: float = 0.0
    # Minimum fraction of clients that must survive a round for aggregation
    # to proceed (masked mean over survivors); reference requires all.
    min_client_fraction: float = 1.0
    # Fresh optimizer state each round — mirrors the reference, where every
    # round is a new process with a newly constructed Adam (client1.py:380).
    reset_optimizer_each_round: bool = True
    # Partial participation: fraction of clients whose round contributes to
    # the aggregate (sampled per round, seeded). Under SPMD every replica
    # still computes in lockstep; non-participants' local epochs are simply
    # excluded from the masked mean and overwritten by its result. 1.0 =
    # everyone, the reference's behavior.
    participation: float = 1.0
    # How the per-round cohort is drawn when participation < 1:
    #   "fixed"   — exactly cohort_size() clients without replacement (the
    #               classic FL sampler; the DP accountant's Poisson bound
    #               is then the standard approximation);
    #   "poisson" — each client joins independently with probability
    #               `participation` (variable cohort; the subsampled-
    #               Gaussian accountant's assumption holds EXACTLY);
    #   "auto"    — poisson when DP is on (exact epsilon), fixed otherwise.
    participation_mode: str = "auto"
    # DP-FedAvg (parallel/dp.py): clip each client's round update to this
    # global L2 norm before aggregation. 0 = off (plain FedAvg, the
    # reference's algorithm — which ships raw unclipped state dicts,
    # client1.py:276-295).
    dp_clip: float = 0.0
    # Gaussian-mechanism noise multiplier: noise std on the mean update is
    # noise_multiplier * dp_clip / n_participants. Requires dp_clip > 0.
    dp_noise_multiplier: float = 0.0
    # DP noise seed. None (default, the only private choice): fresh OS
    # entropy per run, agreed across hosts. Setting a value makes the noise
    # reproducible — anyone who knows it can subtract the noise, so it
    # VOIDS the (epsilon, delta) guarantee; tests only.
    dp_seed: int | None = None
    # Server-side optimizer over the round's mean update (FedOpt, Reddi et
    # al.): "none" = plain FedAvg (new global = mean, the reference's
    # algorithm); "momentum" = FedAvgM (heavy-ball over round updates);
    # "adam" = FedAdam and "yogi" = FedYogi (adaptive per-parameter server
    # steps; yogi's additive second moment resists the non-IID variance
    # spikes that swamp adam's EMA). Server state persists across rounds
    # (unlike the per-round client optimizer reset).
    server_opt: str = "none"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # Personalization (FedAvg + local fine-tuning): after the final round,
    # each client fine-tunes the aggregate on its own shard for this many
    # epochs and is evaluated as a THIRD phase ("personalized") next to
    # the reference's local/aggregated pair. 0 = off. Scope "full"
    # fine-tunes everything (FedAvg+FT); "head" freezes the shared encoder
    # and adapts only the classifier head (FedPer, Arivazhagan et al.).
    personalize_epochs: int = 0
    personalize_scope: str = "full"
    # Survivable fold trees (comm/relay.py): a relay's per-subtree
    # straggler deadline as a fraction of the round budget. Strictly
    # inside (0, 1) — the whole point is that a slow subtree resolves
    # (sheds stragglers locally, or fails its local quorum so its
    # clients re-home) while the root is still inside ITS deadline; a
    # factor >= 1 re-creates the stalled-root failure mode the relay
    # tier exists to remove.
    subtree_deadline_factor: float = 0.5
    # Wire dtype for STREAMED client uploads (comm/wire.py): "fp32" is
    # the exact historical encoding; "bf16" / "int8" quantize each
    # streamed chunk (int8 with a per-4096-element fp32 scale, ~3.98x
    # smaller uploads). Negotiated: the server adverts its decodable
    # encodings in reply meta and the client upgrades one reply behind,
    # so an old peer on either end keeps the fp32 wire. Lossy dtypes are
    # refused alongside secure-agg or compressed uploads; under DP the
    # server re-clips after dequantization (containment).
    wire_dtype: str = "fp32"

    def server_opt_enabled(self) -> bool:
        return self.server_opt != "none"

    def resolve_weighted(self) -> bool:
        """The effective weighting choice: auto (None) weights by sample
        count unless DP needs its uniform mean."""
        if self.weighted is None:
            return self.dp_clip == 0.0
        return self.weighted

    def cohort_size(self) -> int:
        """Clients sampled per round. ceil keeps k >= C * participation
        (round() could land below min_client_fraction via banker's
        rounding) — the SINGLE source of truth shared by the sampler
        (participation_mask) and the DP accountant's effective rate."""
        import math

        if self.participation >= 1.0:
            return self.num_clients
        return min(
            self.num_clients,
            max(1, math.ceil(self.num_clients * self.participation)),
        )

    def effective_participation(self) -> float:
        """The ACTUAL per-round sampling rate ``cohort_size / C`` — what
        the DP accountant must see under the FIXED sampler: ceil rounding
        makes it >= the nominal ``participation`` (e.g. 0.26 of 4 clients
        samples 2/4 = 0.5), and feeding the accountant the nominal
        fraction would overstate the privacy guarantee."""
        return self.cohort_size() / self.num_clients

    def dp_enabled(self) -> bool:
        return self.dp_clip > 0.0 and self.dp_noise_multiplier > 0.0

    def resolve_participation_mode(self) -> str:
        """The effective cohort sampler: "auto" picks poisson when DP is
        on (the accountant's Poisson-sampling assumption then holds
        exactly) and the classic fixed-size sampler otherwise."""
        if self.participation >= 1.0:
            return "fixed"  # everyone participates; no sampling at all
        if self.participation_mode == "auto":
            return "poisson" if self.dp_enabled() else "fixed"
        return self.participation_mode

    def dp_sampling_rate(self) -> tuple[float, bool]:
        """(q for the DP accountant, whether the SGM bound's sampling
        assumption is exact for the sampler in use). Poisson mode: q is
        the nominal participation, exactly the sampler's Bernoulli rate.
        Fixed mode: q = cohort_size/C, the standard approximation."""
        if self.participation >= 1.0:
            return 1.0, True
        if self.resolve_participation_mode() == "poisson":
            return self.participation, True
        return self.effective_participation(), False

    def __post_init__(self) -> None:
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(
                f"participation={self.participation} must be in (0, 1]"
            )
        if self.participation_mode not in ("auto", "fixed", "poisson"):
            raise ValueError(
                f"participation_mode={self.participation_mode!r} must be "
                "'auto', 'fixed' or 'poisson'"
            )
        if self.personalize_epochs < 0:
            raise ValueError(
                f"personalize_epochs={self.personalize_epochs} must be >= 0"
            )
        if self.personalize_scope not in ("full", "head"):
            raise ValueError(
                f"personalize_scope={self.personalize_scope!r} must be "
                "'full' or 'head'"
            )
        if not 0.0 < self.subtree_deadline_factor < 1.0:
            raise ValueError(
                f"subtree_deadline_factor={self.subtree_deadline_factor} "
                "must be in (0, 1): the per-subtree straggler deadline "
                "has to be strictly tighter than the round budget"
            )
        if self.wire_dtype not in ("fp32", "bf16", "int8"):
            raise ValueError(
                f"wire_dtype={self.wire_dtype!r} must be "
                "'fp32', 'bf16' or 'int8'"
            )
        if self.participation < self.min_client_fraction:
            raise ValueError(
                f"participation={self.participation} below "
                f"min_client_fraction={self.min_client_fraction}: every "
                "round would fail its own survivor check — lower "
                "min_client_fraction to at most the participation rate"
            )
        if self.dp_clip < 0.0:
            raise ValueError(f"dp_clip={self.dp_clip} must be >= 0")
        if self.dp_noise_multiplier < 0.0:
            raise ValueError(
                f"dp_noise_multiplier={self.dp_noise_multiplier} must be >= 0"
            )
        if self.dp_noise_multiplier > 0.0 and self.dp_clip == 0.0:
            raise ValueError(
                "dp_noise_multiplier > 0 requires dp_clip > 0: the noise "
                "std is calibrated to the clip norm (sensitivity)"
            )
        if self.dp_clip > 0.0 and self.weighted:
            raise ValueError(
                "dp_clip > 0 is incompatible with weighted FedAvg: the DP "
                "sensitivity bound assumes a uniform mean over participants"
            )
        if self.server_opt not in ("none", "momentum", "adam", "yogi"):
            raise ValueError(
                f"unknown server_opt {self.server_opt!r} "
                "(none|momentum|adam|yogi)"
            )
        if self.server_lr <= 0.0:
            raise ValueError(f"server_lr={self.server_lr} must be > 0")
        if not 0.0 <= self.server_momentum < 1.0:
            raise ValueError(
                f"server_momentum={self.server_momentum} must be in [0, 1) "
                "(a decay >= 1 amplifies every round update geometrically)"
            )


@dataclass(frozen=True)
class DistillConfig:
    """Knowledge distillation (teacher -> student).

    The reference consumes a pre-distilled encoder (HF DistilBERT,
    client1.py:56) but has no distillation capability of its own. Here the
    DistilBERT recipe is first-class: soft-target KL at temperature T plus
    hard-label CE, with the student optionally initialized from every other
    teacher layer (the published DistilBERT init).
    """

    temperature: float = 2.0
    # Loss = alpha * T^2 * KL(teacher || student) + (1 - alpha) * CE(labels).
    alpha: float = 0.5
    # Initialize the student from evenly-strided teacher layers (DistilBERT
    # init: 12 -> 6 layers takes every other one). The stride is derived as
    # teacher_layers // student_layers by DistillTrainer.init_student_state,
    # not configured here; widths must match (depth-only distillation).
    init_from_teacher: bool = True

    def __post_init__(self) -> None:
        if not 0.0 <= self.alpha <= 1.0:
            raise ValueError(f"alpha={self.alpha} must be in [0, 1]")
        if self.temperature <= 0.0:
            raise ValueError(f"temperature={self.temperature} must be > 0")


@dataclass(frozen=True)
class ControlConfig:
    """Control-plane loop (control/ + registry/): the knobs of the
    unattended train -> gate -> promote -> serve -> monitor cycle.

    The reference has no loop at all — a round happens when a human
    re-runs three scripts, and nothing gates what the serving tier loads.
    """

    # Eval gate: a candidate must score >= incumbent[metric] - min_delta
    # on the held-out split or it is rejected (the serving pointer stays
    # on the incumbent — automatic rollback-by-refusal).
    gate_metric: str = "Accuracy"
    gate_min_delta: float = 0.0
    # Round cadence. min_interval_s throttles back-to-back rounds;
    # max_interval_s forces a round even when no drift fired (None = no
    # clock at all — purely drift-triggered once a monitor is attached).
    min_interval_s: float = 0.0
    max_interval_s: float | None = None
    # Drift monitor (control/drift.py): score-distribution shift of live
    # serving traffic vs the promoted artifact's eval reference
    # histogram. PSI > 0.25 is the classic "significant shift" bound.
    drift_method: str = "psi"  # psi | ks
    drift_threshold: float = 0.25
    drift_min_scores: int = 256
    # Histogram resolution for both the eval reference and the serving
    # tier's score export; both sides must agree.
    score_bins: int = 10
    # Per-round deadline handed to the TCP round engine (None = the
    # server's own timeout).
    round_deadline_s: float | None = None
    # Registry GC budget: after every promotion/rejection the controller
    # prunes oldest RETIRED/REJECTED artifacts beyond this count (the
    # serving artifact and its rollback chain are never pruned —
    # registry/store.py gc()). None (default) keeps everything.
    max_artifacts: int | None = None
    # Adaptive cadence (control/drift.py cadence_interval_s): a fired
    # drift verdict's MAGNITUDE scales the next inter-round interval
    # between min_interval_s (drift >= 2x threshold: urgent) and
    # max_interval_s (barely over threshold: relaxed). Needs both bounds
    # configured; the chosen interval rides the drift-trigger span.
    adaptive_cadence: bool = False
    # SLO-driven actuation: while a round-duration burn alert FIRES on
    # the tailed alerts-JSONL (controller --slo-alerts-jsonl), the
    # round's straggler deadline is multiplied by this factor — a fleet
    # already blowing its round SLO should cut stragglers loose sooner,
    # not wait the full budget on them. 1.0 disables the tightening.
    slo_deadline_factor: float = 0.5
    # Drift-scaled cohort (control/drift.py drift_cohort_fraction): a
    # fired drift verdict's MAGNITUDE picks the corrective round's
    # quorum between cohort_min_frac (barely over threshold: a lean,
    # fast cohort) and cohort_max_frac (>= 2x threshold: the full
    # quorum's evidence) of the server's configured min_clients — for
    # ONE round, then the base quorum restores.
    drift_cohort: bool = False
    cohort_min_frac: float = 0.5
    cohort_max_frac: float = 1.0

    def __post_init__(self) -> None:
        if not 0.0 < self.slo_deadline_factor <= 1.0:
            raise ValueError(
                f"slo_deadline_factor={self.slo_deadline_factor} must be "
                "in (0, 1] (1 = no tightening)"
            )
        if self.max_artifacts is not None and self.max_artifacts < 1:
            raise ValueError(
                f"max_artifacts={self.max_artifacts} must be >= 1 "
                "(or None to keep everything)"
            )
        if self.drift_method not in ("psi", "ks"):
            raise ValueError(
                f"drift_method={self.drift_method!r} must be 'psi' or 'ks'"
            )
        if self.drift_threshold <= 0.0:
            raise ValueError(
                f"drift_threshold={self.drift_threshold} must be > 0"
            )
        if self.drift_min_scores < 1:
            raise ValueError(
                f"drift_min_scores={self.drift_min_scores} must be >= 1"
            )
        if not 2 <= self.score_bins <= 64:
            # Upper bound matches the metrics-JSONL short-list cap
            # (reporting.append_metrics_jsonl keeps lists <= 64 entries):
            # a larger histogram would be silently dropped from every
            # serve_batch record and starve the drift monitor.
            raise ValueError(
                f"score_bins={self.score_bins} must be in [2, 64]"
            )
        if self.min_interval_s < 0.0:
            raise ValueError(
                f"min_interval_s={self.min_interval_s} must be >= 0"
            )
        if (
            self.max_interval_s is not None
            and self.max_interval_s < self.min_interval_s
        ):
            raise ValueError(
                f"max_interval_s={self.max_interval_s} below "
                f"min_interval_s={self.min_interval_s}"
            )
        if not 0.0 < self.cohort_min_frac <= 1.0:
            raise ValueError(
                f"cohort_min_frac={self.cohort_min_frac} must be in (0, 1]"
            )
        if not 0.0 < self.cohort_max_frac <= 1.0:
            raise ValueError(
                f"cohort_max_frac={self.cohort_max_frac} must be in (0, 1]"
            )
        if self.cohort_max_frac < self.cohort_min_frac:
            raise ValueError(
                f"cohort_max_frac={self.cohort_max_frac} below "
                f"cohort_min_frac={self.cohort_min_frac}"
            )


@dataclass(frozen=True)
class LabelsConfig:
    """Delayed ground-truth plane (labels/): the journal of late-arriving
    verdicts about what each scored flow actually WAS, the deterministic
    join against what the models ANSWERED, and the supervised promotion
    rung the join feeds. The reference has no feedback path at all once
    a model serves — nothing ever tells it it was wrong."""

    #: Ground-truth journal override (default:
    #: ``<registry>/labels/journal.jsonl`` — labels/store.journal_path).
    journal: str | None = None
    #: Decision threshold the join applies to both models' probabilities.
    threshold: float = 0.5
    #: Minimum joined (labeled) flows before the supervised gate may
    #: rule; fewer FAILS CLOSED.
    min_joined: int = 32
    #: Minimum joined/total coverage of the scored population; below it
    #: the gate FAILS CLOSED (a verdict over a sliver is noise).
    coverage_floor: float = 0.05
    #: Max tolerated candidate-over-serving supervised error excess.
    max_regression: float = 0.0
    #: Supervised drift margin (control/drift.py ErrorRateMonitor): the
    #: serving model's joined error rising this far past its promoted
    #: reference fires a corrective round.
    error_margin: float = 0.05
    #: Joined observations the error monitor needs before it may fire.
    error_min_joined: int = 64

    def __post_init__(self) -> None:
        if not 0.0 < self.threshold < 1.0:
            raise ValueError(
                f"threshold={self.threshold} must be in (0, 1)"
            )
        if self.min_joined < 1:
            raise ValueError(f"min_joined={self.min_joined} must be >= 1")
        if not 0.0 <= self.coverage_floor <= 1.0:
            raise ValueError(
                f"coverage_floor={self.coverage_floor} must be in [0, 1]"
            )
        if self.max_regression < 0.0:
            raise ValueError(
                f"max_regression={self.max_regression} must be >= 0"
            )
        if self.error_margin <= 0.0:
            raise ValueError(
                f"error_margin={self.error_margin} must be > 0"
            )
        if self.error_min_joined < 1:
            raise ValueError(
                f"error_min_joined={self.error_min_joined} must be >= 1"
            )


@dataclass(frozen=True)
class ShadowConfig:
    """Shadow evaluation plane (shadow/): mirror a sampled fraction of
    live scoring traffic onto the registry's ``shadow``-state artifact
    and gate promotion on the measured live disagreement instead of
    offline eval alone. The reference (and the pre-shadow controller)
    promotes on held-out metrics only — exactly the gate that misses
    live-distribution drift."""

    #: Mirror stride: duplicate one live request in ``sample`` onto the
    #: shadow backend (deterministic counter stride, no RNG — the
    #: serve-batch trace-sampling discipline). 0 = shadow plane off.
    sample: int = 0
    #: Minimum mirrored pairs before the gate may rule; fewer at timeout
    #: FAILS CLOSED (the candidate is rejected, the pointer never moves).
    min_pairs: int = 256
    #: Max tolerated fraction of pairs whose thresholded prediction
    #: flipped between serving and shadow.
    max_flip_rate: float = 0.02
    #: Max tolerated PSI between the paired serving/shadow score
    #: histograms (the drift monitor's distance, same 0.25 lore).
    psi_threshold: float = 0.25
    #: Gate patience: how long the controller waits for the evidence.
    timeout_s: float = 600.0
    #: Seconds between the gate's status polls.
    poll_s: float = 0.5
    #: Prediction threshold the flip comparison applies on both sides.
    threshold: float = 0.5
    #: Histogram bins for the paired score distributions (must match the
    #: drift tier's resolution so PSI thresholds transfer).
    bins: int = 10
    #: Mirror queue bound: a full queue drops the mirror COPY — never
    #: delays or fails the live request.
    queue: int = 256

    def __post_init__(self) -> None:
        if self.sample < 0:
            raise ValueError(f"sample={self.sample} must be >= 0 (0 = off)")
        if self.min_pairs < 1:
            raise ValueError(f"min_pairs={self.min_pairs} must be >= 1")
        if not 0.0 <= self.max_flip_rate <= 1.0:
            raise ValueError(
                f"max_flip_rate={self.max_flip_rate} must be in [0, 1]"
            )
        if self.psi_threshold <= 0.0:
            raise ValueError(
                f"psi_threshold={self.psi_threshold} must be > 0"
            )
        if self.timeout_s <= 0.0:
            raise ValueError(f"timeout_s={self.timeout_s} must be > 0")
        if self.poll_s <= 0.0:
            raise ValueError(f"poll_s={self.poll_s} must be > 0")
        if not 0.0 < self.threshold < 1.0:
            raise ValueError(
                f"threshold={self.threshold} must be in (0, 1)"
            )
        if not 2 <= self.bins <= 64:
            raise ValueError(f"bins={self.bins} must be in [2, 64]")
        if self.queue < 1:
            raise ValueError(f"queue={self.queue} must be >= 1")


@dataclass(frozen=True)
class RouterConfig:
    """Serving replica fleet (router/): the knobs of the thin router and
    the rolling hot-reload manager behind ``fedtpu route`` / ``fedtpu
    fleet``. The reference serves nothing at all; the single-process
    ``infer-serve`` tier serves from one scorer — these knobs govern the
    tier that scales past it."""

    #: Local replicas ``fedtpu fleet`` spawns behind the router.
    replicas: int = 3
    #: Seconds between in-band stats() health probes per replica.
    probe_interval_s: float = 1.0
    #: Unanswered-probe age that ejects a replica from the pick set.
    probe_timeout_s: float = 5.0
    #: Rolling reload: how long to wait for one replica's in-flight
    #: requests to finish before swapping anyway.
    drain_timeout_s: float = 30.0
    #: Seconds between serving-pointer polls by the fleet manager.
    reload_poll_s: float = 2.0
    #: Router-side admission bound: a replica at this many in-flight
    #: requests leaves the pick set until replies drain it.
    max_inflight_per_replica: int = 1024

    def __post_init__(self) -> None:
        if self.replicas < 1:
            raise ValueError(f"replicas={self.replicas} must be >= 1")
        if self.probe_interval_s <= 0.0:
            raise ValueError(
                f"probe_interval_s={self.probe_interval_s} must be > 0"
            )
        if self.probe_timeout_s <= 0.0:
            raise ValueError(
                f"probe_timeout_s={self.probe_timeout_s} must be > 0"
            )
        if self.drain_timeout_s < 0.0:
            raise ValueError(
                f"drain_timeout_s={self.drain_timeout_s} must be >= 0"
            )
        if self.reload_poll_s <= 0.0:
            raise ValueError(
                f"reload_poll_s={self.reload_poll_s} must be > 0"
            )
        if self.max_inflight_per_replica < 1:
            raise ValueError(
                f"max_inflight_per_replica={self.max_inflight_per_replica} "
                "must be >= 1"
            )


@dataclass(frozen=True)
class ObsConfig:
    """Observability (obs/): cross-tier round tracing + /metrics.

    The reference's observability is timestamped prints and one-row CSVs
    (SURVEY.md §5). These knobs configure the structured upgrade; the
    matching CLI flags (``--trace-jsonl``, ``--metrics-port``) override
    per process.
    """

    #: Span events-JSONL path for THIS process (obs.trace.Tracer). None
    #: (default) = tracing off. Give every process its own file; `fedtpu
    #: obs timeline --trace-dir` merges a directory of them.
    trace_jsonl: str | None = None
    #: Prometheus text endpoint port (stdlib HTTP, GET /metrics). 0
    #: (default) = off — the endpoint binds nothing unless asked.
    metrics_port: int = 0
    #: Run identity stamped on every span and metrics record. None =
    #: FEDTPU_RUN_ID env var, else a fresh per-process id.
    run_id: str | None = None
    #: Span sampling rate for HIGH-RATE span streams (today: the serving
    #: tier's per-coalesced-batch ``serve-batch`` spans): emit one span
    #: per ~1/rate batches via a deterministic batch-counter stride (no
    #: RNG — reruns sample identically), each carrying
    #: ``sampled_batches`` so consumers can re-scale. 1.0 = every batch.
    #: Round-scoped spans (round/agg/wire-*) are never sampled — they
    #: are one-per-round by construction.
    trace_sample: float = 1.0
    #: Failure flight recorder (obs/flight.py): postmortem bundles land
    #: in this directory on round failure / eject storm / SLO page.
    #: None (default) = recorder off — no ring, no hot-path cost. The
    #: matching CLI flag is ``--flight-dir``.
    flight_dir: str | None = None
    #: Span-ring depth the flight recorder retains per process.
    flight_ring: int = 256
    #: Device performance plane (obs/profile.py): sample every Nth
    #: train/score step with fenced host/dispatch/device timers
    #: (``fedtpu_*_step_seconds`` histograms + span attrs). 0 (default)
    #: = off — the hot loops run the literal unprofiled path (no
    #: fences, no timer reads). The matching CLI flag is
    #: ``--profile-stride``; a deterministic counter stride, no RNG.
    profile_stride: int = 0
    #: Snapshot-JSONL retention cap in MB for the scrape hub / sentinel
    #: (``--snapshot-max-mb``): past this size the live file atomically
    #: rolls to ``<path>.1`` (at most ~2x the cap on disk). None
    #: (default) = unbounded, the pre-existing behavior.
    snapshot_max_mb: float | None = None
    #: Sentinel cadence (obs/sentinel.py): seconds between ticks of the
    #: ``fedtpu obs sentinel`` watch loop.
    sentinel_interval_s: float = 5.0
    #: Long-horizon retention ring rows kept (memory + --ring-jsonl).
    sentinel_ring_records: int = 512
    #: Ring rows pinned as the regression baseline window (the FIRST N
    #: retained — "how the fleet looked when watching began").
    sentinel_baseline_n: int = 8
    #: Current-window rows a trend check averages against the baseline.
    sentinel_window_n: int = 8
    #: A watched field regresses when its current-window mean moves past
    #: baseline * ratio (+ the field's absolute floor); round cadence
    #: fires on the inverse drop.
    sentinel_regression_ratio: float = 1.5

    def __post_init__(self) -> None:
        if not 0 <= self.metrics_port <= 65535:
            raise ValueError(
                f"metrics_port={self.metrics_port} must be a port in "
                "[0, 65535] (0 = off)"
            )
        if not 0.0 < self.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample={self.trace_sample} must be in (0, 1]"
            )
        if self.flight_ring < 1:
            raise ValueError(
                f"flight_ring={self.flight_ring} must be >= 1"
            )
        if self.profile_stride < 0:
            raise ValueError(
                f"profile_stride={self.profile_stride} must be >= 0 "
                "(0 = off)"
            )
        if self.snapshot_max_mb is not None and self.snapshot_max_mb <= 0:
            raise ValueError(
                f"snapshot_max_mb={self.snapshot_max_mb} must be > 0 "
                "(None = unbounded)"
            )
        if self.sentinel_interval_s <= 0:
            raise ValueError(
                f"sentinel_interval_s={self.sentinel_interval_s} must "
                "be > 0"
            )
        if self.sentinel_ring_records < max(
            self.sentinel_baseline_n, self.sentinel_window_n
        ):
            raise ValueError(
                f"sentinel_ring_records={self.sentinel_ring_records} "
                "must hold at least the baseline "
                f"({self.sentinel_baseline_n}) and current "
                f"({self.sentinel_window_n}) windows"
            )
        if self.sentinel_regression_ratio <= 1.0:
            raise ValueError(
                f"sentinel_regression_ratio="
                f"{self.sentinel_regression_ratio} must be > 1 (it "
                "multiplies the baseline mean)"
            )


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout.

    axes: ``clients`` — federated replicas (FedAvg collective rides this axis);
    ``data`` — per-client batch parallelism (grad psum rides this axis).
    A 1-sized axis is dropped from the physical mesh automatically.
    """

    clients: int = 2
    data: int = 1
    # Sequence-parallel axis (ring attention): >1 adds a third ``seq`` mesh
    # axis and routes `federated` through FedSeqTrainer (--seq-parallel N).
    # On the TCP tier, `client --data-parallel/--seq-parallel` reuses the
    # data/seq axes as that host's LOCAL mesh (train/client_mesh.py); the
    # clients axis is the wire there, not a mesh dimension.
    seq: int = 1
    # FSDP shard-at-rest on the TCP client's local mesh (`client
    # --data-parallel N --fsdp`, train/client_mesh.py FsdpMeshTrainer):
    # params AND optimizer state shard per-leaf over the `data` axis
    # (all-gather at use inside the jitted step, backward re-gathers via
    # remat, grads reduce-scatter) so per-chip static bytes scale ~1/N —
    # the big-model-client mode. Trajectory matches the replicated mesh
    # to fp32 reduction-order ulps.
    fsdp: bool = False
    axis_names: tuple[str, str] = ("clients", "data")

    def __post_init__(self) -> None:
        if self.clients < 1 or self.data < 1:
            raise ValueError(
                f"mesh axes must be >= 1 (clients={self.clients}, "
                f"data={self.data})"
            )
        if self.seq < 1:
            raise ValueError(f"mesh.seq={self.seq} must be >= 1")
        if self.fsdp and self.data < 2:
            raise ValueError(
                "mesh.fsdp needs data >= 2 (--data-parallel N): sharding "
                "the static state over one device is a no-op"
            )
        if self.fsdp and self.seq > 1:
            raise ValueError(
                "mesh.fsdp does not compose with seq > 1: the C=1 fedseq "
                "trainer owns the 3-axis layout (sharded-scorer/fedseq "
                "FSDP is the ROADMAP follow-up)"
            )


@dataclass(frozen=True)
class ExperimentConfig:
    model: ModelConfig | KimiLinearConfig | LagunaConfig | Qwen3NextConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    fed: FedConfig = field(default_factory=FedConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    distill: DistillConfig = field(default_factory=DistillConfig)
    control: ControlConfig = field(default_factory=ControlConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    router: RouterConfig = field(default_factory=RouterConfig)
    shadow: ShadowConfig = field(default_factory=ShadowConfig)
    labels: LabelsConfig = field(default_factory=LabelsConfig)
    output_dir: str = "outputs"
    checkpoint_dir: str | None = None

    def __post_init__(self) -> None:
        # Logical clients may exceed the mesh's clients axis (several client
        # replicas per device shard) but must tile it evenly.
        if self.fed.num_clients % self.mesh.clients:
            raise ValueError(
                f"fed.num_clients={self.fed.num_clients} must be a multiple of "
                f"mesh.clients={self.mesh.clients}; use ExperimentConfig.for_clients(n)"
            )
        if self.data.max_len != self.model.max_len:
            raise ValueError(
                f"data.max_len={self.data.max_len} != model.max_len="
                f"{self.model.max_len}: tokenized sequences must match the "
                "position-embedding table"
            )

    @classmethod
    def for_clients(cls, num_clients: int, data_parallel: int = 1, **kw: Any) -> "ExperimentConfig":
        """Consistent config for an N-client fleet on a clients×data mesh."""
        kw.setdefault("fed", FedConfig(num_clients=num_clients))
        kw.setdefault(
            "mesh", MeshConfig(clients=num_clients, data=data_parallel)
        )
        if kw["fed"].num_clients != num_clients:
            kw["fed"] = dataclasses.replace(kw["fed"], num_clients=num_clients)
        return cls(**kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ExperimentConfig":
        sections = {
            "model": MODEL_CONFIG_TYPES.get(
                dict(d.get("model", {})).get("family"), ModelConfig
            ),
            "data": DataConfig,
            "train": TrainConfig,
            "fed": FedConfig,
            "mesh": MeshConfig,
            "distill": DistillConfig,
            "control": ControlConfig,
            "obs": ObsConfig,
            "router": RouterConfig,
            "shadow": ShadowConfig,
            "labels": LabelsConfig,
        }
        scalars = ("output_dir", "checkpoint_dir")
        unknown_top = set(d) - set(sections) - set(scalars)
        if unknown_top:
            raise ValueError(f"unknown config sections: {sorted(unknown_top)}")

        def _mk(tp, key):
            sub = dict(d.get(key, {}))
            names = {f.name for f in dataclasses.fields(tp)}
            unknown = set(sub) - names
            if unknown:
                raise ValueError(f"unknown {key} config keys: {sorted(unknown)}")
            # JSON round-trips tuples as lists; restore tuple-typed fields so
            # frozen dataclasses stay hashable and equality survives to_dict().
            for k, v in sub.items():
                if isinstance(v, list):
                    sub[k] = tuple(v)
            return tp(**sub)

        kw: dict[str, Any] = {key: _mk(tp, key) for key, tp in sections.items()}
        for scalar in scalars:
            if scalar in d:
                kw[scalar] = d[scalar]
        return cls(**kw)

    @classmethod
    def from_checkpoint_dict(cls, d: Mapping[str, Any]) -> "ExperimentConfig":
        """``from_dict`` for a checkpoint's *recorded* config, applying the
        library defaults that were in force when old checkpoints were saved
        rather than today's: configs that predate the ``gelu`` field were
        trained under the then-default erf GELU, so an absent key means
        "exact", not the current ``tanh`` default."""
        model = dict(d.get("model", {}))
        if "gelu" not in model and "family" not in model:
            model["gelu"] = "exact"
        out = dict(d)
        out["model"] = model
        return cls.from_dict(out)
