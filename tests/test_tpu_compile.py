"""The kernels of a benchmark cell's path (the Pallas ones, and the blocked
sliding-window attention that is plain XLA), compiled at the cell's
widths for the chip the cells run on, without the chip: the installed TPU
compiler refuses here what it would refuse there (a block that does not fit
the tiling, more VMEM than a kernel may use). It compiles, it does not run:
nothing here is a result or a speed. All such compiles live in this one
file: the process that describes the topology holds the TPU's library."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import kda, moe
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.causal_attention import (
    BLOCK,
    causal_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "B, L, H, dk, dv",
    [(4, 64 * kda.CHUNK, 32, 128, 128), (1, 256 * kda.CHUNK, 32, 128, 128), (1, 64 * kda.CHUNK, 30, 96, 192)],
    ids=["kimilinear-4x4096", "qwen3next-1x16384", "olmohybrid-1x4096-96-192"],
)
def test_the_kda_gradient_compiles_for_the_v5e_at_the_cells_batches(one_chip, monkeypatch, B, L, H, dk, dv):
    """A step's batch of ``kimilinear-window-fit-l4k`` (4 rows of 64 chunks:
    the kernels read ``[4, 32, 64, 64, 128]``) and of
    ``qwen3next-window-fit-l16k`` (1 row of 256 chunks: ``[1, 32, 256, 64,
    128]``) as the mixers pass them, 32 heads of 128 / 128, bfloat16 products,
    under ``jax.grad``: the ``fwd`` rule's ``kda_fwd``, which also writes every
    chunk's starting state and inverse, and ONE ``kda_bwd`` that builds a
    chunk's pair matrices again and transposes the chunk in VMEM. What they
    ask of VMEM and of the tiling is refused here, not in the cell; nothing
    loops over rows or groups of heads, no substitution is left, and the only
    temporaries are the two residuals (537 MB of states, 268 MB of inverses:
    64 x 64 float32 in tiles of 128 lanes). ``olmohybrid-window-fit-l4k``'s
    (1 row of 64 chunks, 30 heads, keys of 96 and values of 192) runs the same
    kernels on operands widened to whole lanes (its states are 256 x 128),
    with its decay a scalar gate ``[B, H, L]``: the pair matrices of ``(K
    K^T) * D``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the wrapper asks whether to interpret
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bf16, f32 = jnp.bfloat16, jnp.float32
    g = arg(f32, B, H, L) if dk % 128 else arg(f32, B, H, L, dk)
    args = (arg(f32, B, H, L, dk), arg(f32, B, H, L, dk), arg(bf16, B, H, L, dv), g, arg(f32, B, H, L))

    def grads(q, k, v, g, beta, cot):
        return jax.grad(lambda *a: (kda.kda_chunked(*a, dtype=bf16) * cot).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    compiled = jax.jit(grads).lower(*args, arg(f32, B, H, L, dv)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 and "kda_fwd" in text and "kda_bwd" in text
    assert "triangular" not in text and "while" not in text
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731
    residuals = B * H * (L // kda.CHUNK) * (lanes(dk) * lanes(dv) + kda.CHUNK * 128) * 4
    # beside them, at widths that are not whole lanes, the operands' and the gradients' widened copies: at
    # 96 / 192 nine arrays of [1, 30, 4096, 128] float32 (0.57 GB beside 0.31 GB of residuals)
    widened = 10 * B * H * L * lanes(dk) * 4 if dk % 128 or dv % 128 else 0
    assert compiled.memory_analysis().temp_size_in_bytes < 1.01 * residuals + widened


def test_the_undifferentiated_kda_forward_compiles_for_the_v5e_at_the_cells_widths(one_chip, monkeypatch):
    """A step's batch of ``kimilinear-window-fit-l4k`` as the mixer passes it:
    4 rows, 32 heads of 128, 64 chunks of 64 tokens; ``q``, ``k`` and the
    log-decay float32, ``v`` in the products' bfloat16. The whole of a chunk
    (pair matrices, inverse, recurrence) is one Mosaic kernel, so what it asks
    of VMEM and of the tiling is refused here, not in the cell."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, L, d = 4, 32, 64 * kda.CHUNK, 128
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (arg(f32, B, H, L, d), arg(f32, B, H, L, d), arg(bf16, B, H, L, d), arg(f32, B, H, L, d), arg(f32, B, H, L))
    compiled = jax.jit(lambda *a: kda.kda_chunked(*a, dtype=bf16)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "kda_fwd" in text
    assert "triangular" not in text and "while" not in text  # nothing of the XLA half, no loop over the rows
    assert compiled.memory_analysis().temp_size_in_bytes == 0  # the operands are read in place


@pytest.mark.parametrize(
    "kind, B, heads, Hkv, L, dqk, dv, window",
    [
        ("window", 2, 64, 8, 8192, 128, 128, 512),
        ("full", 2, 48, 8, 8192, 128, 128, None),
        ("full", 2, 64, 8, 8192, 128, 128, None),
        ("full", 4, 32, 32, 4096, 192, 128, None),
        ("streamed", 1, 16, 2, 16384, 256, 256, None),
        ("full", 1, 30, 30, 4096, 128, 128, None),
    ],
    ids=["window", "full-48-over-8", "full-64-over-8", "latent-192-128", "gated-256-at-16k", "nope-30-at-4k"],
)
def test_the_blocked_attention_compiles_for_the_v5e_at_the_cells_widths(
    one_chip, monkeypatch, kind, B, heads, Hkv, L, dqk, dv, window
):
    """A step's batch of ``laguna-window-fit-l8k`` as a layer passes it (2
    rows of 8,192 tokens, 64 or 48 query heads over 8 key/value heads of 128)
    and of ``kimilinear-window-fit-l4k``'s latent attention (4 rows of 4,096
    tokens, 32 heads, 192-wide keys and 128-wide values), bfloat16, forward
    and gradient. A sliding layer is XLA blocks whose score products meet 768
    keys, not the row's 8,192, and fits beside the cell's 8 GB of state. A
    full layer and the latent attention are two Mosaic kernels, ``flash_fwd``
    and ONE ``flash_bwd``: what they ask of VMEM (a head's q, k, v, dO and
    three float32 accumulators) and of the tiling is refused here, not in the
    cell, and nothing of the XLA blocks is left in the program: no block of
    float32 scores, none of their loops. ``qwen3next-window-fit-l16k``'s gated
    attention (1 row of 16,384 tokens, 16 query heads over 2 key/value heads of
    256, the key mask) passes the resident kernels' VMEM budget and takes the
    three streamed kernels, ``flash_fwd_streamed``, ``flash_dkv_streamed`` and
    ``flash_dq_streamed``, which hold a few tiles of a head in VMEM whatever
    the length: no loop and no float32 score block in the program either."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the wrapper asks whether to interpret
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    mask = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=one_chip)

    def both(q, k, v, mask):
        loss = lambda q, k, v: causal_attention(q, k, v, mask, window).astype(jnp.float32).sum()  # noqa: E731
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    compiled = jax.jit(both).lower(arg(B, heads, L, dqk), arg(B, Hkv, L, dqk), arg(B, Hkv, L, dv), mask).compile()
    text = compiled.as_text()
    # the widest array a score product of the XLA blocks writes: [B, Hkv, G * BLOCK, keys]
    rows = heads // Hkv * BLOCK
    keys = {int(m.group(1)) for m in re.finditer(rf"\[{B},{Hkv},{rows},(\d+)\]", text)} - {dqk, dv}
    if kind == "window":
        assert compiled.memory_analysis().temp_size_in_bytes < 4e9
        assert keys and max(keys) == BLOCK + 512, sorted(keys)
        return
    kernels = ("flash_fwd_streamed", "flash_dkv_streamed", "flash_dq_streamed") if kind == "streamed" else ("flash_fwd", "flash_bwd")
    assert text.count('custom_call_target="tpu_custom_call"') == len(kernels), kernels
    assert all(f"/{name}/pallas_call" in text for name in kernels), kernels
    # a single row: the compiler drops the batch's axis of 1 from a block's scores
    keys |= {int(m.group(1)) for m in re.finditer(rf"f32\[{Hkv},{rows},(\d+)\]", text)} - {dqk, dv}
    assert not keys and "while" not in text, sorted(keys)
    # delta, the log-sum-exp and the key bias; of the latent attention also the copies a compile of the
    # function alone makes of its 192-wide parameters (the XLA blocks of PR 32: 2.0-2.5 GB, compiled here)
    assert compiled.memory_analysis().temp_size_in_bytes < (0.4e9 if dqk == dv else 1.2e9)


@pytest.mark.parametrize(
    "D, F, held, k, E",
    [(2304, 1024, 8, 8, 256), (2048, 512, 32, 8, 256), (2048, 512, 32, 10, 512)],
    ids=["kimilinear-8-of-256", "laguna-32-of-256", "qwen3next-32-of-512"],
)
def test_the_expert_buffers_ladder_compiles_for_the_v5e_and_holds_one_rungs_residuals(one_chip, D, F, held, k, E):
    """A step's 16,384 tokens through ``held_experts_ffn`` at the three window
    cells' widths (buffers of 16,384, 65,536 and 40,960 rows), float32 weights
    and bfloat16 products, value and gradient under the block's
    ``jax.checkpoint``: the pass over the buffer is a ``conditional`` of two
    branches in the forward pass and one in the backward rule (the
    recomputation's has no consumer), and the program's temporaries are the
    top rung's alone, give or take what the scheduler does with a switch. A
    ``switch`` differentiated by JAX hands back every rung's residuals from
    every branch and, with three rungs, held 3.5 times the top rung's here
    (5.26 GB against 1.49 at the Laguna cell's widths)."""
    import re

    T = 16384
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bf16, f32 = jnp.bfloat16, jnp.float32
    rungs = moe.expert_rungs(T, k, E, held)
    assert len(rungs) == 2 and rungs[-1] == moe.expert_capacity(T, k, E, held)

    def compiled(ladder):
        @jax.checkpoint
        def layer(x, scores, valid, wg, wu, wd):
            idx, w = moe.route_topk(scores, 0.0, k, 1.0)
            y, _, _, rows = moe.held_experts_ffn(
                x, idx, w, valid, wg, wu, wd, offset=0, capacity=rungs[-1], dtype=bf16, rungs=ladder
            )
            return y.astype(bf16), rows

        def both(x, scores, valid, wg, wu, wd, cot):
            loss = lambda x, scores, wg, wu, wd: (lambda y, rows: ((y * cot).astype(f32).sum(), rows))(  # noqa: E731
                *layer(x, scores, valid, wg, wu, wd)
            )
            return jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True)(x, scores, wg, wu, wd)

        return jax.jit(both).lower(
            arg(bf16, T, D), arg(f32, T, E), arg(jnp.bool_, T), arg(f32, held, D, F), arg(f32, held, D, F),
            arg(f32, held, F, D), arg(bf16, T, D),
        ).compile()

    whole, top = compiled(rungs), compiled(())
    # a switch of two is a conditional of a true and a false computation, of more one of ``branch_computations``
    branches = r"branch_computations=\{[^}]*\}|(?:true|false)_computation=%[\w.\-]+"
    switches = [line for line in whole.as_text().splitlines() if " conditional(" in line]
    assert [len(re.findall(r"%", "".join(re.findall(branches, line)))) for line in switches] == [2, 2]
    assert " conditional(" not in top.as_text()
    assert whole.memory_analysis().temp_size_in_bytes < 1.3 * top.memory_analysis().temp_size_in_bytes
