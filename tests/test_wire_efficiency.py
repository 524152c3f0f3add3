"""Wire efficiency (ISSUE 17): quantized streamed uploads, sparse relay
upward deltas, and the batched fold engines.

Contracts pinned here:

* The int8c codec round-trips within its per-chunk quantization step,
  handles denormal/inf/NaN chunks deterministically, and rejects
  malformed or poisoned payloads (non-finite scales) as WireError.
* Every fold engine (naive, blocked) is bit-exact against the reference
  ascending-id accumulation ``acc += float32(w_i) * leaf_i`` over
  shuffled arrival orders — the crc contract the streaming aggregator's
  batched fold must keep.
* A LIVE mixed fleet (int8 + bf16 + old-peer fp32 clients in one round)
  negotiates per-client upgrades one reply behind and the server's fold
  is crc-equal to the deterministic dequantization replay.
* ``--wire-dtype`` refuses the combinations that cannot keep their
  contracts (secure-agg, compressed uploads) and stays fp32 against a
  non-advertising server.
* Quantized uploads compose with central DP: the server holds lossy
  streamed leaves until the trailer, dequantizes, and RE-CLIPS before
  the fold (containment), bit-equal to the numpy replay.
* A relay with ``upward_topk`` goes dense on round 1, adopts the root
  aggregate as its delta base, and uploads sparse topk deltas upward
  from round 2 — with the root's aggregate bit-equal to the replay and
  the upward bytes collapsing.
* Server-side strategy optimizer state survives a restart via
  ``strategy_state_path``: the restarted root continues the momentum
  trajectory instead of re-adopting the mean.
"""

import threading

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
    AggregationServer,
    FederatedClient,
    RelayAggregator,
    StreamAgg,
    WireError,
    aggregate_flat,
    wire,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.quant import (
    QUANT_CHUNK_ELEMS,
    dequantize_int8c,
    int8c_nbytes,
    quantize_int8c,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import (
    fold,
)

pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning"
)


def _leaves(rng, n=4, shape=(32, 9), scale=1.0):
    return {
        f"w{i:02d}": rng.normal(size=shape).astype(np.float32) * scale
        for i in range(n)
    }


def _serve_rounds(server, n, results, key="aggs"):
    def _run():
        results[key] = [server.serve_round(deadline=30) for _ in range(n)]

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    return t


def _run_clients(clients, uploads, n_samples=None):
    results, errors = {}, []

    def go(cid):
        try:
            kw = {}
            if n_samples is not None:
                kw["n_samples"] = n_samples[cid]
            results[cid] = clients[cid].exchange(uploads[cid], **kw)
        except Exception as e:  # noqa: BLE001 - surfaced via the list
            errors.append((cid, e))

    threads = [
        threading.Thread(target=go, args=(cid,), daemon=True)
        for cid in clients
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    return results, errors


def _rt_int8(flat):
    """The deterministic server-side view of an int8-quantized upload."""
    return {
        k: dequantize_int8c(quantize_int8c(v), np.asarray(v).shape)
        for k, v in flat.items()
    }


def _rt_bf16(flat):
    return {
        k: wire.native.unpack_bf16(
            np.ascontiguousarray(wire.native.pack_bf16(v)),
            shape=np.asarray(v).shape,
        )
        for k, v in flat.items()
    }


# ------------------------------------------------------------ int8c codec
def test_int8c_roundtrip_within_quant_step(rng):
    for size in (1, 7, QUANT_CHUNK_ELEMS, QUANT_CHUNK_ELEMS + 1, 3 * 4096 + 5):
        arr = (rng.normal(size=size) * 3.0).astype(np.float32)
        raw = quantize_int8c(arr)
        assert len(raw) == int8c_nbytes(size)
        out = dequantize_int8c(raw, arr.shape)
        # Per chunk the max error is half the quantization step
        # (scale = amax/127; rint rounds to the nearest level).
        nchunks = -(-size // QUANT_CHUNK_ELEMS)
        pad = nchunks * QUANT_CHUNK_ELEMS - size
        a2 = np.pad(arr, (0, pad)).reshape(nchunks, QUANT_CHUNK_ELEMS)
        step = np.abs(a2).max(axis=1) / 127.0
        err = np.abs(
            np.pad(out - arr, (0, pad)).reshape(nchunks, QUANT_CHUNK_ELEMS)
        ).max(axis=1)
        assert np.all(err <= step / 2 + 1e-7)


def test_int8c_deterministic_and_shape_preserving(rng):
    arr = rng.normal(size=(33, 129)).astype(np.float32)
    raw1, raw2 = quantize_int8c(arr), quantize_int8c(arr)
    assert raw1 == raw2
    out1 = dequantize_int8c(raw1, arr.shape)
    out2 = dequantize_int8c(raw2, arr.shape)
    assert out1.shape == arr.shape
    np.testing.assert_array_equal(out1, out2)


def test_int8c_edge_chunks_stay_finite():
    # All-zero chunk: scale falls back to 1.0, decodes to exact zeros.
    zeros = np.zeros(10, np.float32)
    np.testing.assert_array_equal(
        dequantize_int8c(quantize_int8c(zeros), zeros.shape), zeros
    )
    # Denormal-only chunk: amax/127 underflows toward 0 — the fallback
    # keeps both directions finite (values quantize to 0 at scale 1.0).
    den = np.full(5, np.float32(1e-42))
    out = dequantize_int8c(quantize_int8c(den), den.shape)
    assert np.all(np.isfinite(out))
    # inf/NaN chunk: scale is non-finite -> fallback 1.0; NaN -> 0,
    # +/-inf saturate to +/-127. Deterministic, never NaN out.
    ugly = np.array([np.inf, -np.inf, np.nan, 2.5, -300.0], np.float32)
    out = dequantize_int8c(quantize_int8c(ugly), ugly.shape)
    np.testing.assert_array_equal(
        out, np.array([127.0, -127.0, 0.0, 2.0, -127.0], np.float32)
    )


def test_int8c_rejects_malformed_payloads(rng):
    arr = rng.normal(size=100).astype(np.float32)
    raw = quantize_int8c(arr)
    with pytest.raises(WireError, match="bytes"):
        dequantize_int8c(raw + b"x", arr.shape)
    with pytest.raises(WireError, match="bytes"):
        dequantize_int8c(raw[:-1], arr.shape)
    # Poisoned scale (NaN / negative): one crafted upload must not be
    # able to feed non-finite values into the round's running fold.
    for bad in (np.float32(np.nan), np.float32(-1.0), np.float32(0.0)):
        poisoned = bad.tobytes() + raw[4:]
        with pytest.raises(WireError, match="scale"):
            dequantize_int8c(poisoned, arr.shape)


# ------------------------------------------------------------ fold engines
def test_fold_engines_bit_exact_property(rng):
    """naive / blocked / fold_ordered agree BIT-exactly with the
    reference ascending accumulation — across sizes straddling the cache
    block, ill-conditioned scales, and shuffled upload arrival orders
    (arrival never changes fold order; StreamAgg sorts by id)."""
    for _ in range(6):
        k = int(rng.integers(1, 9))
        n = int(rng.integers(1, 3 * fold.FOLD_BLOCK_ELEMS))
        shape = (n,) if n % 2 else (2, n // 2)
        leaves = [
            (rng.normal(size=shape) * 10.0 ** rng.integers(-4, 5)).astype(
                np.float32
            )
            for _ in range(k)
        ]
        weights = [np.float32(w) for w in rng.random(k) + 0.05]
        ref = np.zeros(shape, np.float32)
        for w, a in zip(weights, leaves):
            ref += np.float32(w) * a
        flat = [a.reshape(-1) for a in leaves]
        np.testing.assert_array_equal(fold.fold_naive(flat, weights).reshape(shape), ref)
        np.testing.assert_array_equal(
            fold.fold_blocked(flat, weights).reshape(shape), ref
        )
        # Odd block size: partial tail blocks must not change any bit.
        np.testing.assert_array_equal(
            fold.fold_blocked(flat, weights, block=1000).reshape(shape), ref
        )
        np.testing.assert_array_equal(
            fold.fold_ordered(leaves, weights, engine="blocked"), ref
        )
        np.testing.assert_array_equal(
            fold.fold_ordered(leaves, weights, engine="naive"), ref
        )


def test_streamagg_batched_fold_one_crc_over_arrival_orders(rng):
    """The StreamAgg fold (now batched through fold_ordered) still yields
    ONE crc over shuffled arrival orders, equal to the barrier mean."""
    n = 8
    keys = [f"w{i}" for i in range(3)]
    models = [
        {k: rng.normal(size=(64, 33)).astype(np.float32) for k in keys}
        for _ in range(n)
    ]
    weights = [float(w) for w in rng.integers(1, 9, size=n)]

    def crc(order):
        st = StreamAgg()
        for cid in order:
            st.register(cid, keys=keys, n_samples=weights[cid])
        st.freeze(list(range(n)), weights)
        for cid in order:
            st.add_dense(cid, models[cid])
        return wire.flat_crc32(st.finalize(list(range(n)), weights))

    orders = [list(range(n))]
    for _ in range(3):
        o = list(range(n))
        rng.shuffle(o)
        orders.append(o)
    crcs = {crc(o) for o in orders}
    assert len(crcs) == 1
    want = aggregate_flat(models, weights)
    assert crcs == {wire.flat_crc32(want)}


def test_fold_engine_env_override(monkeypatch):
    for refused in ("gpu", "pallas"):
        monkeypatch.setenv("FEDTPU_FOLD_ENGINE", refused)
        with pytest.raises(ValueError, match="FEDTPU_FOLD_ENGINE"):
            fold._pick_engine()
    monkeypatch.setenv("FEDTPU_FOLD_ENGINE", "naive")
    assert fold._pick_engine() == "naive"
    monkeypatch.delenv("FEDTPU_FOLD_ENGINE")
    assert fold._pick_engine() == "blocked"


# ------------------------------------------------- wire-dtype negotiation
def test_wire_dtype_refusal_matrix():
    # Lossy dtypes refuse secure-agg (masked ring elements cannot be
    # re-quantized) and any compressed upload (one encoding per wire).
    with pytest.raises(ValueError, match="secure"):
        FederatedClient(
            "127.0.0.1", 1, client_id=0, wire_dtype="int8",
            secure_agg=True, num_clients=2,
        )
    for comp in ("topk:0.1", "bf16", "int8"):
        with pytest.raises(ValueError, match="compression"):
            FederatedClient(
                "127.0.0.1", 1, client_id=0, wire_dtype="bf16",
                compression=comp,
            )
    with pytest.raises(ValueError, match="wire_dtype"):
        FederatedClient("127.0.0.1", 1, client_id=0, wire_dtype="fp16")
    # fp32 (the default) composes with everything — no constructor error.
    FederatedClient(
        "127.0.0.1", 1, client_id=0, wire_dtype="fp32",
        compression="topk:0.1",
    )


def test_wire_dtype_stays_fp32_against_old_server(rng):
    """A non-streaming server never adverts decodable encodings: the
    int8 client keeps the fp32 wire and the aggregate is exact."""
    models = [_leaves(rng, n=2)]
    results = {}
    with AggregationServer(
        port=0, num_clients=1, timeout=30, stream_chunk_bytes=0
    ) as server:
        client = FederatedClient(
            "127.0.0.1", server.port, client_id=0, timeout=30,
            wire_dtype="int8",
        )
        t = _serve_rounds(server, 2, results)
        for _ in range(2):
            agg = client.exchange(models[0])
            assert client.last_wire_dtype == "fp32"
            assert wire.flat_crc32(agg) == wire.flat_crc32(
                aggregate_flat(models)
            )
        t.join(timeout=30)


def test_mixed_fleet_quantized_round_crc_pinned(rng):
    """int8 + bf16 + old-peer fp32 clients in one live streamed fleet:
    round 1 is all-fp32 (negotiation is one reply behind), round 2 the
    capable clients upgrade, and the server's fold is crc-equal to the
    deterministic dequantization replay — ``fleet_crc_exact`` extends to
    quantized rounds."""
    models1 = [_leaves(rng, n=3, shape=(40, 30)) for _ in range(3)]
    models2 = [_leaves(rng, n=3, shape=(40, 30)) for _ in range(3)]
    results = {}
    with AggregationServer(
        port=0, num_clients=3, timeout=30, stream_chunk_bytes=1 << 10
    ) as server:
        clients = {
            0: FederatedClient(
                "127.0.0.1", server.port, client_id=0, timeout=30,
                wire_dtype="int8",
            ),
            1: FederatedClient(
                "127.0.0.1", server.port, client_id=1, timeout=30,
            ),
            2: FederatedClient(
                "127.0.0.1", server.port, client_id=2, timeout=30,
                wire_dtype="bf16",
            ),
        }
        t = _serve_rounds(server, 2, results)
        r1, errors = _run_clients(clients, models1)
        assert not errors, errors
        # Round 1: nobody had the advert yet — all fp32, exact mean.
        assert {c.last_wire_dtype for c in clients.values()} == {"fp32"}
        want1 = aggregate_flat(models1)
        for cid in clients:
            assert wire.flat_crc32(r1[cid]) == wire.flat_crc32(want1)
        fp32_bytes = clients[0].last_upload_bytes
        r2, errors = _run_clients(clients, models2)
        t.join(timeout=60)
        assert not errors, errors
        assert clients[0].last_wire_dtype == "int8"
        assert clients[1].last_wire_dtype == "fp32"
        assert clients[2].last_wire_dtype == "bf16"
        # The acceptance floor: int8 streamed uploads >= 3x smaller.
        assert clients[0].last_upload_bytes * 3 < fp32_bytes
        # Deterministic replay: the server folded each client's DECODED
        # leaves — identical to quant/dequant (or bf16) round-trips.
        want2 = aggregate_flat(
            [_rt_int8(models2[0]), models2[1], _rt_bf16(models2[2])]
        )
        for cid in clients:
            assert wire.flat_crc32(r2[cid]) == wire.flat_crc32(want2)
        assert server.stream_totals["fold_engine"] == fold.engine_name()


def test_reply_dtype_refusal_matrix():
    """The reply leg mirrors the upload leg's composition rules: lossy
    reply dtypes refuse secure-agg (the unmask release is bit-exact by
    contract) and any reply compression (one encoder per leg)."""
    with pytest.raises(ValueError, match="reply_dtype"):
        AggregationServer(port=0, num_clients=1, reply_dtype="fp16")
    with pytest.raises(ValueError, match="secure"):
        AggregationServer(
            port=0, num_clients=2, secure_agg=True, reply_dtype="bf16"
        )
    with pytest.raises(ValueError, match="two encoders"):
        AggregationServer(
            port=0, num_clients=1, compression="bf16", reply_dtype="int8"
        )
    # fp32 (the default) composes with everything.
    with AggregationServer(
        port=0, num_clients=1, secure_agg=False, reply_dtype="fp32"
    ):
        pass


def test_reply_dtype_quantizes_streamed_replies_capability_gated(rng):
    """``serve --reply-dtype bf16``: a streaming client that adverts
    decodable reply encodings gets the quantized streamed reply (its
    aggregate is the bf16 round-trip of the fold — deterministic
    dequantization replay), while an old peer that never streams keeps
    the dense fp32 reply, exact — in the SAME round."""
    models = [_leaves(rng, n=3), _leaves(rng, n=3)]
    results = {}
    with AggregationServer(
        port=0, num_clients=2, timeout=30,
        stream_chunk_bytes=1 << 10, reply_dtype="bf16",
    ) as server:
        clients = {
            0: FederatedClient(
                "127.0.0.1", server.port, client_id=0, timeout=30,
            ),
            # "Old SDK": never streams, so it neither adverts reply
            # encodings nor receives a streamed (quantizable) reply.
            1: FederatedClient(
                "127.0.0.1", server.port, client_id=1, timeout=30,
                stream=False,
            ),
        }
        t = _serve_rounds(server, 1, results)
        aggs, errors = _run_clients(clients, models)
        t.join(timeout=60)
        assert not errors, errors
    exact = aggregate_flat(models)
    # Streaming client: every reply leaf rode the wire as bf16.
    assert wire.flat_crc32(aggs[0]) == wire.flat_crc32(_rt_bf16(exact))
    assert wire.flat_crc32(aggs[0]) != wire.flat_crc32(exact)
    # Dense client: byte-exact fp32, byte-identical to a quant-less round.
    assert wire.flat_crc32(aggs[1]) == wire.flat_crc32(exact)


def test_quantized_dp_upload_is_reclipped(rng):
    """int8 + central DP: the server holds the lossy streamed delta
    until the trailer, dequantizes, re-clips, and only then folds —
    bit-equal to the numpy replay (containment, not refusal)."""
    clip = 0.05
    base0 = _leaves(rng, n=2, shape=(30, 20))
    p1 = {k: v + rng.normal(size=v.shape).astype(np.float32) for k, v in base0.items()}
    results = {}
    with AggregationServer(
        port=0, num_clients=1, timeout=30, dp_clip=clip,
        stream_chunk_bytes=1 << 10,
    ) as server:
        client = FederatedClient(
            "127.0.0.1", server.port, client_id=0, timeout=30,
            wire_dtype="int8", dp=True,
        )
        t = _serve_rounds(server, 2, results)
        agg1 = client.exchange(p1, round_base=base0)
        # Round 2: the upload is the quantized clipped delta.
        p2 = {
            k: np.asarray(v, np.float32)
            + rng.normal(size=v.shape).astype(np.float32)
            for k, v in agg1.items()
        }
        agg2 = client.exchange(p2, round_base=agg1)
        t.join(timeout=30)
        assert client.last_wire_dtype == "int8"
    # Replay: client clips, the wire quantizes, the server dequantizes
    # and RE-clips (quantization error can push the norm back over the
    # bound) before folding onto the round base.
    delta = {
        k: np.asarray(p2[k], np.float32) - np.asarray(agg1[k], np.float32)
        for k in p2
    }
    clipped, _, _ = wire.clip_flat(delta, clip)
    rt = _rt_int8(clipped)
    if wire.flat_l2_norm(rt) > clip:
        rt, _, _ = wire.clip_flat(rt, clip)
    expected = {
        k: np.float32(1.0) * (np.asarray(agg1[k], np.float32) + rt[k])
        for k in rt
    }
    assert wire.flat_crc32(agg2) == wire.flat_crc32(expected)


# ----------------------------------------------------- sparse upward hops
def test_relay_upward_topk_refuses_topk_leaf_compression():
    with pytest.raises(ValueError, match="upward"):
        RelayAggregator(
            "127.0.0.1", 0, parent_host="127.0.0.1", parent_port=1,
            relay_id=0, num_clients=1, compression="topk:0.1",
            upward_topk=0.1,
        )
    with pytest.raises(WireError):
        RelayAggregator(
            "127.0.0.1", 0, parent_host="127.0.0.1", parent_port=1,
            relay_id=0, num_clients=1, upward_topk=1.5,
        )


def test_relay_sparse_upward_round2_base_agreement(rng):
    """Relay with upward_topk behind a lossless root: round 1 goes up
    dense (no base), the relay adopts the root aggregate as its delta
    base, and the round-2 upward hop is a topk delta — with the root's
    round-2 aggregate bit-equal to the replay and upward bytes
    collapsing even though the LEAVES uploaded dense."""
    frac = 0.05
    models1 = [_leaves(rng, n=3, shape=(64, 32)) for _ in range(2)]
    models2 = [_leaves(rng, n=3, shape=(64, 32)) for _ in range(2)]
    root_out = {}
    with AggregationServer(
        port=0, num_clients=1, weighted=True, timeout=30,
        stream_chunk_bytes=1 << 10,
    ) as root:
        relay = RelayAggregator(
            "127.0.0.1", 0, parent_host="127.0.0.1",
            parent_port=root.port, relay_id=0, num_clients=2,
            timeout=30, stream_chunk_bytes=1 << 10, upward_topk=frac,
        )
        try:
            rt = _serve_rounds(root, 2, root_out)
            threading.Thread(
                target=relay.serve, args=(2,), daemon=True
            ).start()
            clients = {
                cid: FederatedClient(
                    "127.0.0.1", relay.port, client_id=cid, timeout=30
                )
                for cid in range(2)
            }
            r1, errors = _run_clients(clients, models1)
            assert not errors, errors
            ub1 = relay.upward_bytes
            assert ub1 > 0
            # The relay's parent leg adopted the root aggregate as base.
            assert relay.parent._base is not None
            r2, errors = _run_clients(clients, models2)
            rt.join(timeout=60)
            assert not errors, errors
            ub2 = relay.upward_bytes - ub1
        finally:
            relay.close()
    # Round 1 is the plain subtree mean, bit-exact through the tree.
    want1 = aggregate_flat(models1)
    assert wire.flat_crc32(r1[0]) == wire.flat_crc32(want1)
    # Round-2 replay: subtree partial folds dense; the upward hop sends
    # topk(partial - base) per leaf (error-feedback residual is zero on
    # the first sparse round); the root reconstructs base + densify.
    partial2 = aggregate_flat(models2)
    sent = {}
    for k in sorted(partial2):
        d = partial2[k] - np.asarray(want1[k], np.float32)
        sent[k] = wire.densify_topk(wire.sparsify_topk(d, frac), d.shape)
    expected2 = {
        k: np.float32(1.0) * (np.asarray(want1[k], np.float32) + sent[k])
        for k in sorted(partial2)
    }
    for cid in (0, 1):
        assert wire.flat_crc32(r2[cid]) == wire.flat_crc32(expected2)
    # The whole point: the upward hop collapsed (>= 3x at frac=0.05).
    assert ub2 * 3 < ub1, (ub1, ub2)


# --------------------------------------------- strategy-state persistence
def test_strategy_state_survives_server_restart(rng, tmp_path):
    """PR 16 residual closed: a restarted root with strategy_state_path
    resumes the momentum trajectory (prev global + optimizer state)
    instead of re-adopting the bare mean."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.strategies import (
        make_strategy,
    )

    path = str(tmp_path / "strategy_state.npz")
    ms = [_leaves(rng, n=2, shape=(12, 6)) for _ in range(3)]
    results = {}
    with AggregationServer(
        port=0, num_clients=1, timeout=30, strategy="momentum",
        strategy_state_path=path,
    ) as srv1:
        client = FederatedClient(
            "127.0.0.1", srv1.port, client_id=0, timeout=30
        )
        t = _serve_rounds(srv1, 2, results)
        a1 = client.exchange(ms[0])
        a2 = client.exchange(ms[1])
        t.join(timeout=30)
    # close() drained the persist thread: the snapshot is on disk.
    assert (tmp_path / "strategy_state.npz").exists()

    with AggregationServer(
        port=0, num_clients=1, timeout=30, strategy="momentum",
        strategy_state_path=path,
    ) as srv2:
        # The restart restored the post-strategy global and advanced the
        # round counter past the persisted round.
        assert srv2._last_agg is not None
        assert srv2._round_counter == srv2._last_agg_round + 1
        client = FederatedClient(
            "127.0.0.1", srv2.port, client_id=0, timeout=30
        )
        t = _serve_rounds(srv2, 1, results, key="r3")
        a3 = client.exchange(ms[2])
        t.join(timeout=30)

    # Replay the CONTINUOUS trajectory with one strategy instance.
    s = make_strategy("momentum")
    e1 = s.apply(None, ms[0], round_no=0)
    e2 = s.apply(e1, ms[1], round_no=1)
    e3 = s.apply(e2, ms[2], round_no=2)
    assert wire.flat_crc32(a1) == wire.flat_crc32(e1)
    assert wire.flat_crc32(a2) == wire.flat_crc32(e2)
    assert wire.flat_crc32(a3) == wire.flat_crc32(e3)
    # And the trajectory genuinely differs from re-adopting the mean —
    # the failure mode this satellite closes.
    assert wire.flat_crc32(a3) != wire.flat_crc32(ms[2])


def test_strategy_state_mismatch_starts_fresh(rng, tmp_path):
    """A persisted snapshot from a DIFFERENT strategy is ignored (warn +
    fresh start), never misapplied."""
    path = str(tmp_path / "strategy_state.npz")
    ms = [_leaves(rng, n=2, shape=(8, 4)) for _ in range(2)]
    results = {}
    with AggregationServer(
        port=0, num_clients=1, timeout=30, strategy="momentum",
        strategy_state_path=path,
    ) as srv1:
        client = FederatedClient(
            "127.0.0.1", srv1.port, client_id=0, timeout=30
        )
        t = _serve_rounds(srv1, 2, results)
        client.exchange(ms[0])
        client.exchange(ms[1])
        t.join(timeout=30)
    with AggregationServer(
        port=0, num_clients=1, timeout=30, strategy="fedavg",
        strategy_state_path=path,
    ) as srv2:
        assert srv2._last_agg is None
        assert srv2._round_counter == 0
