"""Decode engine: paged-KV allocator, paged-attention conformance,
bucketed prefill, token-level continuous batching, fleet failover.

Layers under test, bottom up: `KVBlockAllocator` (free-list invariants,
all-or-nothing exhaustion), `PagedKVCache` (page writes, block tables,
int8 page parity), the `paged_attention` kernel pair (Pallas-in-interpret
== jnp reference — the PR 13 two-implementation contract), the
`DecodeEngine` loop (zero fresh compiles after warmup, mid-flight
admit/retire, exhaustion sheds), the `ContinuousBatcher.cancel` slot
release, and `ModelFleet.deploy_decode`/`generate` failover
(restart-and-count, heal via the controller)."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from deeplearning4j_tpu.compile.fingerprint import model_fingerprint
from deeplearning4j_tpu.ops.pallas import dispatch as kd
from deeplearning4j_tpu.ops.pallas import paged_attention as pa
from deeplearning4j_tpu.serving.batcher import (ContinuousBatcher,
                                                RejectedError)
from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                               KVBlockAllocator,
                                               KVCacheExhausted,
                                               PagedKVCache,
                                               TinyDecodeModel)


@pytest.fixture(autouse=True)
def _reset_kernel_tier():
    yield
    kd.reset()


def _random_paged(B=3, H=2, D=64, page=8, n_pages=16, max_pages=4,
                  dtype="f32", seed=0):
    """Random paged-attention inputs with ragged per-sequence lengths."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k = rng.standard_normal((n_pages, page, H, D)).astype(np.float32)
    v = rng.standard_normal((n_pages, page, H, D)).astype(np.float32)
    # ragged: lengths 1, mid, full
    seq_lens = np.array([1, page * max_pages // 2 + 3,
                         page * max_pages][:B], np.int32)
    bt = np.zeros((B, max_pages), np.int32)
    used = iter(rng.permutation(n_pages))      # distinct physical pages
    for b in range(B):
        n = -(-int(seq_lens[b]) // page)
        bt[b, :n] = [next(used) for _ in range(n)]
    if dtype == "int8":
        from deeplearning4j_tpu.ops.quant_kernels import quantize_tensor
        ks = np.ones((n_pages, page, H), np.float32)
        vs = np.ones((n_pages, page, H), np.float32)
        k8 = np.zeros((n_pages, page, H, D), np.int8)
        v8 = np.zeros((n_pages, page, H, D), np.int8)
        for p in range(n_pages):
            for s in range(page):
                qt = quantize_tensor(k[p, s], axis=0)
                k8[p, s] = np.asarray(qt.q)
                ks[p, s] = np.asarray(qt.scale).reshape(-1)
                qt = quantize_tensor(v[p, s], axis=0)
                v8[p, s] = np.asarray(qt.q)
                vs[p, s] = np.asarray(qt.scale).reshape(-1)
        return q, k8, v8, bt, seq_lens, ks, vs, k, v
    return q, k, v, bt, seq_lens, None, None, k, v


def _tiny(seed=0):
    return TinyDecodeModel(vocab=48, d_model=32, n_heads=2, seed=seed)


def _engine(model=None, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("max_decode_batch", 4)
    kw.setdefault("model_label", "t")
    return DecodeEngine(model if model is not None else _tiny(), **kw)


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

class TestKVBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = KVBlockAllocator(8)
        blocks = a.alloc(5)
        assert len(blocks) == len(set(blocks)) == 5
        assert a.in_use == 5 and a.free_count == 3
        a.free(blocks[:2])
        assert a.in_use == 3 and a.free_count == 5
        assert a.high_water == 5

    def test_exhaustion_is_all_or_nothing(self):
        a = KVBlockAllocator(4)
        a.alloc(3)
        with pytest.raises(KVCacheExhausted):
            a.alloc(2)                    # only 1 free: nothing taken
        assert a.free_count == 1          # the failed alloc left it intact
        assert len(a.alloc(1)) == 1

    def test_exhaustion_is_rejected_error(self):
        # shed-not-crash: admission control catches RejectedError
        assert issubclass(KVCacheExhausted, RejectedError)

    def test_double_free_raises(self):
        a = KVBlockAllocator(4)
        b = a.alloc(2)
        a.free(b)
        with pytest.raises(ValueError, match="double free"):
            a.free([b[0]])

    def test_fragmented_free_order_reuses_any_page(self):
        # free pages out of order, then alloc everything back: position
        # independence means fragmentation cannot strand capacity
        a = KVBlockAllocator(6)
        blocks = a.alloc(6)
        a.free([blocks[1], blocks[4], blocks[2]])
        got = a.alloc(3)
        assert set(got) == {blocks[1], blocks[4], blocks[2]}
        assert a.in_use == 6


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------

class TestPagedKVCache:
    def test_write_append_and_block_tables(self):
        c = PagedKVCache(num_blocks=8, page_size=4, n_heads=2, head_dim=8)
        c.allocate(7)
        kv = np.random.default_rng(0).standard_normal((6, 2, 8))
        c.write(7, kv, kv)
        assert c.seq_len(7) == 6
        assert c.blocks_in_use == 2      # ceil(6/4)
        c.write(7, kv[:2], kv[:2])       # fills page 2 exactly
        assert c.seq_len(7) == 8 and c.blocks_in_use == 2
        c.write(7, kv[:1], kv[:1])       # spills into a third page
        assert c.blocks_in_use == 3
        bt, sl = c.block_tables([7], rows=2, max_pages=4)
        assert bt.shape == (2, 4) and sl.tolist() == [9, 1]
        assert (bt[1] == 0).all()        # padding row: page 0, len 1
        c.free_seq(7)
        assert c.blocks_in_use == 0

    def test_atomic_write_on_exhaustion(self):
        c = PagedKVCache(num_blocks=2, page_size=4, n_heads=2, head_dim=8)
        c.allocate(1)
        kv = np.zeros((12, 2, 8), np.float32)     # needs 3 pages, have 2
        with pytest.raises(KVCacheExhausted):
            c.write(1, kv, kv)
        assert c.seq_len(1) == 0                  # untouched
        c.write(1, kv[:8], kv[:8])                # exactly 2 pages fits
        assert c.seq_len(1) == 8

    def test_int8_pages_store_scales_and_roundtrip(self):
        c = PagedKVCache(num_blocks=4, page_size=4, n_heads=2, head_dim=8,
                         dtype="int8")
        rng = np.random.default_rng(1)
        kv = rng.standard_normal((4, 2, 8)).astype(np.float32) * 3.0
        c.allocate(0)
        c.write(0, kv, kv)
        k8, v8, ks, vs = c.pages()
        deq = k8[c._seqs[0].blocks[0]].astype(np.float32) \
            * ks[c._seqs[0].blocks[0]][..., None]
        err = np.abs(deq - kv).max() / np.abs(kv).max()
        assert err < 0.01
        # int8 bytes: 2*page*H*D int8 + 2*page*H f32 scales, per block
        assert c.bytes_per_block == 2 * 4 * 2 * 8 + 2 * 4 * 2 * 4

    def test_int8_block_costs_under_quarter_of_f32(self):
        f32 = PagedKVCache(4, page_size=16, n_heads=4, head_dim=64)
        i8 = PagedKVCache(4, page_size=16, n_heads=4, head_dim=64,
                          dtype="int8")
        assert i8.bytes_per_block < f32.bytes_per_block / 3.5


# ---------------------------------------------------------------------------
# Kernel conformance (the two-implementation contract)
# ---------------------------------------------------------------------------

class TestPagedAttentionKernel:
    def test_pallas_matches_reference_f32_ragged(self):
        q, k, v, bt, sl, _, _, _, _ = _random_paged()
        ref = np.asarray(pa.paged_attention_reference(q, k, v, bt, sl))
        out = np.asarray(pa.paged_attention(q, k, v, bt, sl,
                                            interpret=True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_pallas_matches_reference_int8(self):
        q, k8, v8, bt, sl, ks, vs, _, _ = _random_paged(dtype="int8",
                                                        seed=3)
        ref = np.asarray(pa.paged_attention_reference(
            q, k8, v8, bt, sl, k_scales=ks, v_scales=vs))
        out = np.asarray(pa.paged_attention(
            q, k8, v8, bt, sl, k_scales=ks, v_scales=vs, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_int8_parity_within_one_percent_of_f32(self):
        # int8 KV attention within 1% of the f32 pages
        q, k8, v8, bt, sl, ks, vs, kf, vf = _random_paged(dtype="int8",
                                                          seed=5)
        f32 = np.asarray(pa.paged_attention_reference(q, kf, vf, bt, sl))
        i8 = np.asarray(pa.paged_attention_reference(
            q, k8, v8, bt, sl, k_scales=ks, v_scales=vs))
        rel = np.linalg.norm(i8 - f32) / np.linalg.norm(f32)
        assert rel <= 0.01, f"int8 KV relative error {rel:.4f} > 1%"

    def test_length_one_sequence(self):
        # smallest ragged case: one token, one page, rest of table padded
        q, k, v, bt, sl, _, _, _, _ = _random_paged(B=1, seed=7)
        sl = np.array([1], np.int32)
        ref = np.asarray(pa.paged_attention_reference(q, k, v, bt, sl))
        out = np.asarray(pa.paged_attention(q, k, v, bt, sl,
                                            interpret=True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_registered_in_dispatch(self):
        spec = kd.kernels()["paged_attention"]
        assert spec.pallas_fn is pa.paged_attention
        assert spec.reference_fn is pa.paged_attention_reference
        q, k, v, bt, sl, _, _, _, _ = _random_paged()
        assert spec.supports(q, k, v, bt, sl)
        assert not spec.supports(q[0], k, v, bt, sl)   # q must be [B,H,D]

    def test_supports_rejects_scaleless_int8(self):
        q, k8, v8, bt, sl, ks, vs, _, _ = _random_paged(dtype="int8")
        spec = kd.kernels()["paged_attention"]
        assert spec.supports(q, k8, v8, bt, sl, k_scales=ks, v_scales=vs)
        assert not spec.supports(q, k8, v8, bt, sl)


# ---------------------------------------------------------------------------
# Fingerprint distinctness
# ---------------------------------------------------------------------------

class TestKvDtypeFingerprint:
    def test_kernel_tier_fingerprint_splits_on_kv_dtype(self):
        kd.set_kv_dtype("f32")
        fp32 = kd.kernel_tier_fingerprint()
        kd.set_kv_dtype("int8")
        fp8 = kd.kernel_tier_fingerprint()
        assert fp32 != fp8
        assert fp32["kv_dtype"] == "f32" and fp8["kv_dtype"] == "int8"

    def test_model_fingerprint_splits_on_kv_dtype(self):
        # f32-KV and int8-KV decode programs must never share an AOT
        # cache entry: the model fingerprint folds the tier in
        model = _tiny()
        kd.set_kv_dtype("f32")
        a = model_fingerprint(model)
        kd.set_kv_dtype("int8")
        b = model_fingerprint(model)
        assert a != b

    def test_engine_installs_its_kv_dtype(self):
        eng = _engine(kv_dtype="int8")
        try:
            assert kd.kv_dtype() == "int8"
        finally:
            eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Engine: compile discipline
# ---------------------------------------------------------------------------

class TestZeroRecompile:
    def test_skewed_flood_compiles_nothing_after_warmup(self):
        eng = _engine()
        try:
            warm = eng.warmup()
            assert warm == eng.fresh_compiles() > 0
            # sequence-length-skewed flood: every prompt bucket hit
            rng = np.random.default_rng(0)
            futs = [eng.submit(rng.integers(1, 48, size=n),
                               max_new_tokens=3)
                    for n in (1, 2, 7, 8, 9, 20, 31, 33, 50)]
            for f in futs:
                f.result(timeout=30)
            assert eng.fresh_compiles() == warm, \
                "fresh XLA compile after warmup"
        finally:
            eng.shutdown(drain=False)

    def test_prompt_buckets_are_pow2(self):
        eng = _engine(max_seq_len=128)
        try:
            assert eng.prompt_buckets == [8, 16, 32, 64, 127] \
                or all(b & (b - 1) == 0 for b in eng.prompt_buckets[:-1])
            assert eng.batch_buckets == [1, 2, 4]
        finally:
            eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Engine: continuous batching semantics
# ---------------------------------------------------------------------------

class TestContinuousDecode:
    def test_mid_flight_admit_and_retire(self):
        # more sequences than batch slots, wildly different lengths: the
        # short ones retire mid-flight and free slots for the waiting
        eng = _engine(max_decode_batch=2)
        try:
            eng.warmup()
            futs = [eng.submit(np.arange(1, 4), max_new_tokens=n)
                    for n in (2, 12, 3, 9, 2, 5)]
            outs = [f.result(timeout=60) for f in futs]
            assert [len(o) for o in outs] == [2, 12, 3, 9, 2, 5]
            assert eng.cache.blocks_in_use == 0     # all pages released
            assert eng.queue_depth == 0
        finally:
            eng.shutdown(drain=False)

    def test_deterministic_and_prefix_consistent(self):
        # same prompt twice -> same tokens (greedy argmax, shared cache)
        eng = _engine()
        try:
            a = eng.generate(np.arange(1, 6), max_new_tokens=5,
                             timeout=30)
            b = eng.generate(np.arange(1, 6), max_new_tokens=5,
                             timeout=30)
            np.testing.assert_array_equal(a, b)
        finally:
            eng.shutdown(drain=False)

    def test_cancel_waiting_and_active(self):
        eng = _engine(max_decode_batch=1)
        # The worker steps under the engine's lock and takes it again at
        # once, so on a busy host a thread that wants to cancel can be kept
        # out until a 40-token run is over.  The test must not depend on
        # being faster than the engine: it holds the worker at a gate
        # BETWEEN two steps, once the long runner has 3 tokens, where the
        # worker (as in its idle wait) waits on the condition and gives the
        # lock up.  Retiring the runner, or `resume`, lets it go on.
        resume = threading.Event()
        admit = eng._admit_locked

        def held(seq):
            return len(seq.generated) >= 3

        def admit_then_hold():
            admit()
            eng._cond.wait_for(
                lambda: resume.is_set() or not any(map(held, eng._active)),
                timeout=30)

        eng._admit_locked = admit_then_hold
        try:
            eng.warmup()
            # long runner occupies the single slot
            long = eng.submit(np.arange(1, 4), max_new_tokens=40)
            waiting = eng.submit(np.arange(1, 4), max_new_tokens=40)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with eng._cond:
                    if any(s.future is long and held(s)
                           for s in eng._active):
                        break
                time.sleep(0.005)
            assert not long.done() and eng.cache.blocks_in_use > 0
            assert eng.cancel(waiting) is True
            assert waiting.cancelled()
            assert eng.cancel(long) is True         # mid-flight retire
            deadline = time.monotonic() + 5
            while eng.cache.blocks_in_use and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.cache.blocks_in_use == 0     # pages back NOW
            assert eng.cancel(Future()) is False    # unknown future
        finally:
            resume.set()
            eng.shutdown(drain=False)

    def test_exhaustion_sheds_not_crashes(self):
        # pool of 2 pages (page_size 16 -> 32 tokens): a sequence that
        # outgrows it is shed with KVCacheExhausted; the engine lives on
        eng = _engine(num_blocks=2, page_size=16, max_seq_len=64,
                      max_decode_batch=2)
        try:
            big = eng.submit(np.arange(1, 30), max_new_tokens=20)
            with pytest.raises(KVCacheExhausted):
                big.result(timeout=30)
            # engine still serves admissible work afterward
            ok = eng.generate(np.arange(1, 5), max_new_tokens=3,
                              timeout=30)
            assert len(ok) == 3
            assert eng.cache.blocks_in_use == 0
        finally:
            eng.shutdown(drain=False)

    def test_oversized_prompt_rejected_at_submit(self):
        eng = _engine(max_seq_len=32)
        try:
            with pytest.raises(RejectedError):
                eng.submit(np.arange(1, 31), max_new_tokens=10)
        finally:
            eng.shutdown(drain=False)

    def test_int8_engine_generates(self):
        model = _tiny()
        f32 = _engine(model)
        i8 = _engine(model, kv_dtype="int8", model_label="t8")
        try:
            a = f32.generate(np.arange(1, 9), max_new_tokens=6,
                             timeout=30)
            b = i8.generate(np.arange(1, 9), max_new_tokens=6,
                            timeout=30)
            assert len(a) == len(b) == 6
            # greedy decode may diverge on near-ties; first tokens agree
            assert a[0] == b[0]
        finally:
            f32.shutdown(drain=False)
            i8.shutdown(drain=False)


# ---------------------------------------------------------------------------
# ContinuousBatcher.cancel (the satellite fix)
# ---------------------------------------------------------------------------

class TestBatcherCancel:
    def _batcher(self, **kw):
        started = {"evt": None}

        def dispatch(group, xs):
            if started["evt"] is not None:
                started["evt"].set()
            time.sleep(0.05)
            return xs

        return ContinuousBatcher(dispatch, max_batch=4,
                                 batch_timeout_ms=30.0, **kw), started

    def test_cancel_releases_queue_slot_immediately(self):
        b, _ = self._batcher(max_queue=2)
        try:
            f1 = b.submit(np.ones((1, 2)), group=("a", 1))
            f2 = b.submit(np.ones((1, 2)), group=("b", 1))
            # queue full: a third submit may shed... unless a cancel
            # releases the slot first — mid-group, no boundary wait
            assert b.cancel(f2) is True
            f3 = b.submit(np.ones((1, 2)), group=("a", 1))
            assert f2.cancelled()
            assert np.asarray(f1.result(timeout=5)).shape == (1, 2)
            assert np.asarray(f3.result(timeout=5)).shape == (1, 2)
        finally:
            b.shutdown(drain=False)

    def test_cancel_interleaved_with_admits(self):
        b, _ = self._batcher(max_queue=8)
        try:
            futs = [b.submit(np.ones((1, 2)), group=("g", 1))
                    for _ in range(4)]
            assert b.cancel(futs[1]) is True
            assert b.cancel(futs[3]) is True
            live = [futs[0], futs[2]]
            for f in live:
                assert np.asarray(f.result(timeout=5)).shape == (1, 2)
            assert futs[1].cancelled() and futs[3].cancelled()
        finally:
            b.shutdown(drain=False)

    def test_cancel_unknown_or_dispatched_returns_false(self):
        import threading
        b, started = self._batcher(max_queue=4)
        started["evt"] = threading.Event()
        try:
            assert b.cancel(Future()) is False
            f = b.submit(np.ones((1, 2)), group=("g", 1))
            assert started["evt"].wait(timeout=5)   # now mid-dispatch
            assert b.cancel(f) is False             # cannot recall it
            assert np.asarray(f.result(timeout=5)).shape == (1, 2)
        finally:
            b.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Fleet membership + failover
# ---------------------------------------------------------------------------

class TestDecodeFleet:
    def _fleet(self, replicas=2, **engine_kw):
        from deeplearning4j_tpu.serving import LatencySLO, ModelFleet
        model = _tiny()
        fleet = ModelFleet(max_resident=2)

        def factory(slice_):
            kw = dict(num_blocks=64, max_seq_len=64, max_decode_batch=4,
                      model_label="gen")
            kw.update(engine_kw)
            e = DecodeEngine(model, **kw)
            e.warmup()
            return e

        member = fleet.deploy_decode(
            "gen", factory, slo=LatencySLO(target_p99_ms=1000.0),
            replicas=replicas)
        return fleet, member

    def test_decode_member_is_first_class(self):
        fleet, member = self._fleet()
        try:
            assert member.kind == "decode"
            assert member.state == "resident"
            assert len(member.group.replicas) == 2
            out = fleet.generate("gen", np.arange(1, 5),
                                 max_new_tokens=4).result(timeout=30)
            assert len(out) == 4
            # per-token SLO series feeds the member's latency histogram
            assert member.latency.count > 0
            assert fleet.readyz()["ready"]
            # submit() refuses decode members
            with pytest.raises(ValueError, match="decode member"):
                fleet.submit("gen", np.zeros((1, 4)))
        finally:
            fleet.shutdown()

    def test_failover_restarts_sequence_and_counts(self):
        from deeplearning4j_tpu.monitor.instrument import \
            decode_instruments
        fleet, member = self._fleet()
        try:
            before = decode_instruments().restarts("gen").value
            dead = member.group.replicas[0]
            dead.server.engine.kill()
            # every request lands somewhere: the dead replica's submits
            # fail fatally and restart (from token 0) on the live one
            outs = [fleet.generate("gen", np.arange(1, 6),
                                   max_new_tokens=3).result(timeout=30)
                    for _ in range(8)]
            assert all(len(o) == 3 for o in outs)
            assert dead.poisoned
            after = decode_instruments().restarts("gen").value
            assert after > before, "failover restart was not counted"
        finally:
            fleet.shutdown()

    def test_controller_heals_poisoned_decode_replica(self):
        fleet, member = self._fleet()
        try:
            member.group.replicas[0].server.engine.kill()
            # a probe poisons it (kill sets _poisoned; next submit is
            # fatal), or we poison directly — either way heal respawns
            for _ in range(4):
                fleet.generate("gen", np.arange(1, 5),
                               max_new_tokens=2).result(timeout=30)
            rec = fleet.controller.reconcile()
            heals = [a for a in rec["actions"]
                     if a.get("kind") == "decode"]
            assert heals and heals[0]["cause"] == "poisoned"
            assert member.respawns == 1
            assert all(r.healthy for r in member.group.snapshot())
            out = fleet.generate("gen", np.arange(1, 5),
                                 max_new_tokens=3).result(timeout=30)
            assert len(out) == 3
        finally:
            fleet.shutdown()


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
