"""Paged KV-cache serving (serving/paged.py + ops/pallas/paged_attention).

The load-bearing contracts pinned here:

* TOKEN PARITY — a sequence decoded through the block pool emits exactly
  the tokens of a straight-line dense decode, regardless of slot, block
  layout, neighbors, join order, or chunked-prefill interleaving.
* PREFIX BITWISE IDENTITY — a prompt whose leading blocks hash-hit the
  cross-tenant prefix cache resolves to the SAME physical blocks, skips
  their prefill chunks, and still emits bitwise-identical tokens.
* ALLOCATOR PHYSICS — refcounts under join/evict/cache churn: blocks are
  never double-freed, never leak, and the pool returns to fully-free when
  every reference is dropped.
* ZERO STEADY-STATE RETRACES — once the width ladder is warm, joins,
  evictions, and pool churn never recompile (``executor.traces``), and
  the paged-attention kernel fingerprint rides the compile-cache key.
"""
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core import flags
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import paged as P
from paddle_tpu.serving.paged import (BlockPool, PagedDecoder, PagedKVCache,
                                      PrefixCache, dense_reference_decode,
                                      kv_pool_bytes, make_paged_toy_lm)
from paddle_tpu.serving.slo import AdmissionError
from paddle_tpu.utils import monitor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _metrics_on():
    saved = flags.get_flags(["metrics"])
    flags.set_flags({"metrics": True})
    yield
    flags.set_flags(saved)


@pytest.fixture(scope="module")
def model():
    return make_paged_toy_lm(vocab=64, hidden=32, max_positions=256, seed=3)


def _mk(model, num_blocks=64, block_size=8, max_seqs=8, maxb=16,
        chunk=8, kv_dtype="float32"):
    cache = PagedKVCache(model, num_blocks, block_size, kv_dtype=kv_dtype)
    dec = PagedDecoder(model, cache, max_seqs=max_seqs,
                       max_blocks_per_seq=maxb, prefill_chunk=chunk)
    return cache, dec


# ---------------------------------------------------------------------------
# token parity vs the dense reference
# ---------------------------------------------------------------------------
def test_paged_vs_dense_token_parity_across_prompt_lengths(model):
    _, dec = _mk(model)
    rng = np.random.default_rng(0)
    # lengths straddle block (8) and chunk (8) boundaries
    for plen in (1, 3, 7, 8, 9, 16, 17, 30):
        prompt = rng.integers(1, 64, plen).tolist()
        h = dec.join(prompt, 6)
        dec.run_until_idle()
        assert not h.evicted
        assert h.tokens == dense_reference_decode(model, prompt, 6), plen


def test_prefill_is_handed_no_buffer_the_host_rewrites(model):
    """Dispatch is asynchronous and may read a host array in place after it
    returns; a chunk that does not end its prompt is not waited for.  So the
    prefill step must never be handed the decoder's persistent mirrors,
    which the next chunk rewrites (it was: multi-chunk prompts then decoded
    other tokens, or not, by the timing of the run)."""
    _, dec = _mk(model)
    mirrors = (dec._tables_np, dec._pf_meta_np, dec._meta_np)
    real, handed = dec._prefill_fn, []

    def spy(kc, vc, table, meta):
        handed.extend([table, meta])
        return real(kc, vc, table, meta)

    dec._prefill_fn = spy
    h = dec.join(list(range(1, 31)), 2)      # four chunks of 8
    dec.run_until_idle()
    assert len(handed) == 8 and len(h.tokens) == 2
    for arg in handed:
        assert not any(np.shares_memory(arg, m) for m in mirrors)


def test_paged_parity_concurrent_staggered_joins(model):
    """Neighbors, slot assignment, and join timing must not leak into a
    sequence's tokens (the decode-parity contract of the continuous path,
    re-pinned on block tables)."""
    _, dec = _mk(model, max_seqs=4)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, rng.integers(2, 14)).tolist()
               for _ in range(10)]
    out = dec.decode(prompts, max_new_tokens=8)
    for prompt, toks in zip(prompts, out):
        assert toks == dense_reference_decode(model, prompt, 8)


# ---------------------------------------------------------------------------
# cross-tenant prefix cache
# ---------------------------------------------------------------------------
def test_prefix_hit_bitwise_identity_minimal_chunks(model):
    """Warm joins resolve the shared system prompt from the cache: fewer
    prefill chunks, bitwise-identical tokens, counted hits."""
    _, dec = _mk(model, chunk=8)
    rng = np.random.default_rng(2)
    sys_prompt = rng.integers(1, 64, 32).tolist()   # 4 full blocks
    suffix = [5, 6, 7]

    chunks0 = P.KV_PREFILL_CHUNKS.value()
    h_cold = dec.join(sys_prompt + suffix, 5)
    dec.run_until_idle()
    cold_chunks = P.KV_PREFILL_CHUNKS.value() - chunks0

    hits0 = P.KV_PREFIX_HITS.value()
    chunks1 = P.KV_PREFILL_CHUNKS.value()
    h_warm = dec.join(sys_prompt + suffix, 5)
    dec.run_until_idle()
    warm_chunks = P.KV_PREFILL_CHUNKS.value() - chunks1
    hits = P.KV_PREFIX_HITS.value() - hits0

    assert h_warm.tokens == h_cold.tokens
    # 35-token prompt: 4 cached blocks resolve, only the 3-token tail
    # (+1 boundary token) prefills -> one chunk vs five
    assert cold_chunks == 5
    assert warm_chunks == 1
    assert hits == 4


def test_prefix_cache_shares_across_decoders_same_cache(model):
    """Two decoders (tenants) on ONE PagedKVCache share physical prefix
    blocks — the cross-tenant story — and both see exact tokens."""
    cache = PagedKVCache(model, 64, 8)
    dec_a = PagedDecoder(model, cache, max_seqs=2, max_blocks_per_seq=16,
                         tenant="a")
    dec_b = PagedDecoder(model, cache, max_seqs=2, max_blocks_per_seq=16,
                         tenant="b")
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(1, 64, 16).tolist()   # 2 full blocks
    h_a = dec_a.join(sys_prompt + [9], 4)
    dec_a.run_until_idle()
    hits0 = P.KV_PREFIX_HITS.value()
    h_b = dec_b.join(sys_prompt + [9], 4)
    live0 = cache.pool.live_count
    dec_b.run_until_idle()
    assert P.KV_PREFIX_HITS.value() - hits0 == 2
    assert h_a.tokens == h_b.tokens
    assert h_b.tokens == dense_reference_decode(model, sys_prompt + [9], 4)
    assert live0 > 0   # b's join held shared blocks while a's were cached


def test_prefix_hashes_namespace_model_and_dtype(model):
    other = make_paged_toy_lm(vocab=64, hidden=32, max_positions=256,
                              seed=4)
    c32 = PagedKVCache(model, 8, 8)
    c8 = PagedKVCache(model, 8, 8, kv_dtype="int8")
    c_other = PagedKVCache(other, 8, 8)
    toks = list(range(16))
    assert c32.block_hashes(toks) != c8.block_hashes(toks)
    assert c32.block_hashes(toks) != c_other.block_hashes(toks)
    assert c32.block_hashes(toks) == PagedKVCache(model, 4, 8).block_hashes(
        toks)


# ---------------------------------------------------------------------------
# allocator physics: refcounts under churn
# ---------------------------------------------------------------------------
def test_block_pool_alloc_free_refcount_physics():
    pool = BlockPool(4)
    bids = [pool.alloc() for _ in range(4)]
    assert sorted(bids) == [1, 2, 3, 4]   # block 0 is the pinned null
    assert pool.alloc() is None
    pool.share(bids[0])
    pool.free(bids[0])
    assert pool.free_count == 0           # one ref still held
    pool.free(bids[0])
    assert pool.free_count == 1
    with pytest.raises(RuntimeError, match="double free"):
        pool.free(bids[0])
    with pytest.raises(RuntimeError, match="null block"):
        pool.free(0)
    with pytest.raises(RuntimeError, match="unallocated"):
        pool.share(bids[0])


def test_prefix_cache_reclaim_frees_lru_entries():
    pool = BlockPool(4)
    cache = PrefixCache(pool)
    a, b = pool.alloc(), pool.alloc()
    cache.put("ha", a)
    cache.put("hb", b)
    pool.free(a)
    pool.free(b)                          # only the cache's refs remain
    assert pool.free_count == 2
    assert cache.reclaim(1) == 1          # LRU entry "ha" dropped
    assert pool.free_count == 3
    assert cache.get("ha") is None
    assert cache.get("hb") == b           # re-shared: caller now holds a ref
    pool.free(b)


def test_no_double_free_under_join_evict_churn(model):
    """Random join/evict/step churn with a small pool: every handle ends
    done, nothing raises (the pool would raise on any double free), and
    dropping the last references returns the pool to fully free."""
    cache, dec = _mk(model, num_blocks=16, max_seqs=4, maxb=8)
    rng = np.random.default_rng(4)
    live = []
    for it in range(120):
        op = rng.integers(0, 3)
        if op == 0:
            h = dec.try_join(rng.integers(1, 64, rng.integers(1, 20)).tolist(),
                             int(rng.integers(1, 8)))
            if h is not None:
                live.append(h)
        elif op == 1 and live:
            dec.evict(live.pop(int(rng.integers(0, len(live)))))
        else:
            dec.step()
    dec.run_until_idle()
    assert all(h.done for h in live)
    assert dec.active_count == 0
    # the prefix cache holds the only remaining refs; reclaim them all
    cache.prefix.reclaim(cache.pool.num_blocks)
    assert len(cache.prefix) == 0
    assert cache.pool.free_count == cache.pool.num_blocks


def test_evict_mid_decode_keeps_tokens_and_frees_blocks(model):
    cache, dec = _mk(model, num_blocks=16, max_seqs=2, maxb=8)
    h = dec.join([1, 2, 3], 50)
    for _ in range(5):
        dec.step()
    got = list(h.tokens)
    assert got                             # mid-stream
    free0 = cache.pool.free_count
    dec.evict(h)
    assert h.evicted and h.done and h.tokens == got
    assert cache.pool.free_count > free0
    assert dec.active_count == 0


def test_join_sheds_on_slots_and_blocks(model):
    _, dec = _mk(model, num_blocks=64, max_seqs=1, maxb=8)
    dec.join([1, 2, 3], 4)
    with pytest.raises(AdmissionError, match="slots"):
        dec.join([4, 5, 6], 4)
    # blocks exhausted: 2-block pool, 17-token prompt needs 3
    _, tiny = _mk(model, num_blocks=2, max_seqs=2, maxb=8)
    with pytest.raises(AdmissionError, match="kv_blocks"):
        tiny.join(list(range(1, 18)), 2)


# ---------------------------------------------------------------------------
# zero steady-state retraces + kernel fingerprint in the cache key
# ---------------------------------------------------------------------------
def test_zero_steady_state_retraces_under_churn(model):
    reg = monitor.default_registry()
    _, dec = _mk(model, max_seqs=4, maxb=8)
    rng = np.random.default_rng(5)

    def churn():
        for _ in range(12):
            dec.try_join(rng.integers(1, 64, rng.integers(2, 12)).tolist(),
                         4)
            dec.step()
        dec.run_until_idle()

    churn()                                # warm the width ladder
    traces0 = reg.get("executor.traces").value()
    churn()                                # same shapes, new content
    assert reg.get("executor.traces").value() == traces0


def test_paged_kernel_fingerprint_rides_cache_key(monkeypatch):
    monkeypatch.setattr(pcfg, "backend_is_tpu", lambda: True)
    assert "pgat=1" in pcfg.fingerprint()
    assert "pgat=1" in pcfg.cache_key_part()
    saved = flags.get_flags(["use_paged_attention"])
    try:
        flags.set_flags({"use_paged_attention": False})
        assert "pgat=0" in pcfg.fingerprint()
    finally:
        flags.set_flags(saved)
    monkeypatch.setattr(pcfg, "backend_is_tpu", lambda: False)
    assert "pgat=0" in pcfg.fingerprint()  # CPU: kernel never effective


# ---------------------------------------------------------------------------
# the Pallas kernel (interpret mode on CPU CI)
# ---------------------------------------------------------------------------
def _kernel_case(rng, dtype, num_seqs=4, max_blocks=3, block_size=8, d=128):
    num_blocks = num_seqs * max_blocks + 1
    if dtype == "int8":
        k_cache = rng.integers(-127, 128,
                               (num_blocks, block_size, d)).astype(np.int8)
        v_cache = rng.integers(-127, 128,
                               (num_blocks, block_size, d)).astype(np.int8)
        scales = rng.uniform(0.01, 0.1, (num_blocks, 2)).astype(np.float32)
    else:
        k_cache = rng.normal(size=(num_blocks, block_size, d)).astype(
            np.float32)
        v_cache = rng.normal(size=(num_blocks, block_size, d)).astype(
            np.float32)
        scales = None
    q = rng.normal(size=(num_seqs, d)).astype(np.float32)
    tables = rng.permutation(np.arange(1, num_blocks))[
        :num_seqs * max_blocks].reshape(num_seqs, max_blocks).astype(
        np.int32)
    # lens cover: empty row, partial block, exact block, full table
    lens = np.array([0, 3, block_size, max_blocks * block_size][:num_seqs],
                    np.int32)
    args = (jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.asarray(tables), jnp.asarray(lens))
    kw = {}
    if scales is not None:
        kw["kv_scales"] = jnp.asarray(scales)
    return args, kw, lens


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_paged_attention_kernel_matches_reference(dtype):
    rng = np.random.default_rng(6)
    args, kw, lens = _kernel_case(rng, dtype)
    assert pa.supported(args[0].shape[0], args[1].shape[1],
                        args[0].shape[-1], args[1].dtype)
    out_k = pa.paged_attention_kernel(*args, sm_scale=0.088, **kw)
    out_r = pa.paged_attention_reference(*args, sm_scale=0.088, **kw)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    # a row that has seen no tokens must come back exactly zero, not NaN
    assert np.all(np.asarray(out_k)[lens == 0] == 0.0)


def test_paged_attention_gate_falls_back_off_tpu(monkeypatch):
    monkeypatch.setattr(pcfg, "backend_is_tpu", lambda: False)
    rng = np.random.default_rng(7)
    args, kw, _ = _kernel_case(rng, "float32", d=8)   # unsupported d too
    before = pcfg._m_fallbacks.value(kernel="paged_attention",
                                     reason="unsupported")
    out = pa.paged_attention(*args, **kw)
    assert out.shape == args[0].shape
    assert pcfg._m_fallbacks.value(kernel="paged_attention",
                                   reason="unsupported") == before + 1


# ---------------------------------------------------------------------------
# int8 KV blocks
# ---------------------------------------------------------------------------
def test_int8_kv_tolerance_gated_token_parity(model):
    """int8 blocks are lossy: greedy argmax can flip on near-ties, so the
    gate is a token match RATE against the dense oracle, not bitwise."""
    _, dec = _mk(model, kv_dtype="int8")
    rng = np.random.default_rng(8)
    total = matched = 0
    for _ in range(12):
        prompt = rng.integers(1, 64, rng.integers(3, 20)).tolist()
        h = dec.join(prompt, 8)
        dec.run_until_idle()
        ref = dense_reference_decode(model, prompt, 8)
        matched += sum(a == b for a, b in zip(h.tokens, ref))
        total += len(ref)
    assert matched / total >= 0.9, f"int8 token match {matched}/{total}"


def test_int8_kv_cache_bytes_reflect_compression(model):
    fp32 = kv_pool_bytes(64, 8, model.hidden, "float32")
    int8 = kv_pool_bytes(64, 8, model.hidden, "int8")
    assert int8 < fp32 / 3.5               # ~4x minus the scale overhead
    cache = PagedKVCache(model, 64, 8, kv_dtype="int8")
    assert cache.bytes == int8
    reg = monitor.default_registry()
    assert reg.get("serve.kv_cache_bytes").value() == float(int8)


# ---------------------------------------------------------------------------
# MC008: pool pricing at admission
# ---------------------------------------------------------------------------
def test_mc008_prices_pool_against_capacity():
    from paddle_tpu.static.memcheck import check_kv_pool

    cap = kv_pool_bytes(64, 8, 32, "float32") + 1000
    assert check_kv_pool(64, 8, 32, capacity_bytes=cap * 100) == []
    warn = check_kv_pool(64, 8, 32, capacity_bytes=cap)
    assert [d.severity for d in warn] == ["warning"]
    err = check_kv_pool(64, 8, 32, existing_bytes=2000, capacity_bytes=cap)
    assert [d.severity for d in err] == ["error"]
    assert "MC008" in err[0].code and "int8" in err[0].hint


def test_tenant_manager_rejects_over_capacity_pool():
    from paddle_tpu.core.errors import ProgramVerificationError
    from paddle_tpu.serving.tenancy import TenantManager

    tm = TenantManager(max_live_programs=2)
    cap = kv_pool_bytes(64, 8, 32, "float32") + 1
    got = tm.admit_kv_pool("a", 64, 8, 32, capacity_bytes=cap)
    assert got == kv_pool_bytes(64, 8, 32, "float32")
    assert tm.kv_pool_bytes_admitted() == got
    with pytest.raises(ValueError, match="already admitted"):
        tm.admit_kv_pool("a", 1, 8, 32, capacity_bytes=cap)
    # the second pool stacks on the first and busts capacity BEFORE any
    # device allocation happens
    with pytest.raises(ProgramVerificationError, match="MC008"):
        tm.admit_kv_pool("b", 64, 8, 32, capacity_bytes=cap)
    tm.release_kv_pool("a")
    assert tm.kv_pool_bytes_admitted() == 0
    tm.admit_kv_pool("b", 64, 8, 32, capacity_bytes=cap)


def test_server_add_decode_tenant_admits_and_shares_cache():
    from paddle_tpu.serving import Server

    srv = Server()
    model = make_paged_toy_lm(vocab=64, hidden=32, max_positions=256,
                              seed=9)
    try:
        dec = srv.add_decode_tenant("t1", model, num_blocks=16,
                                    block_size=8, max_seqs=2,
                                    max_blocks_per_seq=8)
        assert srv.tenants.kv_pool_bytes_admitted() == dec.cache.bytes
        # cross-tenant: same cache object, no second admission
        dec2 = srv.add_decode_tenant("t2", model, num_blocks=16,
                                     block_size=8, max_seqs=2,
                                     max_blocks_per_seq=8,
                                     cache=dec.cache)
        assert dec2.cache is dec.cache
        assert srv.tenants.kv_pool_bytes_admitted() == dec.cache.bytes
        h = dec.join([1, 2, 3], 3)
        dec.run_until_idle()
        assert h.tokens == dense_reference_decode(model, [1, 2, 3], 3)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# capi worker: PDGN streaming decode
# ---------------------------------------------------------------------------
def _child_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    existing = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = ROOT + (os.pathsep + existing if existing else "")
    env.update(extra)
    return env


class _StreamClient:
    def __init__(self, model_dir, **env):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.inference.capi_worker",
             model_dir], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=_child_env(**env))
        assert self._rd(4) == b"PDOK"

    def _rd(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self.proc.stdout.read(n - len(buf))
            assert chunk, "worker EOF"
            buf += chunk
        return buf

    def send_pdgn(self, req_id, prompt, max_new):
        frame = (b"PDID" + struct.pack("<Q", req_id) + b"PDGN"
                 + struct.pack("<i", len(prompt))
                 + struct.pack(f"<{len(prompt)}q", *prompt)
                 + struct.pack("<i", max_new))
        self.proc.stdin.write(frame)
        self.proc.stdin.flush()

    def send_legacy(self, x):
        frame = (b"PDRQ" + struct.pack("<i", 1)
                 + struct.pack("<i", 1) + b"x"
                 + struct.pack("<ii", 1, x.ndim)
                 + struct.pack(f"<{x.ndim}q", *x.shape) + x.tobytes())
        self.proc.stdin.write(frame)
        self.proc.stdin.flush()

    def read_frame(self):
        """(req_id|None, kind, payload): kind is 'tokens' (PDTK delta),
        'result' (PDRS {name: array}), or 'error' (message str)."""
        magic, rid = self._rd(4), None
        if magic == b"PDID":
            (rid,) = struct.unpack("<Q", self._rd(8))
            magic = self._rd(4)
        if magic == b"PDTK":
            (n,) = struct.unpack("<i", self._rd(4))
            toks = struct.unpack(f"<{n}q", self._rd(8 * n))
            return rid, "tokens", list(toks)
        if magic == b"PDER":
            (n,) = struct.unpack("<i", self._rd(4))
            return rid, "error", self._rd(n).decode()
        assert magic == b"PDRS", magic
        (n,) = struct.unpack("<i", self._rd(4))
        outs = {}
        for _ in range(n):
            (nl,) = struct.unpack("<i", self._rd(4))
            name = self._rd(nl).decode()
            code, ndim = struct.unpack("<ii", self._rd(8))
            dims = struct.unpack(f"<{ndim}q", self._rd(8 * ndim))
            dt = {0: np.float32, 1: np.int32, 2: np.int64,
                  3: np.float64}[code]
            raw = self._rd(int(np.prod(dims)) * np.dtype(dt).itemsize)
            outs[name] = np.frombuffer(raw, dt).reshape(dims)
        return rid, "result", outs

    def close(self):
        self.proc.stdin.close()
        self.proc.wait(timeout=60)


@pytest.fixture(scope="module")
def _stream_model(tmp_path_factory):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [6], dtype="int32")
        y = L.elementwise_add(L.elementwise_mul(x, x), x)
    exe = static.Executor()
    exe.run(startup)
    model_dir = str(tmp_path_factory.mktemp("paged_capi") / "m")
    static.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)
    return model_dir


def test_capi_pdgn_streams_deltas_then_final_result(_stream_model):
    client = _StreamClient(_stream_model, PDTPU_CAPI_DECODE="1")
    try:
        prompts = {7: [1, 2, 3], 11: [9, 8, 7, 6, 5]}
        for rid, prompt in prompts.items():
            client.send_pdgn(rid, prompt, 6)
        streamed = {rid: [] for rid in prompts}
        finals = {}
        while len(finals) < 2:
            rid, kind, payload = client.read_frame()
            assert rid in prompts and kind in ("tokens", "result")
            if kind == "tokens":
                streamed[rid].extend(payload)
            else:
                finals[rid] = list(payload["tokens"])
        # the worker's decode model is the default paged toy LM at the
        # worker's max_positions; the deltas must reassemble the final
        # result, and the result must match the dense oracle
        ref_model = make_paged_toy_lm(max_positions=256)
        for rid, prompt in prompts.items():
            assert streamed[rid] == finals[rid]
            assert finals[rid] == dense_reference_decode(ref_model, prompt,
                                                         6)
    finally:
        client.close()


def test_capi_pdgn_interleaves_with_legacy_and_drains(_stream_model):
    """Legacy PDRQ after PDGN traffic = drain barrier: the stream's final
    PDRS arrives before the legacy response, and the legacy reply stays
    byte-identical to the non-streaming protocol."""
    client = _StreamClient(_stream_model, PDTPU_CAPI_DECODE="1")
    try:
        client.send_pdgn(1, [4, 4, 4], 4)
        x = np.arange(6, dtype=np.int32).reshape(1, 6)
        client.send_legacy(x)
        kinds = []
        while True:
            rid, kind, payload = client.read_frame()
            kinds.append((rid, kind))
            if rid is None:
                assert kind == "result"
                np.testing.assert_array_equal(payload["y"]
                                              if "y" in payload else
                                              list(payload.values())[0],
                                              x * x + x)
                break
        assert (1, "result") in kinds      # stream finished first
        assert kinds[-1][0] is None        # legacy response came last
    finally:
        client.close()


def test_capi_pdgn_rejected_when_disabled(_stream_model):
    client = _StreamClient(_stream_model)   # no PDTPU_CAPI_DECODE
    try:
        client.send_pdgn(3, [1, 2], 4)
        rid, kind, msg = client.read_frame()
        assert rid == 3 and kind == "error"
        assert "PDTPU_CAPI_DECODE" in msg
    finally:
        client.close()


# ---------------------------------------------------------------------------
# the cost model registers for the kernel op
# ---------------------------------------------------------------------------
def test_paged_attention_cost_int8_blocks_move_fewer_bytes():
    # that the family is registered and priced above zero is
    # test_pallas_vision.py's, for every family
    _, bytes_ = pa.paged_attention_cost(num_seqs=4, max_blocks=3,
                                        block_size=8, head_dim=128)
    _, b8 = pa.paged_attention_cost(4, 3, 8, 128, kv_bytes_per_elem=1)
    assert b8 < bytes_ / 3
