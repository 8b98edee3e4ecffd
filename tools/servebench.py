"""Serving-frontend load generator: closed-loop and open-loop (qps ramp)
benchmarks of ``paddle_tpu.serving`` plus the continuous-batching decode
path, printing exactly ONE JSON line.

What it measures:

* ``baseline`` — closed loop, ONE client: every request is dispatched
  alone (batch of 1).  This is the reference predictor-pool model (one
  AnalysisPredictor::Run per request) and the denominator of ``speedup``.
* ``batched`` — closed loop, ``--clients`` concurrent submitters
  coalescing through the shape-bucket frontend.  ``speedup`` =
  batched qps / baseline qps — the throughput the server-side batching
  buys at equal work per request (acceptance floor: >= 3x on a host where
  per-dispatch overhead dominates small-model step time).
* ``open_loop`` — requests injected at fixed target rates
  (``--qps-ramp``, e.g. "50,100,200"), one record per level: achieved
  qps, latency percentiles, and how many requests the SLO/quota admission
  shed.  Unlike the closed loop, this shows saturation: achieved qps
  flattens and p99 blows up past the knee.
* ``continuous`` — iteration-level decode of ``--seqs`` prompts on a
  ``--slots``-slot pool vs the same prompts decoded sequentially
  (single-slot pool = request-level batching floor), with per-sequence
  token parity (``parity`` MUST be true: slot placement never changes a
  sequence's tokens).
* ``occupancy_hist`` — the ``serve.batch_size`` histogram observed during
  the batched phase: how full the dispatched buckets actually were.
* ``paged`` (``--paged``) — the paged-KV serving blocks
  (``serving/paged.py``): ``capacity`` measures max concurrent short
  sequences admitted at a FIXED KV-pool HBM budget vs the dense
  slot-reservation equivalent (every slot provisioned for ``max_len``);
  ``decode`` races paged decode against a ``ContinuousBatcher`` given the
  SAME HBM (the dense pool affords only ``pool_bytes / max_len-row``
  slots) with per-sequence token parity vs a straight-line dense
  reference decode; ``ttft_mix`` joins a long prompt and measures how
  much short-request first-token latency moves when chunked prefill
  interleaves it (steps and wall ms, alone vs mixed); ``prefix_cache``
  replays a shared-system-prompt workload and reports the block hit rate
  plus prefill chunks cold vs warm.

Latency percentiles come from the SAME ``Histogram.percentile`` estimator
the SLO admission uses (one quantile implementation everywhere).

Usage:
    python -m tools.servebench [--clients N] [--duration S] [--hidden H]
                               [--buckets 1,2,4,8,16,32] [--max-wait-ms W]
                               [--qps-ramp 50,100,200] [--slo-p99-ms MS]
                               [--seqs N] [--slots N] [--new-tokens N]
                               [--paged] [--out FILE]
    python -m tools.servebench --selfcheck     # smoke: rides tier-1
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time


def _percentiles(lat_ms):
    import numpy as np

    if not lat_ms:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    a = np.asarray(lat_ms, dtype=np.float64)
    return {"p50_ms": round(float(np.percentile(a, 50)), 4),
            "p95_ms": round(float(np.percentile(a, 95)), 4),
            "p99_ms": round(float(np.percentile(a, 99)), 4)}


def _build_tenant(hidden: int):
    """A small row-independent inference graph (dims chosen well clear of
    the degenerate gemm shapes where XLA:CPU picks batch-dependent kernel
    strategies — see tests/test_serving.py)."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    main.random_seed = 11
    startup.random_seed = 11
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [hidden])
        y = L.fc(L.fc(x, 2 * hidden, act="tanh"), hidden)
        exe = static.Executor()
        exe.run(startup, scope=scope)
    return main, y, scope


def _mk_server(serving, edges, max_wait_ms, slo_p99_ms=None):
    slo = serving.SLOPolicy(p99_ms=slo_p99_ms)
    return serving.Server(bucket_edges=edges, max_wait_ms=max_wait_ms,
                          slo=slo)


def _closed_loop(srv, rows_feed, clients: int, duration: float):
    """``clients`` threads each submit-and-wait in a loop for ``duration``
    seconds; returns (achieved_qps, latencies_ms)."""
    lat_ms, lock = [], threading.Lock()
    stop = time.perf_counter() + duration

    def client():
        mine = []
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            srv.submit("bench", rows_feed).result()
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat_ms.extend(mine)

    t_start = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return (len(lat_ms) / wall if wall > 0 else 0.0), lat_ms


def _open_loop(srv, rows_feed, qps: float, duration: float):
    """Inject at a fixed target rate (no waiting for results); returns
    (achieved_qps, latencies_ms, shed_count)."""
    from paddle_tpu.serving import AdmissionError

    lat_ms, lock = [], threading.Lock()
    shed = [0]
    pending = []
    period = 1.0 / qps
    t_start = time.perf_counter()
    n = 0
    while True:
        target = t_start + n * period
        now = time.perf_counter()
        if now >= t_start + duration:
            break
        if now < target:
            time.sleep(min(target - now, 0.01))
            continue
        t0 = time.perf_counter()
        try:
            fut = srv.submit("bench", rows_feed)
        except AdmissionError:
            shed[0] += 1
            n += 1
            continue

        def done(f, t0=t0):
            with lock:
                if f.exception() is None:
                    lat_ms.append((time.perf_counter() - t0) * 1e3)
        fut.add_done_callback(done)
        pending.append(fut)
        n += 1
    for f in pending:
        try:
            f.result(timeout=60)
        except Exception:
            pass
    wall = time.perf_counter() - t_start
    return (len(lat_ms) / wall if wall > 0 else 0.0), lat_ms, shed[0]


def _continuous(seqs: int, slots: int, new_tokens: int):
    """Multi-slot continuous decode vs sequential single-slot decode of the
    same prompts: tokens/s both ways + per-sequence token parity."""
    from paddle_tpu.serving import ContinuousBatcher, make_toy_lm

    max_len = 8 + new_tokens
    step_fn, init_fn = make_toy_lm(vocab=64, hidden=16, max_len=max_len,
                                   seed=3)
    prompts = [[(7 * i + j) % 64 for j in range(2 + i % 5)]
               for i in range(seqs)]

    cb = ContinuousBatcher(step_fn, init_fn, num_slots=slots,
                           max_len=max_len)
    cb.decode(prompts[:1], max_new_tokens=new_tokens)  # compile, off-clock
    t0 = time.perf_counter()
    multi = cb.decode(prompts, max_new_tokens=new_tokens)
    t_multi = time.perf_counter() - t0

    seq = ContinuousBatcher(step_fn, init_fn, num_slots=1, max_len=max_len)
    seq.decode(prompts[:1], max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    sequential = [seq.decode([p], max_new_tokens=new_tokens)[0]
                  for p in prompts]
    t_seq = time.perf_counter() - t0

    toks = sum(len(t) for t in multi)
    return {
        "sequences": seqs, "slots": slots, "new_tokens": new_tokens,
        "tok_s_continuous": round(toks / t_multi, 1) if t_multi else None,
        "tok_s_sequential": round(toks / t_seq, 1) if t_seq else None,
        "decode_speedup": round(t_seq / t_multi, 2) if t_multi else None,
        "parity": multi == sequential,
    }


def _paged(seqs: int, new_tokens: int):
    """The paged-KV serving blocks: fixed-HBM concurrency, decode tok/s at
    equal HBM vs the continuous path, chunked-prefill TTFT isolation, and
    prefix-cache hit rate."""
    import numpy as np

    from paddle_tpu.serving import ContinuousBatcher, make_toy_lm
    from paddle_tpu.serving import paged as P

    hidden, bs, nb, maxb = 32, 8, 64, 32
    max_len = maxb * bs                      # the provisioned capability
    model = P.make_paged_toy_lm(vocab=64, hidden=hidden, max_positions=512,
                                seed=3)
    rec = {"block_size": bs, "num_blocks": nb, "max_blocks_per_seq": maxb,
           "hidden": hidden}

    # -- capacity: short requests admitted at fixed pool HBM ------------------
    # 9 prompt + 7 new = 16 tokens = exactly 2 blocks per sequence, so the
    # admission count is pure allocator physics (no decode-time growth).
    # The dense equivalent reserves max_blocks_per_seq per slot (every
    # sequence provisioned for max_len — the ContinuousBatcher model).
    cache = P.PagedKVCache(model, nb, bs)
    dec = P.PagedDecoder(model, cache, max_seqs=nb,
                         max_blocks_per_seq=maxb)
    rng = np.random.default_rng(5)
    handles = []
    while True:
        h = dec.try_join([int(t) for t in rng.integers(0, 64, 9)], 7)
        if h is None:
            break
        handles.append(h)
    paged_cap = len(handles)
    for h in handles:
        dec.evict(h)
    dense_slots_cap = max(1, nb // maxb)
    rec["capacity"] = {
        "pool_bytes": cache.bytes, "paged_concurrent": paged_cap,
        "dense_slots": dense_slots_cap,
        "concurrent_speedup": round(paged_cap / dense_slots_cap, 2)}

    # -- decode tok/s at equal HBM vs the continuous path ---------------------
    cache = P.PagedKVCache(model, nb, bs)
    dec = P.PagedDecoder(model, cache, max_seqs=16,
                         max_blocks_per_seq=maxb)
    prompts = [[int(t) for t in rng.integers(0, 64, 4)] for _ in range(seqs)]
    dec.decode(prompts[:1], max_new_tokens=new_tokens)  # compile, off-clock
    t_paged = math.inf
    for _ in range(3):                       # best-of-3 rides out host noise
        t0 = time.perf_counter()
        paged_out = dec.decode(prompts, max_new_tokens=new_tokens)
        t_paged = min(t_paged, time.perf_counter() - t0)
    parity = all(
        paged_out[i] == P.dense_reference_decode(model, prompts[i],
                                                 new_tokens)
        for i in range(min(4, seqs)))

    # the dense pool gets the SAME bytes: rows provisioned at max_len
    dense_row = max_len * hidden * 4
    cont_slots = max(1, int(cache.bytes // dense_row))
    step_fn, init_fn = make_toy_lm(vocab=64, hidden=hidden, max_len=max_len,
                                   seed=3)
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=cont_slots,
                           max_len=max_len)
    cb.decode(prompts[:1], max_new_tokens=new_tokens)
    t_cont = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        cb.decode(prompts, max_new_tokens=new_tokens)
        t_cont = min(t_cont, time.perf_counter() - t0)
    toks = sum(len(t) for t in paged_out)
    rec["decode"] = {
        "sequences": seqs, "max_seqs": 16,
        "dense_slots_equal_hbm": cont_slots,
        "tok_s_paged": round(toks / t_paged, 1) if t_paged else None,
        "tok_s_continuous": round(toks / t_cont, 1) if t_cont else None,
        "decode_speedup": round(t_cont / t_paged, 2) if t_paged else None,
        "parity": parity}

    # -- chunked prefill: short-request TTFT, alone vs long-prompt mix --------
    chunk = 4
    long_tokens, short_tokens, n_short = 64, 6, 4

    def _ttft(with_long: bool):
        c = P.PagedKVCache(model, nb, bs)
        d = P.PagedDecoder(model, c, max_seqs=8, max_blocks_per_seq=16,
                           prefill_chunk=chunk)
        # compile off-clock across the gather-width ladder both runs will
        # touch (the step width tracks the longest live table, so the long
        # prompt and the shorts hit different compiled shapes)
        d.decode([[1, 2, 3]], 2)
        d.decode([[int(t) for t in rng.integers(0, 64, short_tokens)]],
                 short_tokens)
        d.decode([[int(t) for t in rng.integers(0, 64, long_tokens)]], 4)
        if with_long:
            d.join([int(t) for t in rng.integers(0, 64, long_tokens)], 4)
        shorts = [d.join([int(t) for t in rng.integers(0, 64,
                                                       short_tokens)], 4)
                  for _ in range(n_short)]
        ttft_ms, ttft_steps = {}, {}
        steps = 0
        while d.active_count:
            d.step()
            steps += 1
            now = time.perf_counter()
            for i, h in enumerate(shorts):
                if h.tokens and i not in ttft_ms:
                    ttft_ms[i] = (now - h._t_submit) * 1e3
                    ttft_steps[i] = steps
        return list(ttft_ms.values()), max(ttft_steps.values())

    alone_ms, alone_steps = _ttft(with_long=False)
    mixed_ms, mixed_steps = _ttft(with_long=True)
    rec["ttft_mix"] = {
        "long_tokens": long_tokens, "short_tokens": short_tokens,
        "prefill_chunk": chunk,
        "short_ttft_alone_p99_ms": _percentiles(alone_ms)["p99_ms"],
        "short_ttft_mixed_p99_ms": _percentiles(mixed_ms)["p99_ms"],
        "short_ttft_alone_steps": alone_steps,
        "short_ttft_mixed_steps": mixed_steps}

    # -- prefix cache: shared system prompt, unique suffixes ------------------
    cache = P.PagedKVCache(model, nb, bs)
    dec = P.PagedDecoder(model, cache, max_seqs=4, max_blocks_per_seq=16)
    sys_prompt = [int(t) for t in rng.integers(0, 64, 32)]
    n_req = 8
    lookups_per_req = (len(sys_prompt) + 3 - 1) // bs   # full blocks probed
    h0 = P.KV_PREFIX_HITS.value()
    c0 = P.KV_PREFILL_CHUNKS.value()
    dec.decode([sys_prompt + [int(t) for t in rng.integers(0, 64, 3)]], 4)
    cold_chunks = P.KV_PREFILL_CHUNKS.value() - c0
    c1 = P.KV_PREFILL_CHUNKS.value()
    for _ in range(n_req - 1):
        dec.decode([sys_prompt + [int(t) for t in rng.integers(0, 64, 3)]],
                   4)
    warm_chunks = (P.KV_PREFILL_CHUNKS.value() - c1) / (n_req - 1)
    hits = P.KV_PREFIX_HITS.value() - h0
    rec["prefix_cache"] = {
        "requests": n_req, "system_prompt_tokens": len(sys_prompt),
        "prefix_hits": int(hits),
        "hit_rate": round(hits / (n_req * lookups_per_req), 3),
        "prefill_chunks_cold": int(cold_chunks),
        "prefill_chunks_warm_mean": round(warm_chunks, 2)}
    return rec


def _occupancy_hist():
    """The serve.batch_size histogram (cumulative bucket counts) from the
    metrics registry — how full dispatched batches were."""
    from paddle_tpu.utils import monitor

    doc = monitor.default_registry().to_json()
    m = doc.get("metrics", {}).get("serve.batch_size")
    for s in (m or {}).get("samples", []):
        return {"buckets": s.get("buckets", {}),
                "count": s.get("count"),
                "mean": (round(s["sum"] / s["count"], 2)
                         if s.get("count") else None)}
    return None


def run_bench(args) -> dict:
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.core import flags

    flags.set_flags({"metrics": True})  # occupancy hist + SLO data
    edges = tuple(int(e) for e in args.buckets.split(","))
    main, y, scope = _build_tenant(args.hidden)
    rng = np.random.default_rng(0)
    rows_feed = {"x": rng.normal(size=(1, args.hidden)).astype(np.float32)}

    record = {"bench": "servebench", "schema": 1, "hidden": args.hidden,
              "buckets": list(edges), "max_wait_ms": args.max_wait_ms,
              "clients": args.clients}

    # baseline: one closed-loop client == single-request-at-a-time.
    # max_wait_ms=0 so the dispatcher never holds its lone request open
    # waiting for rows that cannot come — the honest serialized floor
    with _mk_server(serving, edges, 0.0) as srv:
        srv.add_tenant("bench", main, ["x"], [y], scope)
        srv.submit("bench", rows_feed).result()  # compile b1, off-clock
        qps0, lat0 = _closed_loop(srv, rows_feed, 1, args.duration)
    record["baseline"] = {"qps": round(qps0, 1), **_percentiles(lat0)}

    # batched: N concurrent closed-loop clients through the bucket ladder
    with _mk_server(serving, edges, args.max_wait_ms) as srv:
        srv.add_tenant("bench", main, ["x"], [y], scope)
        srv.submit("bench", rows_feed).result()
        qps1, lat1 = _closed_loop(srv, rows_feed, args.clients,
                                  args.duration)
    record["batched"] = {"qps": round(qps1, 1), **_percentiles(lat1)}
    record["speedup"] = round(qps1 / qps0, 2) if qps0 else None
    record["occupancy_hist"] = _occupancy_hist()

    # open loop: ramp the injection rate, watch saturation + shedding
    if args.qps_ramp:
        levels = []
        for qps in (float(q) for q in args.qps_ramp.split(",")):
            with _mk_server(serving, edges, args.max_wait_ms,
                            slo_p99_ms=args.slo_p99_ms) as srv:
                srv.add_tenant("bench", main, ["x"], [y], scope)
                srv.submit("bench", rows_feed).result()
                aq, lats, shed = _open_loop(srv, rows_feed, qps,
                                            args.duration)
            levels.append({"target_qps": qps, "achieved_qps": round(aq, 1),
                           "shed": shed, **_percentiles(lats)})
        record["open_loop"] = levels

    record["continuous"] = _continuous(args.seqs, args.slots,
                                       args.new_tokens)
    if args.paged:
        record["paged"] = _paged(args.seqs, args.new_tokens)
    return record


def _selfcheck() -> int:
    ns = _parser().parse_args(
        ["--duration", "0.8", "--clients", "8", "--buckets", "1,2,4,8",
         "--qps-ramp", "40", "--seqs", "6", "--slots", "4",
         "--new-tokens", "5", "--hidden", "16", "--paged"])
    rec = run_bench(ns)
    assert rec["baseline"]["qps"] > 0 and rec["batched"]["qps"] > 0
    assert rec["baseline"]["p99_ms"] is not None
    assert rec["continuous"]["parity"] is True, "decode parity broken"
    assert rec["occupancy_hist"] is not None
    assert rec["open_loop"][0]["achieved_qps"] > 0
    pg = rec["paged"]
    assert pg["decode"]["parity"] is True, "paged decode parity broken"
    assert pg["capacity"]["concurrent_speedup"] > 1
    assert pg["prefix_cache"]["prefix_hits"] > 0
    assert pg["prefix_cache"]["prefill_chunks_warm_mean"] < \
        pg["prefix_cache"]["prefill_chunks_cold"]
    print(json.dumps(rec))
    print("servebench selfcheck: OK")
    return 0


def _parser():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds per load phase")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--buckets", default="1,2,4,8,16,32")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--qps-ramp", default="",
                    help="comma-separated open-loop target qps levels")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="enable SLO load-shedding in the open-loop phases")
    ap.add_argument("--seqs", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--paged", action="store_true",
                    help="run the paged-KV serving blocks (capacity, "
                         "decode vs continuous, TTFT mix, prefix cache)")
    ap.add_argument("--out", default="",
                    help="also write the JSON document here")
    ap.add_argument("--selfcheck", action="store_true")
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    from paddle_tpu.core.jax_cache import configure_compile_cache

    configure_compile_cache()
    if args.selfcheck:
        return _selfcheck()
    rec = run_bench(args)
    line = json.dumps(rec)
    print(line)
    if args.out:
        doc = {
            "_note": ("servebench run on XLA:CPU — absolute qps measures "
                      "host dispatch, not TPU compute; 'speedup' (server-"
                      "side batching vs single-request-at-a-time) and "
                      "'continuous.parity' are the portable numbers."),
            "environment": "cpu",
            "record": rec,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
