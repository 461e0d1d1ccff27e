"""Tracked serving benchmark gate — batched serving vs per-query solving.

Replays the three synthetic workload scenarios (repro/serve/workload.py)
through the serving subsystem in closed loop (submit everything, drain)
and measures queries/s, then replays the SAME trace sequentially — one
fresh ``frontier`` engine solve per query, no dedup, no cache, no
batching, which is what the repo could do before the serve layer existed
— and writes the comparison to ``BENCH_serve.json``.

The ``gate`` section asserts, on the largest Zipf point:

* batched-serving queries/s >= ``min_ratio`` x sequential per-query
  solving (1.5x at the full n=10000 scale; 1.0x for smoke-sized corpora
  where fixed overheads dominate), and
* the distance cache actually hits on the skewed scenario (hit rate > 0)
  — the workload property the whole cache exists for.

Correctness rides along like run_bench.py: every served answer on the
verified points is checked bitwise against a fresh ``serial`` solve.

``--devices P`` (default 1) adds the SHARDED serving leg: the same Zipf
replay on a larger graph routed through the vertex-partitioned engines
(serve/dispatch.py) on a P-device mesh — emulated host devices on CPU, the
MPI-procs analogue — against the single-device serve stack on the same
graph.  Its ``gate_sharded`` asserts the union-frontier engine relaxes
STRICTLY fewer edges per solved source than per-query single-device
``frontier`` solves (the coalescing win of arXiv:1903.12085, measured),
and at n >= 20000 additionally that sharded steady-state throughput
>= 1.0x the single-device route (the crossover DEFAULT_SHARD_THRESHOLD
encodes); smoke corpora record the ratio without enforcing it, since
below the crossover the exchange overhead is expected to dominate.

``--overload`` adds the DEGRADED-MODE leg (README.md §Robustness): the
sustainable p2p service rate is measured closed-loop, then the same
workload is offered OPEN-LOOP at 2x that rate against (a) an
unprotected scheduler — unbounded queue, no deadlines, queueing delay
compounds without limit — and (b) a protected one (bounded queue +
per-query deadlines + landmark/stale degradation).  Its
``gate_overload`` asserts the protected scheduler SHEDS OR DEGRADES
rather than collapses: every accepted query is answered, the overload
protection actually engages (load rejected/shed/expired, or answered
degraded from landmark bounds), and the p99 latency of served (ok)
answers stays <= 2x the deadline — while the unprotected p99 is
recorded for contrast.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
                                                    [--out PATH]
                                                    [--devices P]
                                                    [--overload]

Spliced into EXPERIMENTS.md by benchmarks/make_experiments_md.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import time

import numpy as np

import jax

from benchmarks.common import REPO
from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.launch.runtime import enable_compile_cache, use_devices
from repro.serve import (DispatchPolicy, DistanceCache, GraphRegistry,
                         MicroBatchScheduler, QueryRejected, SCENARIOS,
                         make_trace)

DEFAULT_OUT = os.path.join(REPO, "BENCH_serve.json")

# scenario trace parameters (rate only shapes arrival stamps; both sides
# replay closed-loop so the comparison is pure service throughput)
RATE = 1000.0
LANDMARKS = 8
MAX_BATCH = 16
CACHE_ROWS = 256


def _make_scheduler(cg, dispatch=None, **sched_kwargs):
    """Serving stack for one graph with the jit cache pre-warmed (one
    compile per source-bucket size a drain can hit, plus the p2p path)
    — compiles stay outside the timed windows, as run_bench.py does.
    Prewarms whichever engine family ``dispatch`` will route this graph
    to; default is an explicit never-shard policy so the single-device
    section measures the same stack at any ``--devices``.  Extra kwargs
    reach the scheduler (the overload leg's max_queue/degrade knobs)."""
    import jax.numpy as jnp

    from repro.core.bellman_csr import sssp_multisource_csr
    from repro.core.frontier import sssp_frontier

    if dispatch is None:
        dispatch = DispatchPolicy(shard_threshold=None)
    registry = GraphRegistry()
    cache = DistanceCache(capacity=CACHE_ROWS)
    sched = MicroBatchScheduler(registry, cache, max_batch=MAX_BATCH,
                                dispatch=dispatch, **sched_kwargs)
    handle = registry.register("g", cg, landmarks=LANDMARKS)
    if dispatch.would_shard(cg.n):
        from repro.core.sharded_csr import (sssp_frontier_sharded,
                                            sssp_multisource_csr_sharded)

        ch = dispatch.choose(handle, kind="batch")
        parts = handle.partition(ch.nprocs)
        pops = handle.partition_ops(ch.mesh, ch.axis)
        b = 1
        while True:
            sssp_multisource_csr_sharded(
                parts, jnp.zeros((b,), jnp.int32), ch.mesh, axis=ch.axis,
                ops=pops)
            if b >= MAX_BATCH:
                break
            b *= 2
        sssp_frontier_sharded(parts, 0, ch.mesh, axis=ch.axis, ops=pops)
        return sched
    b = 1
    while True:
        sssp_multisource_csr(handle.csr_ops(),
                             jnp.zeros((b,), jnp.int32), n=cg.n)
        if b >= MAX_BATCH:
            break
        b *= 2
    sssp_frontier(handle.frontier_ops(), jnp.int32(0), n=cg.n,
                  target=jnp.int32(1), target_lb=jnp.float32(0.0))
    sssp_frontier(handle.frontier_ops(), jnp.int32(0), n=cg.n,
                  target=jnp.int32(1))
    return sched


def _drain_timed(sched, events, cg, *, verify: bool):
    """Submit + drain one trace closed-loop; returns (qps, hit_rate over
    this drain only)."""
    h0, m0 = sched.cache.hits, sched.cache.misses
    t0 = time.perf_counter()
    for e in events:
        sched.submit("g", e.source, e.target, arrival=e.arrival)
    answers = sched.drain()
    dt = time.perf_counter() - t0
    if verify:
        _verify(cg, answers)
    probes = (sched.cache.hits - h0) + (sched.cache.misses - m0)
    hit_rate = (sched.cache.hits - h0) / probes if probes else 0.0
    return len(events) / dt, hit_rate


def _replay_sequential(cg, events):
    """The pre-serve baseline: one fresh frontier solve per query, in
    trace order — no dedup, no cache, no batching.  Point-to-point
    queries index the solved row (no target early exit — that
    optimization belongs to the serving layer under test)."""
    shortest_paths(cg, 0, engine="frontier")               # warm jit
    t0 = time.perf_counter()
    for e in events:
        res = shortest_paths(cg, e.source, engine="frontier")
        _ = res.dist if e.target is None else float(res.dist[e.target])
    return len(events) / (time.perf_counter() - t0)


def _verify(cg, answers):
    rows = {}
    for a in answers:
        q = a.query
        if q.source not in rows:
            rows[q.source] = shortest_paths(cg, q.source,
                                            engine="serial").dist
        ref = rows[q.source]
        if q.target is None:
            ok = np.array_equal(a.value, ref)
        else:
            got, want = np.float32(a.value), ref[q.target]
            ok = got == want or (np.isinf(got) and np.isinf(want))
        if not ok:
            raise SystemExit(
                f"served answer mismatch vs serial: {q} via {a.via}")


def _run_sharded(smoke: bool, devices: int):
    """The --devices P leg: one Zipf cold+steady replay through the
    sharded route vs the single-device route on the same (larger) graph,
    plus the per-solve edge-work comparison against fresh per-query
    ``frontier`` solves.  Returns (record, gate_sharded)."""
    n = 1000 if smoke else 20000
    queries = 120 if smoke else 400
    verify = smoke or n <= 2000
    cg = C.random_csr_graph(n, 3 * n, seed=n)
    cold = make_trace("zipf", [("g", n)], num_queries=queries,
                      rate=RATE, seed=7, hot_seed=13)
    steady = make_trace("zipf", [("g", n)], num_queries=queries,
                        rate=RATE, seed=8, hot_seed=13)

    sched1 = _make_scheduler(cg)            # never-shard policy
    _drain_timed(sched1, cold, cg, verify=False)
    qps1, _ = _drain_timed(sched1, steady, cg, verify=False)

    shard_pol = DispatchPolicy(shard_threshold=n, nprocs=devices)
    schedP = _make_scheduler(cg, dispatch=shard_pol)
    qpsP_cold, _ = _drain_timed(schedP, cold, cg, verify=verify)
    qpsP, hitP = _drain_timed(schedP, steady, cg, verify=verify)
    s = schedP.stats()
    assert s["sharded_sources"] > 0, "sharded route never engaged"

    # edge-work baseline: fresh single-device frontier solves, one per
    # distinct trace source (what serving each query unbatched costs).
    srcs = sorted({e.source for e in cold + steady})
    base = [shortest_paths(cg, src, engine="frontier").edges_relaxed
            for src in srcs]
    frontier_per_solve = sum(base) / len(base)
    sharded_per_solve = s["sharded_edges"] / s["sharded_sources"]

    rec = {
        "scenario": "zipf-sharded", "n": n, "m": 3 * n,
        "devices": shard_pol.nprocs, "queries_per_trace": queries,
        "sharded_cold_qps": round(qpsP_cold, 2),
        "sharded_steady_qps": round(qpsP, 2),
        "single_steady_qps": round(qps1, 2),
        "speedup_vs_single_steady": round(qpsP / qps1, 3),
        "steady_cache_hit_rate": round(hitP, 4),
        "sharded_batches": s["sharded_batches"],
        "sharded_p2p": s["sharded_p2p"],
        "sharded_sources": s["sharded_sources"],
        "sharded_edges_per_solve": round(sharded_per_solve, 1),
        "frontier_edges_per_solve": round(frontier_per_solve, 1),
        "verified_bitwise": verify,
    }
    print(f"  sharded  n={n} P={shard_pol.nprocs}: cold {qpsP_cold:8.1f} / "
          f"steady {qpsP:8.1f} q/s, single-device steady {qps1:7.1f} q/s "
          f"({rec['speedup_vs_single_steady']:.2f}x) | edges/solve "
          f"{sharded_per_solve:.0f} vs frontier {frontier_per_solve:.0f}",
          flush=True)
    enforce_ratio = n >= 20000
    gate = {
        "rule": ("sharded union-frontier serving relaxes strictly fewer "
                 "edges per solved source than per-query frontier solves"
                 + (f", and sharded steady-state Zipf throughput >= 1.0x "
                    f"the single-device route at n={n}" if enforce_ratio
                    else f" (throughput ratio recorded, not enforced below "
                         f"the n=20000 crossover; n={n})")),
        "speedup_vs_single_steady": rec["speedup_vs_single_steady"],
        "min_ratio": 1.0,
        "ratio_enforced": enforce_ratio,
        "edges_ratio": round(sharded_per_solve / frontier_per_solve, 4),
        "pass": bool(sharded_per_solve < frontier_per_solve
                     and (not enforce_ratio or qpsP / qps1 >= 1.0)),
    }
    return rec, gate


def _replay_open_loop(sched, events):
    """Wall-clock open-loop replay with deadlines: submits when arrivals
    pass (dropping backpressure-rejected ones), ticks with the live
    clock so expiry/degradation engage.  Returns (answers, rejected)."""
    events = sorted(events, key=lambda e: e.arrival)
    t0 = time.perf_counter()
    i, answers, rejected = 0, [], 0
    while i < len(events) or sched.pending:
        now = time.perf_counter() - t0
        while i < len(events) and events[i].arrival <= now:
            e = events[i]
            try:
                sched.submit("g", e.source, e.target, arrival=e.arrival,
                             deadline=e.deadline)
            except QueryRejected:
                rejected += 1
            i += 1
        if sched.pending:
            out = sched.tick(now)
            done = time.perf_counter() - t0
            for a in out:
                a.done_at = done
            answers.extend(out)
        elif i < len(events):
            time.sleep(min(events[i].arrival - now, 1e-3))
    return answers, rejected


def _p99(latencies) -> float:
    lat = np.asarray(sorted(latencies), np.float64)
    return float(np.percentile(lat, 99)) if lat.size else 0.0


def _run_overload(smoke: bool):
    """The --overload leg (see module docstring): 2x-sustainable offered
    load against the unprotected vs the protected scheduler.  Returns
    (record, gate_overload)."""
    n = 1000 if smoke else 10000
    span = 0.5 if smoke else 1.0          # seconds of offered arrivals
    cg = C.random_csr_graph(n, 3 * n, seed=n)

    # Both schedulers under test are warmed IN PLACE (distance cache +
    # staged operands, on top of _make_scheduler's jit prewarm) before
    # the overload arrives: the leg measures a steady-state server hit
    # with 2x load, not a cold start whose first tick alone outlives
    # every deadline.
    warm = make_trace("p2p", [("g", n)], num_queries=160, rate=RATE,
                      seed=7, hot_seed=13)
    steady = make_trace("p2p", [("g", n)], num_queries=160, rate=RATE,
                        seed=8, hot_seed=13)
    schedU = _make_scheduler(cg)
    _drain_timed(schedU, warm, cg, verify=False)
    # sustainable service rate: closed-loop steady drain, warm cache
    capacity, _ = _drain_timed(schedU, steady, cg, verify=False)
    # service-time-aware deadline: a full batch costs ~MAX_BATCH/capacity
    # seconds of solve time on THIS host at THIS graph size, so each query
    # gets a few batch-times of budget.  A fixed wall-clock deadline is
    # either unservable (one n=10000 tick outlives it — served p99 can
    # never meet the gate no matter how well the scheduler sheds) or
    # trivially loose at smoke size.
    deadline = float(min(max(6.0 * MAX_BATCH / capacity, 0.1), 1.0))
    # protected: bounded queue + deadlines + degraded fallbacks.
    # margin = deadline/2: a query that has burned half its budget in the
    # queue is answered from landmark bounds instead of gambling on an
    # exact solve it may not get — the knob that makes degraded answers
    # actually appear under 2x load rather than only expiries.
    schedP = _make_scheduler(cg, max_queue=16 * MAX_BATCH,
                             degrade_margin=deadline / 2)
    _drain_timed(schedP, warm, cg, verify=False)
    _drain_timed(schedP, steady, cg, verify=False)
    offered = 2.0 * capacity
    # enough arrivals to span many ticks at the offered rate — an
    # open-loop trace shorter than one tick is just a burst, not load.
    queries = int(min(max(offered * span, 240), 4000))
    trace = make_trace("p2p", [("g", n)], num_queries=queries,
                       rate=offered, seed=9, hot_seed=13,
                       deadline=deadline)

    # unprotected: unbounded queue, no deadlines — queueing compounds
    ansU, _ = _replay_open_loop(
        schedU, [dataclasses.replace(e, deadline=None) for e in trace])
    p99_unprotected = _p99(a.done_at - a.query.arrival for a in ansU)

    ansP, rejected = _replay_open_loop(schedP, trace)
    served = [a for a in ansP if a.status == "ok"]
    _verify(cg, [a for a in served if a.exact])
    p99_served = _p99(a.done_at - a.query.arrival for a in served)
    sP = schedP.stats()
    shed_total = rejected + sP["shed"] + sP["deadline_expired"]
    accepted = queries - rejected

    rec = {
        "scenario": "p2p-overload", "n": n, "m": 3 * n,
        "queries": queries, "deadline_s": round(deadline, 3),
        "sustainable_qps": round(capacity, 2),
        "offered_qps": round(offered, 2),
        "unprotected_p99_s": round(p99_unprotected, 4),
        "protected_p99_served_s": round(p99_served, 4),
        "accepted": accepted,
        "answered": len(ansP),
        "served_ok": len(served),
        "served_degraded": sP["degraded_p2p"] + sP["degraded_batch"],
        "rejected_at_submit": rejected,
        "shed": sP["shed"],
        "deadline_expired": sP["deadline_expired"],
        "statuses": sP["answered_status"],
    }
    degraded = rec["served_degraded"]
    print(f"  overload n={n}: offered {offered:7.1f} q/s (2x sustainable "
          f"{capacity:.1f}) | protected p99 {p99_served * 1e3:.1f} ms "
          f"({len(served)} served, {degraded} degraded, "
          f"{shed_total} shed/rejected/expired) vs unprotected p99 "
          f"{p99_unprotected * 1e3:.1f} ms", flush=True)
    gate = {
        "rule": (f"at 2x sustainable offered load the protected scheduler "
                 f"sheds or degrades instead of collapsing: every accepted "
                 f"query is answered, overload protection actually engages "
                 f"(rejected/shed/expired or degraded answers > 0), and "
                 f"served-answer p99 stays <= 2x the {deadline:.3f}s "
                 f"service-time-scaled deadline "
                 f"(unprotected p99 recorded for contrast)"),
        "protected_p99_served_s": rec["protected_p99_served_s"],
        "p99_bound_s": 2 * deadline,
        "shed_total": shed_total,
        "degraded": degraded,
        "all_accepted_answered": bool(len(ansP) == accepted),
        "pass": bool(len(ansP) == accepted and shed_total + degraded > 0
                     and p99_served <= 2 * deadline),
    }
    return rec, gate


def _run_obs(smoke: bool, trace_out=None):
    """The --obs leg: TWO identically-warmed serving stacks drain the
    same fresh-seeded Zipf steady traces — one with tracing disabled,
    one with a live Tracer + CostLog installed — so both sides see the
    identical steady mix of cache hits and engine solves.  The gate
    pins the enabled/disabled throughput ratio >= 0.9 (best of 3 paired
    drains) — tracing must stay out of the solve hot path.  With
    ``trace_out`` the enabled side's artifacts are written + validated
    (chains included: the drains go through submit/tick/solve/answer).
    Returns (record, gate_obs)."""
    from repro.obs import (CostLog, Tracer, cost_path_for, finalize_capture,
                           set_cost_log, set_tracer)

    n = 1000 if smoke else 10000
    queries = 120 if smoke else 400
    # smoke drains finish in ~30 ms, where run-to-run jitter swamps any
    # real tracing cost — take best-of-more there; full-size drains run
    # for seconds and settle with 3.
    reps = 7 if smoke else 3
    cg = C.random_csr_graph(n, 3 * n, seed=n)
    cold = make_trace("zipf", [("g", n)], num_queries=queries,
                      rate=RATE, seed=7, hot_seed=13)
    sched_off = _make_scheduler(cg)
    sched_on = _make_scheduler(cg)
    _drain_timed(sched_off, cold, cg, verify=False)
    _drain_timed(sched_on, cold, cg, verify=False)
    tr, cl = Tracer(), CostLog()
    off_qps, on_qps = [], []
    for rep in range(reps):
        # fresh event seed per rep, shared hot set: every rep is a
        # steady-state drain (hot rows cached, cold tail solved), both
        # sides replay the identical trace, and the side order flips
        # each rep so clock/cache drift cannot bias one leg.
        steady = make_trace("zipf", [("g", n)], num_queries=queries,
                            rate=RATE, seed=8 + rep, hot_seed=13)

        def _off():
            off_qps.append(_drain_timed(sched_off, steady, cg,
                                        verify=False)[0])

        def _on():
            prev_tr, prev_cl = set_tracer(tr), set_cost_log(cl)
            try:
                on_qps.append(_drain_timed(sched_on, steady, cg,
                                           verify=False)[0])
            finally:
                set_tracer(prev_tr)
                set_cost_log(prev_cl)

        first, second = (_off, _on) if rep % 2 == 0 else (_on, _off)
        first()
        second()
    qps_off, qps_on = max(off_qps), max(on_qps)
    ratio = qps_on / qps_off
    if trace_out:
        errs = finalize_capture(tr, cl, trace_out)
        print(f"  obs      trace: {len(tr.spans)} spans -> {trace_out} | "
              f"{len(cl.records)} cost records -> {cost_path_for(trace_out)}",
              flush=True)
        if errs:
            for e in errs[:20]:
                print(f"  obs      trace INVALID: {e}", flush=True)
            raise SystemExit("observability capture invalid")
    rec = {
        "scenario": "zipf-obs", "n": n, "m": 3 * n,
        "queries_per_trace": queries, "reps": reps,
        "tracing_off_qps": round(qps_off, 2),
        "tracing_on_qps": round(qps_on, 2),
        "tracing_ratio": round(ratio, 4),
        "spans": len(tr.spans),
        "cost_records": len(cl.records),
    }
    print(f"  obs      n={n}: tracing off {qps_off:8.1f} / on "
          f"{qps_on:8.1f} q/s ({ratio:.3f}x, best of {reps}), "
          f"{len(tr.spans)} spans, {len(cl.records)} cost records",
          flush=True)
    gate = {
        "rule": (f"tracing-enabled steady Zipf serving throughput >= 0.9x "
                 f"tracing-disabled on the same warm trace at n={n} "
                 f"(best of {reps} drains each)"),
        "tracing_ratio": rec["tracing_ratio"],
        "min_ratio": 0.9,
        "pass": bool(ratio >= 0.9),
    }
    return rec, gate


def run(smoke: bool = False, out: str = DEFAULT_OUT, devices: int = 1,
        overload: bool = False, obs: bool = False,
        trace_out=None) -> str:
    n = 1000 if smoke else 10000
    queries = 120 if smoke else 400
    verify = smoke or n <= 2000       # serial verify is O(n^2)/row: cap it
    cg = C.random_csr_graph(n, 3 * n, seed=n)
    records = []
    for scen in SCENARIOS:
        # two traces per scenario, different event seeds but a SHARED
        # Zipf hot set (hot_seed): the first drain is the cold start, the
        # second measures the steady serving state where the hot rows are
        # already cached — the repeat-query regime of arXiv:1505.05033.
        cold_trace = make_trace(scen, [("g", n)], num_queries=queries,
                                rate=RATE, seed=7, hot_seed=13)
        steady_trace = make_trace(scen, [("g", n)], num_queries=queries,
                                  rate=RATE, seed=8, hot_seed=13)
        sched = _make_scheduler(cg)
        qps_cold, _ = _drain_timed(sched, cold_trace, cg, verify=verify)
        qps_steady, hit_steady = _drain_timed(sched, steady_trace, cg,
                                              verify=verify)
        qps_s = _replay_sequential(cg, steady_trace)
        stats = sched.stats()
        rec = {
            "scenario": scen, "n": n, "m": 3 * n,
            "queries_per_trace": queries,
            "batched_cold_qps": round(qps_cold, 2),
            "batched_steady_qps": round(qps_steady, 2),
            "sequential_qps": round(qps_s, 2),
            "speedup_steady": round(qps_steady / qps_s, 3),
            "speedup_cold": round(qps_cold / qps_s, 3),
            "steady_cache_hit_rate": round(hit_steady, 4),
            "mean_occupancy": stats["mean_occupancy"],
            "dedup_saved": stats["dedup_saved"],
            "answered_via": stats["answered_via"],
            "verified_bitwise": verify,
        }
        records.append(rec)
        print(f"  {scen:8s} n={n}: batched cold {qps_cold:8.1f} / steady "
              f"{qps_steady:8.1f} q/s, sequential {qps_s:7.1f} q/s "
              f"({rec['speedup_steady']:.2f}x steady), steady hit rate "
              f"{hit_steady:.2f}", flush=True)

    zipf = next(r for r in records if r["scenario"] == "zipf")
    min_ratio = 1.5 if n >= 10000 else 1.0
    gate = {
        "rule": (f"steady-state batched serving >= {min_ratio}x sequential "
                 f"per-query frontier solves on the Zipf trace at n={n}, "
                 f"and the distance cache hits on the skewed scenario"),
        "zipf_speedup_steady": zipf["speedup_steady"],
        "min_ratio": min_ratio,
        "zipf_steady_cache_hit_rate": zipf["steady_cache_hit_rate"],
        "pass": bool(zipf["speedup_steady"] >= min_ratio
                     and zipf["steady_cache_hit_rate"] > 0),
    }
    doc = {
        "schema": 2,
        "meta": {
            "created_unix": int(time.time()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "smoke": smoke,
            "devices": devices,
            "rate": RATE, "landmarks": LANDMARKS,
            "max_batch": MAX_BATCH, "cache_rows": CACHE_ROWS,
        },
        "results": records,
        "gate": gate,
    }
    if devices > 1:
        srec, sgate = _run_sharded(smoke, devices)
        doc["sharded_results"] = [srec]
        doc["gate_sharded"] = sgate
    if overload:
        orec, ogate = _run_overload(smoke)
        doc["overload_results"] = [orec]
        doc["gate_overload"] = ogate
    if obs:
        brec, bgate = _run_obs(smoke, trace_out=trace_out)
        doc["obs_results"] = [brec]
        doc["gate_obs"] = bgate
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"\nwrote {len(records)} scenario records to {out}")
    from benchmarks.gates import enforce
    enforce(doc)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized corpus (n=1000, short traces)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh size for the sharded leg (emulated host "
                         "devices on CPU; 1 = skip the leg)")
    ap.add_argument("--overload", action="store_true",
                    help="add the 2x-offered-load degraded-mode leg and "
                         "its shed-don't-collapse gate")
    ap.add_argument("--obs", action="store_true",
                    help="add the observability-overhead leg: tracing on "
                         "vs off on the same warm Zipf trace, gated at "
                         ">= 0.9x")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="with --obs: write + validate the enabled leg's "
                         "Chrome trace (and .cost.jsonl) here")
    args = ap.parse_args()
    enable_compile_cache()
    use_devices(args.devices)
    run(args.smoke, out=args.out, devices=args.devices,
        overload=args.overload, obs=args.obs, trace_out=args.trace_out)
