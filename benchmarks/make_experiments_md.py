"""Assemble EXPERIMENTS.md §SSSP-bench, §Serve-bench, §Dynamic-bench and
§Weak-scaling tables from BENCH_sssp.json, BENCH_serve.json,
BENCH_dynamic.json and experiments/bench/weak_scaling.csv (single sources
of truth), leaving hand-written sections (§Paper, §Perf) intact via
marker comments.

    PYTHONPATH=src python -m benchmarks.make_experiments_md
"""
from __future__ import annotations

import csv
import json
import os

from benchmarks.common import OUT_DIR, REPO

BENCH_JSON = os.path.join(REPO, "BENCH_sssp.json")
SERVE_JSON = os.path.join(REPO, "BENCH_serve.json")
DYNAMIC_JSON = os.path.join(REPO, "BENCH_dynamic.json")
WEAK_CSV = os.path.join(OUT_DIR, "weak_scaling.csv")
MD = os.path.join(REPO, "EXPERIMENTS.md")

BEGIN = "<!-- BEGIN GENERATED:{} -->"
END = "<!-- END GENERATED:{} -->"


def bench_tables(path: str) -> str:
    """BENCH_sssp.json (benchmarks/run_bench.py) -> per-point engine table
    plus the edges-relaxed gate summary."""
    with open(path) as f:
        doc = json.load(f)
    meta = doc["meta"]
    rows = [f"jax {meta['jax']} on {meta['backend']}"
            f"{' (smoke)' if meta.get('smoke') else ''}, "
            f"best of {meta['repeats']}; times are per source; sharded "
            f"engines run on {meta.get('devices', 1)} forced host devices.",
            "",
            "| corpus | n | m | engine | P | time_s/src | sweeps "
            "| edges relaxed |",
            "|---|---|---|---|---|---|---|---|"]
    for r in doc["results"]:
        er = r["edges_relaxed"]
        rows.append(
            f"| {r['corpus']} | {r['n']} | {r['m']} | {r['engine']} "
            f"| {r.get('procs', 1)} "
            f"| {r['time_s'] / r['sources']:.5f} | {r['sweeps'] or ''} "
            f"| {'' if er is None else er} |")
    gate = doc["gate"]
    rows += ["", f"**Gate** ({gate['rule']}): "
                 f"{'PASS' if gate['pass'] else 'FAIL'}",
             "",
             "| n | frontier edges | bellman_csr edges | ratio |",
             "|---|---|---|---|"]
    for p in gate["points"]:
        rows.append(f"| {p['n']} | {p['frontier_edges']} "
                    f"| {p['bellman_csr_edges']} | {p['edge_ratio']} |")
    gs = doc.get("gate_sharded")
    if gs:
        rows += ["", f"**Gate** ({gs['rule']}): "
                     f"{'PASS' if gs['pass'] else 'FAIL'}",
                 "",
                 "| n | P | frontier_sharded edges | frontier edges |",
                 "|---|---|---|---|"]
        for p in gs["points"]:
            rows.append(f"| {p['n']} | {p['procs']} "
                        f"| {p['frontier_sharded_edges']} "
                        f"| {p['frontier_edges']} |")
    gd = doc.get("gate_delta")
    if gd:
        rows += ["", f"**Gate** ({gd['rule']}): "
                     f"{'PASS' if gd['pass'] else 'FAIL'}",
                 "",
                 "| corpus | n | Δ phases | frontier sweeps "
                 "| Δ time_s | frontier time_s |",
                 "|---|---|---|---|---|---|"]
        for p in gd["points"]:
            rows.append(f"| {p['corpus']} | {p['n']} | {p['delta_phases']} "
                        f"| {p['frontier_sweeps']} | {p['delta_time_s']} "
                        f"| {p['frontier_time_s']} |")
    return "\n".join(rows)


def serve_table(path: str) -> str:
    """BENCH_serve.json (benchmarks/serve_bench.py) -> per-scenario
    serving table plus the throughput/cache gate summary."""
    with open(path) as f:
        doc = json.load(f)
    meta = doc["meta"]
    rows = [f"jax {meta['jax']} on {meta['backend']}"
            f"{' (smoke)' if meta.get('smoke') else ''}; closed-loop "
            f"drains, {meta['max_batch']} max distinct sources/tick, "
            f"{meta['landmarks']} landmarks, {meta['cache_rows']}-row "
            "cache; cold = first trace, steady = second trace over the "
            "same Zipf hot set; sequential = one fresh frontier solve "
            "per query.",
            "",
            "| scenario | n | queries | cold q/s | steady q/s "
            "| sequential q/s | steady speedup | steady hit rate "
            "| occupancy | dedup saved |",
            "|---|---|---|---|---|---|---|---|---|---|"]
    for r in doc["results"]:
        rows.append(
            f"| {r['scenario']} | {r['n']} | {r['queries_per_trace']} "
            f"| {r['batched_cold_qps']} | {r['batched_steady_qps']} "
            f"| {r['sequential_qps']} | {r['speedup_steady']}x "
            f"| {r['steady_cache_hit_rate']} | {r['mean_occupancy']} "
            f"| {r['dedup_saved']} |")
    gate = doc["gate"]
    rows += ["", f"**Gate** ({gate['rule']}): "
                 f"{'PASS' if gate['pass'] else 'FAIL'} — zipf steady "
                 f"speedup {gate['zipf_speedup_steady']}x (min "
                 f"{gate['min_ratio']}x), steady cache hit rate "
                 f"{gate['zipf_steady_cache_hit_rate']}"]
    srecs = doc.get("sharded_results")
    if srecs:
        rows += ["",
                 "Sharded route (serve/dispatch.py, --devices leg): the "
                 "same Zipf replay through the vertex-partitioned engines "
                 "vs the single-device serve stack on the same graph.",
                 "",
                 "| scenario | n | P | cold q/s | steady q/s "
                 "| single-device steady q/s | speedup | hit rate "
                 "| edges/solve | frontier edges/solve |",
                 "|---|---|---|---|---|---|---|---|---|---|"]
        for r in srecs:
            rows.append(
                f"| {r['scenario']} | {r['n']} | {r['devices']} "
                f"| {r['sharded_cold_qps']} | {r['sharded_steady_qps']} "
                f"| {r['single_steady_qps']} "
                f"| {r['speedup_vs_single_steady']}x "
                f"| {r['steady_cache_hit_rate']} "
                f"| {r['sharded_edges_per_solve']} "
                f"| {r['frontier_edges_per_solve']} |")
        gs = doc["gate_sharded"]
        rows += ["", f"**Gate** ({gs['rule']}): "
                     f"{'PASS' if gs['pass'] else 'FAIL'} — speedup "
                     f"{gs['speedup_vs_single_steady']}x"
                     f"{' (enforced)' if gs['ratio_enforced'] else ''}, "
                     f"edges ratio {gs['edges_ratio']}"]
    orecs = doc.get("overload_results")
    if orecs:
        rows += ["",
                 "Degraded mode (README.md §Robustness, --overload leg): "
                 "the same p2p workload offered OPEN-LOOP at 2x the "
                 "measured sustainable rate, unprotected scheduler vs "
                 "protected (bounded queue + per-query deadlines + "
                 "landmark degradation).",
                 "",
                 "| n | deadline | sustainable q/s | offered q/s "
                 "| unprotected p99 | protected p99 (served) | served ok "
                 "| degraded | rejected/shed/expired |",
                 "|---|---|---|---|---|---|---|---|---|"]
        for r in orecs:
            shed = (r["rejected_at_submit"] + r["shed"]
                    + r["deadline_expired"])
            rows.append(
                f"| {r['n']} | {r['deadline_s']}s "
                f"| {r['sustainable_qps']} | {r['offered_qps']} "
                f"| {round(r['unprotected_p99_s'] * 1e3, 1)} ms "
                f"| {round(r['protected_p99_served_s'] * 1e3, 1)} ms "
                f"| {r['served_ok']} | {r['served_degraded']} "
                f"| {shed} |")
        og = doc["gate_overload"]
        rows += ["", f"**Gate** ({og['rule']}): "
                     f"{'PASS' if og['pass'] else 'FAIL'} — protected "
                     f"p99 {round(og['protected_p99_served_s'] * 1e3, 1)} "
                     f"ms (bound {round(og['p99_bound_s'] * 1e3, 1)} ms), "
                     f"{og['shed_total']} shed + {og['degraded']} "
                     f"degraded"]
    brecs = doc.get("obs_results")
    if brecs:
        rows += ["",
                 "Observability overhead (README.md §Observability, --obs "
                 "leg): two identically-warmed serving stacks drain the "
                 "same steady Zipf traces, tracing disabled vs a live "
                 "Tracer + CostLog installed (repro/obs).",
                 "",
                 "| n | queries | reps | tracing off q/s | tracing on q/s "
                 "| ratio | spans | cost records |",
                 "|---|---|---|---|---|---|---|---|"]
        for r in brecs:
            rows.append(
                f"| {r['n']} | {r['queries_per_trace']} | {r['reps']} "
                f"| {r['tracing_off_qps']} | {r['tracing_on_qps']} "
                f"| {r['tracing_ratio']} | {r['spans']} "
                f"| {r['cost_records']} |")
        bg = doc["gate_obs"]
        rows += ["", f"**Gate** ({bg['rule']}): "
                     f"{'PASS' if bg['pass'] else 'FAIL'} — ratio "
                     f"{bg['tracing_ratio']} (min {bg['min_ratio']})"]
    return "\n".join(rows)


def dynamic_table(path: str) -> str:
    """BENCH_dynamic.json (benchmarks/dynamic_bench.py) -> per-batch-size
    repair-vs-resolve table plus the gate summary."""
    with open(path) as f:
        doc = json.load(f)
    meta = doc["meta"]
    rows = [f"jax {meta['jax']} on {meta['backend']}"
            f"{' (smoke)' if meta.get('smoke') else ''}; medians over "
            f"{meta['rounds']} chained mutation rounds per batch size "
            "(each round bitwise-verified against a full re-solve on the "
            "mutated graph); full = cold frontier solve on the same "
            "committed overlay operands.",
            "",
            "| n | m | batch edges | repair ms | full ms | speedup "
            "| repair edges | full edges | edge ratio | cone |",
            "|---|---|---|---|---|---|---|---|---|---|"]
    for r in doc["results"]:
        rows.append(
            f"| {r['n']} | {r['m']} | {r['batch_edges']} "
            f"| {r['repair_time_s'] * 1e3:.2f} | {r['full_time_s'] * 1e3:.2f} "
            f"| {r['speedup']}x | {r['repair_edges']} | {r['full_edges']} "
            f"| {r['edge_ratio']} | {r['cone_median']} |")
    gate = doc["gate"]
    rows += ["", f"**Gate** ({gate['rule']}): "
                 f"{'PASS' if gate['pass'] else 'FAIL'}"]
    return "\n".join(rows)


def weak_scaling_table(path: str) -> str:
    """experiments/bench/weak_scaling.csv (benchmarks/weak_scaling.py) ->
    fixed-n/proc scaling table: dense column slabs vs the vertex-
    partitioned CSR engines (the paper's footnote-7 experiment, the CSR
    leg at 8x the per-process vertex count since no dense matrix exists
    on that path)."""
    with open(path) as f:
        rd = list(csv.reader(f))
    rows = ["fixed vertices/process; efficiency = t(P=1) / t(P).",
            "",
            "| " + " | ".join(rd[0]) + " |",
            "|" + "---|" * len(rd[0])]
    for r in rd[1:]:
        rows.append("| " + " | ".join(r) + " |")
    return "\n".join(rows)


def splice(text: str, name: str, content: str) -> str:
    b, e = BEGIN.format(name), END.format(name)
    if b in text:
        pre, rest = text.split(b, 1)
        _, post = rest.split(e, 1)
        return pre + b + "\n" + content + "\n" + e + post
    return text + f"\n{b}\n{content}\n{e}\n"


def main():
    text = open(MD).read() if os.path.exists(MD) else "# EXPERIMENTS\n"
    if os.path.exists(BENCH_JSON):
        text = splice(text, "sssp-bench", bench_tables(BENCH_JSON))
    if os.path.exists(SERVE_JSON):
        text = splice(text, "serve-bench", serve_table(SERVE_JSON))
    if os.path.exists(DYNAMIC_JSON):
        text = splice(text, "dynamic-bench", dynamic_table(DYNAMIC_JSON))
    if os.path.exists(WEAK_CSV):
        text = splice(text, "weak-scaling", weak_scaling_table(WEAK_CSV))
    with open(MD, "w") as f:
        f.write(text)
    print("wrote tables"
          f"{' SSSP bench' if os.path.exists(BENCH_JSON) else ''}"
          f"{' + serve bench' if os.path.exists(SERVE_JSON) else ''}"
          f"{' + dynamic bench' if os.path.exists(DYNAMIC_JSON) else ''}"
          f"{' + weak scaling' if os.path.exists(WEAK_CSV) else ''}"
          f" into {MD}")


if __name__ == "__main__":
    main()
