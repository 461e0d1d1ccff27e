"""A configuration, a traffic mix and a metric reader dropped in as new
files are found by their names, with no edit to any file already there."""
import json
import os

import jax

from bench import harness

READER = '''"""Queries answered in the window."""


def read(ctx):
    return float(sum(len(s.job.queries) for s in ctx["window"].sent))
'''


def test_new_files_are_found_by_name(tiny_root):
    root = tiny_root
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()

    with open(os.path.join(root, "bench", "configs",
                           "sparse-small.json"), "w") as f:
        json.dump({"name": "sparse-small", "generator": "random_connected",
                   "n": 400, "edges": 800, "max_weight": 10.0,
                   "directed": False,
                   "serving": {"max_batch": 16, "cache_rows": 8,
                               "landmarks": 0}}, f)
    with open(os.path.join(root, "bench", "traffic", "pairs.json"),
              "w") as f:
        json.dump({"graphs": 1, "sources": 30, "check_sources": 5,
                   "trace_jobs": 1,
                   "job": {"kind": "rows", "sources_per_job": 2}}, f)
    with open(os.path.join(root, "bench", "metrics",
                           "answered_queries.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "sparse-small", "source": "test",
                            "file": "bench/configs/sparse-small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "sparse-small.pairs",
                              "config": "sparse-small", "traffic": "pairs",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "answered_queries", "unit": "queries",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["sparse-small.pairs"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.load_cell("sparse-small.pairs", root)
    assert cell.config["name"] == "sparse-small"
    assert cell.mix["job"]["sources_per_job"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "answered_queries"]
    assert cell.per_layer == []
    result = harness.run_cell(jax, cell, 3, 0.3, False, t0=0.0, root=root)
    assert result["correct"]
    assert result["metrics"]["answered_queries"]["value"] == \
        result["attempted"]
    assert set(result["metrics"]) == {"setup_s", "answered_queries"}

    for p, data in before.items():          # nothing that was there moved
        with open(p, "rb") as fh:
            assert fh.read() == data, p
