"""A configuration, a graph generator, a traffic mix and a metric reader
dropped in as new files are found by their names, with no edit to any file
already there."""
import json
import os
import shutil

import jax

from bench import harness
from bench.tests.conftest import BENCH, make_tiny_root

READER = '''"""Queries answered in the window."""


def read(ctx):
    return float(sum(len(s.job.queries) for s in ctx["window"].sent))
'''


def snapshot(root: str) -> dict:
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    return before


def unmoved(before: dict) -> None:
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p


def test_new_files_are_found_by_name(tiny_root):
    root = tiny_root
    before = snapshot(root)

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

    unmoved(before)                         # nothing that was there moved


def test_a_new_generator_is_found_by_name(tmp_path):
    """A generator file under a name no file of the benchmark knows, with
    a configuration whose graphs have vertices with no arc, a rows mix
    and a cell: a run finds each by name and comes out correct."""
    root = make_tiny_root(str(tmp_path))
    before = snapshot(root)
    shutil.copy(os.path.join(BENCH, "graphs", "kronecker.py"),
                os.path.join(root, "bench", "graphs", "skewed_copy.py"))
    with open(os.path.join(root, "bench", "configs", "skewed-10.json"),
              "w") as f:
        json.dump({"name": "skewed-10", "generator": "skewed_copy",
                   "scale": 10, "edgefactor": 16,
                   "initiator": [0.57, 0.19, 0.19],
                   "serving": {"max_batch": 16, "cache_rows": 8,
                               "landmarks": 0}}, f)
    with open(os.path.join(root, "bench", "traffic", "keys.json"),
              "w") as f:
        json.dump({"graphs": 1, "sources": 16, "check_sources": 8,
                   "trace_jobs": 1,
                   "job": {"kind": "rows", "sources_per_job": 4}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "skewed-10", "source": "test",
                            "file": "bench/configs/skewed-10.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "skewed-10.keys",
                              "config": "skewed-10", "traffic": "keys",
                              "chips": 1, "why": "test"})
    rows_per_s, = [m for m in spec["end_to_end"]
                   if m["name"] == "rows_per_s"]
    rows_per_s["workloads"].append("skewed-10.keys")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.load_cell("skewed-10.keys", root)
    assert cell.config["generator"] == "skewed_copy"
    result = harness.run_cell(jax, cell, 2 ** 40 + 9, 0.3, False, t0=0.0,
                              root=root)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 4
    assert set(result["metrics"]) == {"setup_s", "rows_per_s"}
    unmoved(before)
