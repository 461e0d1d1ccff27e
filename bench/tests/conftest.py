"""A copy of the benchmark's layout at a size the CPU runs in a second:
the same files, with the graphs, ranks and job lists cut down."""
import json
import os
import shutil

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

SIZES = {"tableii-40k": {"n": 2048, "edges": 6144, "arcs": 12288}}
MIXES = {"p2p": {"sources": 12, "check_sources": 6,
                 "job": {"kind": "p2p", "rank_exponents": [2, 4, 6, 8]}},
         "batch": {"graphs": 2, "sources": 64, "check_sources": 24}}


def make_tiny_root(dest: str) -> str:
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        _update(os.path.join(dest, c["file"]), SIZES[c["name"]])
    for w in spec["workloads"]:
        _update(os.path.join(dest, "bench", "traffic", w["traffic"] + ".json"),
                MIXES[w["traffic"]])
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest


def _update(path: str, changes: dict) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench_root")))
