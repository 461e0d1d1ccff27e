"""A copy of the benchmark's layout at a size the CPU runs in a second:
the same files, with the graphs, ranks and job lists cut down.  Each
configuration is cut by its generator's ``tiny()``, each traffic mix by
its job kind, so a cell added as new files is cut with no edit here."""
import json
import os
import shutil

import pytest

from bench import graphs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

MIXES = {"p2p": {"sources": 12, "check_sources": 6,
                 "job": {"kind": "p2p", "rank_exponents": [2, 4, 6, 8]}},
         "rows": {"graphs": 2, "sources": 64, "check_sources": 24}}


def make_tiny_root(dest: str) -> str:
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = os.path.join(dest, c["file"])
        config = _load(path)
        _update(path,
                graphs.generator(config["generator"], dest).tiny(config))
    for w in spec["workloads"]:
        path = os.path.join(dest, "bench", "traffic", w["traffic"] + ".json")
        _update(path, MIXES[_load(path)["job"]["kind"]])
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest


def build(config: dict, seed: int, index: int = 0):
    """Graph ``index`` of a run of ``seed``, drawn by this checkout's
    generator of ``config``."""
    return graphs.build(config, seed, index,
                        graphs.generator(config["generator"], ROOT))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _update(path: str, changes: dict) -> None:
    data = _load(path)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench_root")))
