import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count at first init.
#   setdefault lets tests/smoke runs override with their own XLA_FLAGS.
"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell against the production mesh, with ShapeDtypeStruct stand-ins
(no allocation), and record memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun

Each cell writes ``<out>/<arch>__<shape>__<mesh>.json`` containing:
  memory_analysis   bytes-per-device breakdown (proves the cell fits)
  cost_analysis     HLO FLOPs / bytes accessed (per-device program)
  collectives       payload bytes by kind, parsed from compiled HLO
  roofline          the three terms in seconds + dominant bottleneck

SSSP cells (the paper's engine at production scale) are included alongside
the 40 LM cells: --arch sssp --shape bellman_512k | dijkstra_128k |
multisource_128k.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import LONG_CONTEXT_ARCHS, SHAPES, ARCHS, get_config
from repro.launch import hlo_analysis as H
from jax import set_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

SSSP_SHAPES = ("bellman_512k", "dijkstra_128k", "multisource_128k")


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:            # backend without memory analysis
        return {"error": repr(e)}
    out = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_generated_code_size_in_bytes",
              "host_argument_size_in_bytes", "host_output_size_in_bytes",
              "host_temp_size_in_bytes", "host_alias_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    if not out:
        out["repr"] = str(ma)
    if "argument_size_in_bytes" in out and "temp_size_in_bytes" in out:
        out["live_bytes_per_device"] = (
            out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            + out["temp_size_in_bytes"] - out.get("alias_size_in_bytes", 0))
    return out


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        return {"error": repr(e)}


def build_sssp_cell(shape_name: str, mesh, overrides=None):
    """SSSP engines as dry-run cells (adjacency as ShapeDtypeStruct).
    overrides: {"minloc": "pmin"} etc. for §Perf variants."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.bellman import sssp_bellman_sharded
    from repro.core.multisource import sssp_multisource_sharded
    from repro.core.sharded import dijkstra_sharded

    ov = overrides or {}
    axis = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    nproc = 1
    for a in axis:
        nproc *= mesh.shape[a]

    if shape_name == "bellman_512k":
        n = 524_288
        fn = lambda adj, src: sssp_bellman_sharded(
            adj, src, mesh, axis=axis, max_sweeps=64)
        meta = {"n": n, "engine": "bellman_sharded", "sweep_cap": 64}
    elif shape_name == "dijkstra_128k":
        n = 131_072
        minloc = ov.get("minloc", "allgather")
        fn = lambda adj, src: dijkstra_sharded(
            adj, src, mesh, axis=axis, n_true=n, minloc=minloc)
        meta = {"n": n, "engine": "dijkstra_sharded (paper Alg.2)",
                "minloc": minloc}
    elif shape_name == "multisource_128k":
        n, s = 131_072, 64
        fn = lambda adj, srcs: sssp_multisource_sharded(
            adj, srcs, mesh, axis=axis, max_sweeps=64)
        meta = {"n": n, "sources": s, "engine": "multisource_sharded"}
    else:
        raise KeyError(shape_name)

    adj = jax.ShapeDtypeStruct((n, n), jnp.float32)
    adj_sh = NamedSharding(mesh, P(None, axis))
    if shape_name == "multisource_128k":
        src = jax.ShapeDtypeStruct((64,), jnp.int32)
    else:
        src = jax.ShapeDtypeStruct((), jnp.int32)
    src_sh = NamedSharding(mesh, P())

    class _C:                          # duck-typed Cell
        arch, shape, kind = "sssp", shape_name, "sssp"
        step_fn = staticmethod(fn)
        args = (adj, src)
        in_shardings = (adj_sh, src_sh)
        out_shardings = None
        cfg = None
        meta_ = meta
    _C.meta = dict(meta, tokens_per_step=0)
    return _C


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             *, save_hlo: bool = False, overrides: dict | None = None,
             tag: str = "") -> dict:
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.devices.size
    t0 = time.time()
    if arch == "sssp":
        cell = build_sssp_cell(shape_name, mesh, overrides)
        model_flops = None
    else:
        ga = (overrides or {}).pop("grad_accum", None) if overrides else None
        cell = build_cell(arch, shape_name, mesh, cfg_overrides=overrides,
                          grad_accum=ga)
        cfg = cell.cfg
        toks = cell.meta["tokens_per_step"]
        if cell.kind == "train":
            model_flops = H.analytic_train_flops(cfg, toks)
        elif cell.kind == "prefill":
            model_flops = H.analytic_decode_flops(cfg, toks)
        else:
            model_flops = H.analytic_decode_flops(cfg, toks)

    # set_mesh (not just `with mesh:`) so in-model with_sharding_constraint
    # activation rules see the ambient abstract mesh during tracing.
    with set_mesh(mesh):
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    ws = H.weighted_stats(hlo)          # loop-weighted per-device stats
    cost = _cost_dict(compiled)         # raw XLA numbers (loop bodies × 1)
    mem = _memory_dict(compiled)
    rf = H.roofline(ws, chips=chips, model_flops=model_flops)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": int(chips), "kind": cell.kind, "meta": cell.meta,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "cost_analysis_unweighted": {
            k: cost.get(k) for k in ("flops", "bytes accessed")},
        "weighted": ws.to_dict(),
        "roofline": rf.to_dict(),
        "mfu_fraction": H.mfu_fraction(rf, chips),
    }
    rec["overrides"] = overrides or {}
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_kind}{tag}".replace("/", "_")
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(os.path.join(out_dir, name + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def cells_for(mesh_kind: str):
    for arch in ARCHS:
        for sh in SHAPES:
            if sh == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                continue
            yield arch, sh
    for sh in SSSP_SHAPES:
        yield "sssp", sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (e.g. moe_impl=ep); "
                         "values parsed as python literals when possible")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, _, v = kv.partition("=")
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    todo = []
    for mk in meshes:
        if args.all:
            todo += [(a, s, mk) for a, s in cells_for(mk)]
        else:
            assert args.arch and args.shape, "--arch/--shape or --all"
            todo.append((args.arch, args.shape, mk))

    failures = 0
    for arch, sh, mk in todo:
        try:
            rec = run_cell(arch, sh, mk, args.out, save_hlo=args.save_hlo,
                           overrides=overrides or None, tag=args.tag)
            rf = rec["roofline"]
            mfu = rec["mfu_fraction"]
            mfu_s = f" mfu={mfu:.3f}" if mfu is not None else ""
            temp = rec["memory_analysis"].get("temp_size_in_bytes", 0)
            print(f"[ok] {arch:24s} {sh:16s} {mk:8s} "
                  f"compile={rec['compile_s']:.1f}s "
                  f"dominant={rf['dominant']:10s} "
                  f"bound={rf['bound_time_s']:.4f}s "
                  f"temp={temp/1e9:.1f}GB{mfu_s}", flush=True)
        except Exception:
            failures += 1
            print(f"[FAIL] {arch} {sh} {mk}\n{traceback.format_exc()}",
                  flush=True)
    print(f"done: {len(todo) - failures}/{len(todo)} cells passed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
