import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and record what the compiler reports.  The
train workload comes from the phase execution engine's step builder (via
``launch.steps.build_workload``) — the same compiled step the Trainer
dispatches, so the dry-run's memory/collective analysis describes the
real hot path.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
        --shape train_4k [--multipod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Outputs one JSON per (arch, shape, mesh) under --out with:
  memory_analysis, cost_analysis (FLOPs/bytes), per-collective byte
  totals parsed from the optimized HLO, and wall compile time.
"""
import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config  # noqa: E402
from repro.launch import steps as ST     # noqa: E402
from repro.launch.mesh import auto_mesh, make_production_mesh  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|s8|s16|s64|u8|u16|u32|u64|"
                       r"pred|f8e4m3|f8e5m2|c64|c128)\[([0-9,]*)\]")


def _bytes_of_shapes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum result bytes of every collective op in the optimized HLO, per
    collective kind, split by whether the op sits inside a loop body
    (lax.scan over layers ⇒ loop-body bytes repeat once per trip).
    Result size ≈ bytes moved per device."""
    out = {k: 0 for k in _COLLECTIVES}
    out_loop = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    comp = ""
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # computation headers look like:  %name (args) -> type {   /  ENTRY ...
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", stripped)
        if m and stripped.endswith("{"):
            comp = m.group(2)
            continue
        if stripped == "}":
            comp = ""
            continue
        for kind in _COLLECTIVES:
            if re.search(rf"= .*\b{kind}-done\(", stripped):
                break  # bytes were counted at the matching -start
            if re.search(rf"= .*\b{kind}(-start)?\(", stripped):
                lhs = stripped.split("=", 1)[1]
                op_part = lhs.split("(", 1)[0]
                b = _bytes_of_shapes(op_part)
                in_loop = ("body" in comp) or ("while" in comp) \
                    or ("region" in comp)
                counts[kind] += 1
                if in_loop:
                    out_loop[kind] += b
                else:
                    out[kind] += b
                break
    return out, out_loop, counts


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            out_dir: str, seq_shard: bool = True, remat_policy: str = "",
            serve_resident: bool = False, capacity_factor: float = 0.0,
            cache_seq_shard: bool = False, mesh_shape: str = "",
            tag: str = "") -> dict:
    cfg = get_config(arch)
    if capacity_factor and cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=capacity_factor))
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.mode, "tag": tag or "baseline"}
    ok, why = ST.shape_supported(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _save(rec, out_dir)
        return rec
    t0 = time.time()
    try:
        if mesh_shape:
            dims = tuple(int(x) for x in mesh_shape.split("x"))
            names = ("data", "model") if len(dims) == 2 else                 ("pod", "data", "model")
            mesh = auto_mesh(dims, names)
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, in_specs, out_specs = ST.build_workload(
            cfg, shape, multi_pod=multi_pod, seq_shard=seq_shard, remat_policy=remat_policy,
            serve_resident=serve_resident,
            cache_seq_shard=cache_seq_shard)
        with jax.set_mesh(mesh):
            in_sh = ST._named(mesh, in_specs)
            out_sh = ST._named(mesh, out_specs)
            # donate params/opt (train) or cache (decode) exactly like the
            # real runtime — without aliasing, XLA double-buffers the
            # largest arrays and memory_analysis overstates the footprint
            donate = (0, 1) if shape.mode == "train" else (
                (1,) if shape.mode == "decode" else ())
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        rec["status"] = "ok"
        rec["lower_s"] = round(t_lower, 1)
        rec["compile_s"] = round(t_compile, 1)
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in dir(ma)
                if k.endswith("_size_in_bytes") and not k.startswith("_")}
        except Exception as e:        # CPU backend may not implement
            rec["memory_analysis"] = {"error": str(e)[:200]}
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and
                (k in ("flops", "bytes accessed", "optimal_seconds") or
                 k.startswith("bytes accessed"))}
        except Exception as e:
            rec["cost_analysis"] = {"error": str(e)[:200]}
        try:
            hlo = compiled.as_text()
            cb, cl, cc = collective_bytes(hlo)
            rec["collective_bytes"] = cb
            rec["collective_bytes_in_loop"] = cl
            rec["collective_counts"] = cc
            rec["hlo_lines"] = hlo.count("\n")
        except Exception as e:
            rec["collective_bytes"] = {"error": str(e)[:200]}
        print(f"OK   {arch:26s} {shape_name:12s} {mesh_name:8s} "
              f"compile={rec.get('compile_s', '?')}s")
        del compiled, lowered, jitted

    except Exception as e:
        rec["status"] = "error"
        rec["error"] = traceback.format_exc()[-2000:]
        print(f"FAIL {arch:26s} {shape_name:12s} {mesh_name}: "
              f"{str(e)[:200]}")
    _save(rec, out_dir)
    # XLA CPU retains compiled executables in process-level caches —
    # clear them or a long sweep OOMs (observed at ~33 GB RSS).
    jax.clear_caches()
    import gc
    gc.collect()
    return rec


def _save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    tag = rec.get("tag", "baseline")
    suffix = "" if tag == "baseline" else f".{tag}"
    path = os.path.join(
        out_dir, f"{rec['arch']}.{rec['shape']}.{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--remat-policy", default="")
    ap.add_argument("--serve-resident", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--cache-seq-shard", action="store_true")
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh, e.g. 32x8 (data x model)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        archs = ASSIGNED_ARCHS
        shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    else:
        assert args.arch and args.shape
        archs = [args.arch]
        shapes = [args.shape]

    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multipod]
    results = []
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                suffix = "" if not args.tag else f".{args.tag}"
                path = os.path.join(
                    args.out, f"{arch}.{shp}.{mesh_name}{suffix}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"SKIP {arch} {shp} {mesh_name} (cached)")
                        results.append(prev)
                        continue
                results.append(run_one(
                    arch, shp, multi_pod=mp, out_dir=args.out,
                    seq_shard=not args.no_seq_shard,
                    remat_policy=args.remat_policy,
                    serve_resident=args.serve_resident,
                    capacity_factor=args.capacity_factor,
                    cache_seq_shard=args.cache_seq_shard,
                    mesh_shape=args.mesh_shape,
                    tag=args.tag))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"/ {len(results)} total")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
