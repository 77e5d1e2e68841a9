import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective statistics.

Usage:
    python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
Each cell writes JSON {mem, cost, collectives, timings} to --out.
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax

from repro.configs import list_archs
from repro.configs.base import SHAPES
from repro.launch import specs as SPEC
from repro.launch.mesh import make_production_mesh

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:\[[0-9,]*\]))")
_RESULT_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9]+\[[0-9,]*\]))\S*\s+([a-z0-9\-]+)")


def _bytes_of_shape(s: str) -> int:
    m = re.match(r"([a-z]+[0-9]+)\[([0-9,]*)\]", s)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for c in _COLLECTIVES:
            # match ` = shape... collective-name(` and fused variants like
            # `all-gather-start`
            if f" {c}(" in stripped or f" {c}-start(" in stripped:
                m = _RESULT_RE.search(stripped)
                total = 0
                if m:
                    tuple_part, single, _ = m.groups()
                    if single:
                        total = _bytes_of_shape(single)
                    elif tuple_part:
                        total = sum(_bytes_of_shape(s) for s in
                                    _SHAPE_RE.findall(tuple_part))
                out[c] += total
                counts[c] += 1
                break
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    return {**out, **out_counts,
            "total_bytes": sum(out[c] for c in _COLLECTIVES)}


def _compile_stats(fn, args, mesh) -> dict:
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    return {
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
        "cost": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        "collectives": collective_bytes(hlo),
        "hlo_ops": len(hlo.splitlines()),
    }


def _extrapolate(p1: dict, p2: dict, units: int) -> dict:
    """cost(full) = cost(1 unit) + (units - 1) * [cost(2) - cost(1)]."""
    def lerp(a, b):
        return a + (units - 1) * (b - a)

    out = {"cost": {}, "collectives": {}}
    for k in p1["cost"]:
        out["cost"][k] = lerp(p1["cost"][k], p2["cost"][k])
    for k in p1["collectives"]:
        out["collectives"][k] = lerp(p1["collectives"][k],
                                     p2["collectives"][k])
    return out


def run_cell(arch: str, shape: str, mesh_kind: str,
             cost_probes: bool = True, remat_policy: str = "full") -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    result: dict = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                    "mesh_shape": dict(mesh.shape)}

    ok, reason = SPEC.cell_is_applicable(arch, shape)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = reason
        return result

    # full-size compile: proves sharding coherence + memory fit
    fn, args = SPEC.make_cell(arch, shape, mesh, remat_policy=remat_policy)
    full = _compile_stats(fn, args, mesh)
    result.update({"status": "ok", **full})
    result["cost_raw_scanned"] = full["cost"]  # body-once numbers, for ref

    # cost probes: truncated + unrolled k=1, k=2 -> linear extrapolation
    if cost_probes and arch != "grnnd-ann":
        from repro.configs import get_arch
        from repro.configs.base import n_pattern_units
        units = n_pattern_units(get_arch(arch))
        if units >= 2:
            f1, a1 = SPEC.make_cell(arch, shape, mesh, cost_probe=1,
                                    remat_policy=remat_policy)
            p1 = _compile_stats(f1, a1, mesh)
            f2, a2 = SPEC.make_cell(arch, shape, mesh, cost_probe=2,
                                    remat_policy=remat_policy)
            p2 = _compile_stats(f2, a2, mesh)
            ex = _extrapolate(p1, p2, units)
            result["cost"] = ex["cost"]
            result["collectives"] = ex["collectives"]
            result["probe_compile_s"] = [p1["compile_s"], p2["compile_s"]]
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-grnnd", action="store_true")
    ap.add_argument("--remat-policy", type=str, default="full")
    ap.add_argument("--out", type=str, default="results/dryrun")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [(a, s) for a in list_archs() for s in SHAPES]
        if args.include_grnnd:
            cells += [("grnnd-ann", s) for s in SPEC.GRNND_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        for mk in meshes:
            tag = f"{arch}__{shape}__{mk}"
            fpath = outdir / f"{tag}.json"
            if fpath.exists():
                prev = json.loads(fpath.read_text())
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[cached] {tag}: {prev['status']}")
                    n_ok += prev["status"] == "ok"
                    n_skip += prev["status"] == "skipped"
                    continue
            try:
                res = run_cell(arch, shape, mk,
                               remat_policy=args.remat_policy)
            except Exception as e:  # record the failure, keep sweeping
                res = {"arch": arch, "shape": shape, "mesh": mk,
                       "status": "failed", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            fpath.write_text(json.dumps(res, indent=2))
            st = res["status"]
            n_ok += st == "ok"
            n_skip += st == "skipped"
            n_fail += st == "failed"
            extra = ""
            if st == "ok":
                gb = res["memory"]["argument_size_bytes"] / 2**30
                extra = (f" compile={res['compile_s']}s arg={gb:.2f}GiB "
                         f"coll={res['collectives']['total_bytes']/2**30:.2f}GiB")
            elif st == "failed":
                extra = " " + res["error"][:160]
            print(f"[{st}] {tag}{extra}", flush=True)

    print(f"\nDONE ok={n_ok} skipped={n_skip} failed={n_fail}")


if __name__ == "__main__":
    main()
