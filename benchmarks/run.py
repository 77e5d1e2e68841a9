"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figures 5-9 reproduce the paper's
experiment families at reduced CPU scale; fig10 measures the dynamic index
under churn (beyond the paper); `roofline` reads the dry-run artifacts (run
`python -m repro.launch.dryrun --all` first to refresh).

``--smoke`` is the CI perf-trajectory seed (ISSUE 3): a tiny-preset,
interpret-mode-kernel run of the representative families (fig5 build path,
fig6 query path, fig10 dynamic path, analytic roofline) written to a JSON
artifact and validated against the row schema — so every PR leaves a
comparable breadcrumb and a schema drift fails the build instead of
silently corrupting the trajectory.

    PYTHONPATH=src python -m benchmarks.run [--only fig5 roofline]
    PYTHONPATH=src python -m benchmarks.run --smoke --out BENCH_smoke.json
    PYTHONPATH=src python -m benchmarks.run --check BENCH_smoke.json
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time

from repro.launch.cache import enable_compile_cache

ALL = ("fig5", "fig6", "fig7", "fig8", "fig9", "fig10", "fig11", "fig12",
       "fig13", "fig14", "fig15", "fig16", "roofline")

# the artifact contract: bump ONLY with a matching update to every consumer
# of the perf trajectory (EXPERIMENTS.md §Tables tooling)
# schema 2: rows carry `precision=` and `bpv=` (bytes/vector of the
# traversal tier) so the trajectory can distinguish dtype regressions from
# algorithmic ones (ISSUE 4)
# schema 3: filtered-search rows (fig12) carry `selectivity=` — validated
# as a float in [0, 1] wherever present, required on every fig12 row —
# so the trajectory can slice the filtered cost curve per selectivity
# (ISSUE 5)
# schema 4: graph-layout rows carry `opt_layout=` (core/layout.py): "none"
# for the raw pool layout or the ordering(+pruned-degree) tag of an
# optimized index — required on every fig6 row, and the fig6 validator
# gates QPS(optimized) >= QPS(baseline) per (dataset, ef) (ISSUE 6)
# schema 5: corpus-sharded rows (fig13) carry `corpus_shards=` (int >= 1,
# core/corpus_shard.py) — required on every fig13 row, and the fig13
# validator gates the recall floor plus per-shard memory < replicated
# wherever S > 1 (the N-ceiling claim, ISSUE 7)
# schema 6: serving rows (fig14, serve/ann_engine.py) carry
# `p50_ms=`/`p99_ms=`/`qps=` (nearest-rank per-request latency + achieved
# throughput) — required on every fig14 row by the fig14 validator; the
# family gate now also requires at least one SUCCESSFUL row per family
# (a family that silently stops emitting rows fails, not just schema
# drift), and `--check FILE` re-validates an existing artifact so CI can
# gate the uploaded file independently of the process that wrote it
# (ISSUE 8)
# schema 7: tiered-storage rows (fig15, core/vecstore.py HostTier) carry
# `tier=` ("device" or "host" — where the fp32 rescore tier lives) —
# validated wherever present, required on every fig15 row by the fig15
# validator, which also gates zero device-resident rescore bytes and
# bitwise host/device parity on every host row (ISSUE 9)
# schema 8: kNN-LM decode rows (fig16, retrieval/knn_lm.py +
# serve/engine.py) carry `tok_s=` (end-to-end generate throughput) and,
# on retrieval rows, `fused_nll=`/`lm_nll=` (teacher-forced NLL on the
# memorization corpus) — lifted wherever present as non-negative floats;
# the fig16 validator REQUIRES the lm baseline + a knn-* retrieval row
# and gates fused_nll <= lm_nll (the decode-time retrieval hook provably
# retrieves, ISSUE 10)
SMOKE_SCHEMA = 8
SMOKE_N = 192
_ROW_RE = re.compile(r"^(fig\d+|roofline)/[\w./@+-]+$")
_PRECISIONS = ("fp32", "bf16", "int8")
_PREC_RE = re.compile(r"(?:^|\s)precision=(\S+)")
_BPV_RE = re.compile(r"(?:^|\s)bpv=(\S+)")
_SEL_RE = re.compile(r"(?:^|\s)selectivity=(\S+)")
_OPT_RE = re.compile(r"(?:^|\s)opt_layout=([\w.-]+)")
_CS_RE = re.compile(r"(?:^|\s)corpus_shards=(\S+)")
_P50_RE = re.compile(r"(?:^|\s)p50_ms=(\S+)")
_P99_RE = re.compile(r"(?:^|\s)p99_ms=(\S+)")
_QPS_RE = re.compile(r"(?:^|\s)qps=(\S+)")
_TIER_RE = re.compile(r"(?:^|\s)tier=(\S+)")
_TIERS = ("device", "host")
_FNLL_RE = re.compile(r"(?:^|\s)fused_nll=(\S+)")
_LNLL_RE = re.compile(r"(?:^|\s)lm_nll=(\S+)")
# families the smoke artifact must always cover (one per serving surface)
SMOKE_FAMILIES = ("fig5", "fig6", "fig10", "fig11", "fig12", "fig13",
                  "fig14", "fig15", "fig16", "roofline")


def _module(name: str):
    if name == "fig5":
        from benchmarks import fig5_construction as m
    elif name == "fig6":
        from benchmarks import fig6_qps as m
    elif name == "fig7":
        from benchmarks import fig7_order as m
    elif name == "fig8":
        from benchmarks import fig8_rho as m
    elif name == "fig9":
        from benchmarks import fig9_iters as m
    elif name == "fig10":
        from benchmarks import fig10_churn as m
    elif name == "fig11":
        from benchmarks import fig11_precision as m
    elif name == "fig12":
        from benchmarks import fig12_filtered as m
    elif name == "fig13":
        from benchmarks import fig13_corpus_sharded as m
    elif name == "fig14":
        from benchmarks import fig14_serving as m
    elif name == "fig15":
        from benchmarks import fig15_tiered as m
    elif name == "fig16":
        from benchmarks import fig16_knn_lm as m
    elif name == "roofline":
        from benchmarks import roofline as m
    else:
        return None
    return m


def parse_row(row: str) -> dict:
    """Split one CSV row into the artifact dict; raises ValueError on drift.

    Schema 2: the derived column must carry `precision=<rung>` and
    `bpv=<float>` (traversal-tier bytes/vector; 0.0 for cells with no
    vector storage, e.g. analytic roofline LLM cells) — both are lifted
    into top-level artifact fields.

    Schema 3: an optional `selectivity=<float>` (filtered-search rows) is
    lifted as well; where present it must parse as a float in [0, 1].
    The fig12 validator additionally REQUIRES it on every fig12 row.

    Schema 4: an optional `opt_layout=<tag>` (graph-layout rows,
    core/layout.py) is lifted; the fig6 validator REQUIRES it on every
    fig6 row and gates QPS(optimized) >= QPS(baseline).

    Schema 5: an optional `corpus_shards=<int>` (corpus-sharded rows,
    core/corpus_shard.py) is lifted; where present it must parse as an
    int >= 1.  The fig13 validator additionally REQUIRES it on every
    fig13 row and gates recall + the per-shard memory reduction.

    Schema 6: optional `p50_ms=`/`p99_ms=`/`qps=` (serving rows,
    serve/ann_engine.py) are lifted; where present they must parse as
    non-negative floats.  The fig14 validator additionally REQUIRES all
    three on every fig14 row and gates p50 <= p99 + completion.

    Schema 7: an optional `tier=<placement>` (tiered-storage rows,
    core/vecstore.py HostTier) is lifted; where present it must be
    "device" or "host".  The fig15 validator additionally REQUIRES it on
    every fig15 row and gates the placement + parity contract.

    Schema 8: optional `fused_nll=`/`lm_nll=` (kNN-LM decode rows,
    retrieval/knn_lm.py) are lifted; where present they must parse as
    non-negative floats.  The fig16 validator additionally REQUIRES both
    on every retrieval row and gates fused_nll <= lm_nll.
    """
    parts = row.split(",", 2)
    if len(parts) != 3:
        raise ValueError(f"row is not name,us_per_call,derived: {row!r}")
    name, us, derived = parts
    if not _ROW_RE.match(name):
        raise ValueError(f"row name outside the fig*/roofline namespace: "
                         f"{name!r}")
    prec = _PREC_RE.search(derived)
    bpv = _BPV_RE.search(derived)
    if not prec or prec.group(1) not in _PRECISIONS:
        raise ValueError(f"row lacks a valid precision= field: {row!r}")
    if not bpv:
        raise ValueError(f"row lacks a bpv= field: {row!r}")
    bpv_val = float(bpv.group(1))
    if bpv_val < 0:
        raise ValueError(f"negative bytes/vector: {row!r}")
    sel = _SEL_RE.search(derived)
    sel_val = None
    if sel:
        sel_val = float(sel.group(1))
        if not 0.0 <= sel_val <= 1.0:
            raise ValueError(f"selectivity outside [0, 1]: {row!r}")
    opt = _OPT_RE.search(derived)
    cs = _CS_RE.search(derived)
    cs_val = None
    if cs:
        cs_val = int(cs.group(1))
        if cs_val < 1:
            raise ValueError(f"corpus_shards below 1: {row!r}")
    serving = {}
    for field, rx in (("p50_ms", _P50_RE), ("p99_ms", _P99_RE),
                      ("qps", _QPS_RE)):
        m = rx.search(derived)
        serving[field] = None
        if m:
            serving[field] = float(m.group(1))
            if serving[field] < 0:
                raise ValueError(f"negative {field}: {row!r}")
    tier = _TIER_RE.search(derived)
    tier_val = None
    if tier:
        tier_val = tier.group(1)
        if tier_val not in _TIERS:
            raise ValueError(f"tier outside {_TIERS}: {row!r}")
    nlls = {}
    for field, rx in (("fused_nll", _FNLL_RE), ("lm_nll", _LNLL_RE)):
        m = rx.search(derived)
        nlls[field] = None
        if m:
            nlls[field] = float(m.group(1))
            if nlls[field] < 0:
                raise ValueError(f"negative {field}: {row!r}")
    return {"name": name, "us_per_call": float(us), "derived": derived,
            "precision": prec.group(1), "bytes_per_vector": bpv_val,
            "selectivity": sel_val,
            "opt_layout": opt.group(1) if opt else None,
            "corpus_shards": cs_val, "tier": tier_val, **serving, **nlls}


def validate_rows(parsed: list[dict]) -> None:
    """Schema gate for the smoke artifact: every family present WITH at
    least one successful row (a family that silently stops emitting rows
    must fail, not just one that crashes), no ERROR rows (a crashed
    benchmark must fail CI, not upload a hole), and the per-family
    validators (fig6 layout, fig11 precision ladder, fig12 filtered,
    fig13 corpus-sharded, fig14 serving, fig15 tiered placement, fig16
    kNN-LM decode)."""
    for fam in SMOKE_FAMILIES:
        ok = [p for p in parsed
              if p["name"].startswith(fam + "/")
              and "/ERROR" not in p["name"]]
        if not ok:
            raise ValueError(
                f"smoke artifact has no successful {fam!r} rows")
    errors = [p["name"] for p in parsed if "/ERROR" in p["name"]]
    if errors:
        raise ValueError(f"benchmark families crashed: {errors}")
    from benchmarks.fig6_qps import validate_layout_rows
    from benchmarks.fig11_precision import validate_precision_rows
    from benchmarks.fig12_filtered import validate_filtered_rows
    from benchmarks.fig13_corpus_sharded import validate_corpus_rows
    from benchmarks.fig14_serving import validate_serving_rows
    from benchmarks.fig15_tiered import validate_tiered_rows
    from benchmarks.fig16_knn_lm import validate_knn_rows
    validate_layout_rows(parsed)
    validate_precision_rows(parsed)
    validate_filtered_rows(parsed)
    validate_corpus_rows(parsed)
    validate_serving_rows(parsed)
    validate_tiered_rows(parsed)
    validate_knn_rows(parsed)


def run_smoke(out_path: str) -> None:
    """Tiny-preset interpret-kernel run -> validated JSON artifact."""
    rows: list[str] = []
    calls = (
        ("fig5", lambda m: m.run(n_seq=SMOKE_N, backend="interpret")),
        ("fig6", lambda m: m.run(n=SMOKE_N, backend="interpret",
                                 optimize_layout=True)),
        ("fig10", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig11", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig12", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig13", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig14", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig15", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("fig16", lambda m: m.run(n=SMOKE_N, backend="interpret")),
        ("roofline", lambda m: m.run()),
    )
    for name, call in calls:
        t0 = time.time()
        try:
            rows.extend(call(_module(name)))
        except Exception as e:
            # placeholder precision/bpv keep the row parseable so the
            # failure surfaces as "families crashed", not schema noise
            rows.append(f"{name}/ERROR,0.0,{type(e).__name__}:{e}"
                        f" precision=fp32 bpv=0.0")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    parsed = [parse_row(r) for r in rows]
    payload = {"schema": SMOKE_SCHEMA, "n": SMOKE_N, "backend": "interpret",
               "rows": parsed}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {len(parsed)} rows -> {out_path}", file=sys.stderr)
    validate_rows(parsed)  # raises (non-zero exit) on drift


def check_artifact(path: str) -> None:
    """Re-validate an EXISTING smoke artifact from disk: schema version,
    row contract, and family completeness.  This is the CI gate run as a
    separate step from the process that wrote the file — `run_smoke`'s
    in-process validation cannot catch an artifact that was uploaded
    stale, truncated, or from a diverged writer."""
    with open(path) as f:
        payload = json.load(f)
    if payload.get("schema") != SMOKE_SCHEMA:
        raise ValueError(f"{path}: schema {payload.get('schema')!r} != "
                         f"expected {SMOKE_SCHEMA}")
    rows = payload.get("rows", [])
    if not rows:
        raise ValueError(f"{path}: artifact has no rows")
    # re-parse from the raw columns, not the stored lifted fields: the
    # artifact must revalidate from first principles
    parsed = [parse_row(f"{p['name']},{p['us_per_call']},{p['derived']}")
              for p in rows]
    validate_rows(parsed)
    print(f"# {path}: schema {SMOKE_SCHEMA}, {len(parsed)} rows, "
          f"all {len(SMOKE_FAMILIES)} families present", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {ALL}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-preset interpret-mode run -> JSON artifact "
                         "(the CI perf-trajectory seed)")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="smoke artifact path (only with --smoke)")
    ap.add_argument("--check", default=None, metavar="FILE",
                    help="re-validate an existing smoke artifact (schema "
                         "+ family completeness) and exit; the CI gate "
                         "step (runs nothing)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.check:
        if args.smoke or args.only:
            ap.error("--check runs nothing; drop --smoke/--only")
        check_artifact(args.check)
        return
    if args.smoke:
        if args.only:
            ap.error("--only does not apply to --smoke (fixed family set)")
        run_smoke(args.out)
        return

    which = args.only or ALL
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        t0 = time.time()
        m = _module(name)
        if m is None:
            print(f"# unknown benchmark {name}", file=sys.stderr)
            failed.append(name)
            continue
        try:
            for row in m.run():
                print(row, flush=True)
        except Exception as e:  # run the other families, then fail
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"# benchmark families failed: {failed}")


if __name__ == "__main__":
    main()
