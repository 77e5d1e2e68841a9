"""The control and the planted faults: runs that have to read not correct.

    python3 bench/control.py --workload sift1m.build --seeds 11 12 13 --seconds 1

Runs a cell with the timed path replaced underneath and prints the
compared numbers of each seed, beside the limits.  The benchmark's own
runs never do this.

The control is the step below the float32 that the configurations
state.  In the query cells it is the plain reference computed in
bfloat16 (reference.py), put in the place of `core.search.search`.  In
the build cells it is the program's own bfloat16 path: the corpus is
handed to the build, and to the search that reads the graph, as a
bfloat16 `VectorStore`.

The faults (`FAULTS`) break the timed path as a later change could:
a step that returns its state unchanged, half of the batch left out,
an answer altered where it is produced.  bench/tests checks that each
of them, and the control, comes out not correct.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@contextlib.contextmanager
def _patched(*triples):
    """Set (object, attribute, value) triples; restore them and JAX's
    caches on exit (a jitted program traced before would hide a patch)."""
    import jax
    old = [(o, a, getattr(o, a)) for o, a, _ in triples]
    jax.clear_caches()
    for o, a, v in triples:
        setattr(o, a, v)
    try:
        yield
    finally:
        for o, a, v in old:
            setattr(o, a, v)
        jax.clear_caches()


def _search_sites():
    """Every name through which the runners reach `search()`."""
    import importlib
    return [importlib.import_module(m) for m in
            ("repro.core", "repro.core.search", "repro.serve.ann_engine")]


def _replace_search(fn):
    orig = _search_sites()[1].search
    return _patched(*[(m, "search", fn(orig)) for m in _search_sites()])


def _replace_build(fn):
    import repro.core
    return _patched((repro.core, "build_graph", fn(repro.core.build_graph)))


def control(mode: str):
    """The control of a cell whose traffic has this mode."""
    from repro.core import vecstore as VS
    from repro.core.search import SearchResult

    if mode == "build":
        def bf16(orig):
            return lambda key, x, cfg: orig(key, VS.encode(x, "bf16"), cfg)

        def bf16_search(orig):
            return lambda x, g, q, **kw: orig(VS.encode(x, "bf16"), g, q, **kw)
        return _stack(_replace_build(bf16), _replace_search(bf16_search))

    def reference_bf16(orig):
        import jax.numpy as jnp

        from bench import reference

        def search(x, graph_ids, queries, *, k=10, **kw):
            ids, d = reference.knn(x, queries, k, precision="bfloat16")
            return SearchResult(jnp.asarray(ids), jnp.asarray(d),
                                jnp.zeros(len(ids), jnp.int32))
        return search
    return _replace_search(reference_bf16)


@contextlib.contextmanager
def _stack(*cms):
    with contextlib.ExitStack() as st:
        for cm in cms:
            st.enter_context(cm)
        yield


def _rows_altered(res, n):
    """Each row's first id moved to another vertex, its distance kept."""
    from repro.core.search import SearchResult
    ids = res.ids.at[:, 0].set((res.ids[:, 0] + 1) % n)
    return SearchResult(ids, res.dists, res.n_expanded)


def _half_left_out(res):
    """The second half of the rows never computed: no ids, no distances."""
    import jax.numpy as jnp

    from repro.core.search import SearchResult
    half = res.ids.shape[0] // 2
    return SearchResult(res.ids.at[half:].set(-1),
                        res.dists.at[half:].set(jnp.inf), res.n_expanded)


def state_unchanged(mode: str):
    if mode == "build":
        import repro.core.grnnd as G
        # every round hands back the pool it was given: the build
        # returns its random initial graph
        return _patched((G, "_apply_requests", lambda pool, *a, **k: pool))
    # the beam loop never steps: the search returns its initial beam
    return _replace_search(
        lambda orig: lambda *a, **kw: orig(*a, **dict(kw, max_steps=0)))


def half_batch(mode: str):
    if mode == "build":
        import repro.core.grnnd as G
        from repro.core.pools import Pool
        orig = G._apply_requests

        def apply(pool, *a, **k):
            # the second half of the vertices keep the pools they had
            new = orig(pool, *a, **k)
            half = pool.ids.shape[0] // 2
            return Pool(new.ids.at[half:].set(pool.ids[half:]),
                        new.dists.at[half:].set(pool.dists[half:]))
        return _patched((G, "_apply_requests", apply))
    return _replace_search(
        lambda orig: lambda *a, **kw: _half_left_out(orig(*a, **kw)))


def answer_altered(mode: str):
    if mode == "build":
        from repro.core.pools import Pool

        def alter(orig):
            def build(key, x, cfg):
                p = orig(key, x, cfg)
                n = p.ids.shape[0]
                ids = p.ids.at[:, 0].set((p.ids[:, 0] + 1) % n)
                return Pool(ids, p.dists)
            return build
        return _replace_build(alter)
    return _replace_search(
        lambda orig: lambda x, *a, **kw: _rows_altered(
            orig(x, *a, **kw), x.shape[0]))


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="plant this fault instead of the control")
    args = ap.parse_args(argv)

    from bench import harness
    from repro.launch.cache import enable_compile_cache
    spec = harness.resolve(args.workload)
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    mode = spec.traffic["mode"]
    plant = FAULTS[args.fault] if args.fault else control
    for seed in args.seeds:
        with plant(mode):
            line = harness.execute(spec, seed, args.seconds, False,
                                   time.perf_counter(), device)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "planted": args.fault or "control",
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
