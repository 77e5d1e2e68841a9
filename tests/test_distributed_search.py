"""Query-sharded distributed_search: bitwise parity with single-device.

Locks the DESIGN.md §6.4 contract — searches are embarrassingly parallel
over queries, so any shard count must return bitwise-identical results —
for both visited representations, including the query-padding path
(Q not divisible by the shard count).  Same forced-host-device subprocess
pattern as tests/test_distributed_build.py.

ISSUE 5 grows the suite with the filtered path (DESIGN.md §9):

  * shard-count invariance across 1/2/4 shards, for the unfiltered AND
    the filtered search — the per-query predicate words shard with the
    queries, so the route-through beam and result heap stay shard-local;
  * a cache-key regression: the shard_map executable cache keys on the
    presence of the filter operands (`has_filter`), so an unfiltered call
    followed by a filtered call of identical shapes can never reuse a
    stale unfiltered executable (every filtered id must satisfy its
    predicate, and the cache must grow between the calls).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Subprocess with 8 forced host devices (~15 s) — nightly tier.
pytestmark = pytest.mark.slow

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import grnnd, distributed
    from repro.core import labels as L
    from repro.core.distributed import _sharded_search_fn
    from repro.core.search import search
    from repro.data import synthetic
    from repro.launch.mesh import make_mesh

    x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", 600)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, 100)  # 100 % 8 != 0
    cfg = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x, cfg)
    mesh = make_mesh((8,), ("data",))
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(3), (600,), 0, 30), 30)
    fw = L.random_query_filters(jax.random.PRNGKey(4), 100, 30, 0.2)

    def same(a, b):
        return {
            "ids": np.array_equal(np.asarray(a.ids), np.asarray(b.ids)),
            "dists": np.array_equal(np.asarray(a.dists),
                                    np.asarray(b.dists)),
            "n_expanded": np.array_equal(np.asarray(a.n_expanded),
                                         np.asarray(b.n_expanded)),
            "shape_ok": b.ids.shape == a.ids.shape,
        }

    out = {}
    for vis in ("dense", "hashed"):
        ref = search(x, pool.ids, q, k=10, ef=32, visited=vis)
        got = distributed.distributed_search(
            mesh, ("data",), x, pool.ids, q, k=10, ef=32, visited=vis)
        out[vis] = same(ref, got)

    # shard-count invariance, unfiltered + filtered, on device subsets
    ref_u = search(x, pool.ids, q, k=10, ef=32)
    ref_f = search(x, pool.ids, q, k=10, ef=32, labels=store, filter=fw)
    for s in (1, 2, 4):
        m = make_mesh((s,), ("data",), devices=jax.devices()[:s])
        got_u = distributed.distributed_search(
            m, ("data",), x, pool.ids, q, k=10, ef=32)
        got_f = distributed.distributed_search(
            m, ("data",), x, pool.ids, q, k=10, ef=32,
            labels=store, filter=fw)
        out[f"shards{s}-unfiltered"] = same(ref_u, got_u)
        out[f"shards{s}-filtered"] = same(ref_f, got_f)

    # cache-key regression: unfiltered then filtered at IDENTICAL shapes
    # on a fresh mesh axis name -> the cache must add an entry (has_filter
    # is part of the key) and the filtered results must obey the predicate
    m2 = make_mesh((2,), ("ck",), devices=jax.devices()[:2])
    _ = distributed.distributed_search(m2, ("ck",), x, pool.ids, q,
                                       k=10, ef=32)
    before = _sharded_search_fn.cache_info().currsize
    got = distributed.distributed_search(m2, ("ck",), x, pool.ids, q,
                                         k=10, ef=32,
                                         labels=store, filter=fw)
    after = _sharded_search_fn.cache_info().currsize
    out["cache_key"] = {
        "grew": after == before + 1,
        "pred_ok": float(L.predicate_fraction(got.ids, fw, store.words)),
        "matches_single_device": np.array_equal(np.asarray(ref_f.ids),
                                                np.asarray(got.ids)),
    }
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def dist_search_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("visited", ["dense", "hashed"])
def test_sharded_search_bitwise_parity(dist_search_results, visited):
    res = dist_search_results[visited]
    assert res["shape_ok"]       # pad rows sliced back off
    assert res["ids"]
    assert res["dists"]
    assert res["n_expanded"]


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("mode", ["unfiltered", "filtered"])
def test_shard_count_invariance(dist_search_results, shards, mode):
    """1/2/4 shards return bitwise-identical results to the single-device
    search, with and without a per-query filter predicate."""
    res = dist_search_results[f"shards{shards}-{mode}"]
    assert res["shape_ok"]
    assert res["ids"]
    assert res["dists"]
    assert res["n_expanded"]


def test_filter_operands_in_shard_map_cache_key(dist_search_results):
    """An unfiltered compile must never be reused for a filtered batch of
    identical shapes: the cache grows, the filtered results match the
    single-device filtered search, and every id passes its predicate."""
    res = dist_search_results["cache_key"]
    assert res["grew"]
    assert res["pred_ok"] == 1.0
    assert res["matches_single_device"]
