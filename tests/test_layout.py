"""Layout-equivalence suite (PR CI fast tier): ISSUE 6 acceptance contracts.

The post-build layout pass (core/layout.py, DESIGN.md §10) repacks the
adjacency to a fixed degree and renumbers vertices for locality; its whole
safety argument is the permutation contract — external callers must see
IDENTICAL results before and after `optimize()`.  Four contracts:

  * **bitwise equivalence** — `OptimizedIndex.search` returns bitwise-
    identical ids, dists AND n_expanded to the unoptimized search, on all
    three precision rungs (fp32/bf16/int8 + rescore), filtered and
    unfiltered, dense and hashed (cap ≥ N) visited sets, for both the
    "bfs" and "hub" orderings — and under ANY random permutation
    (hypothesis property);
  * **pack/unpack laws** — packing is a stable sentinel compaction that
    preserves distance-rank edge order; `unpack(pack(g, D), R)` equals
    `pack(g, R)` whenever no row exceeds degree D (hypothesis property);
  * **sharded parity** — `OptimizedIndex.distributed_search` matches the
    single-device optimized search bitwise across 1/2/4 shards, and the
    `ids_map` operand is part of the shard_map executable cache key (an
    unmapped compile can never serve a mapped call of identical shapes);
  * **pruning semantics** — detour pruning is opt-in, bounds the degree,
    only ever KEEPS original edges (never invents them), and holds a
    recall floor at half degree on the fast-tier corpus.

Runs in BOTH CI legs (REPRO_KERNEL_BACKEND=ref and =interpret): sizes are
kept small enough for the Python-stepped interpret kernels.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import grnnd, labels as L, layout as LY, recall
from repro.core import vecstore as VS
from repro.core.search import search
from repro.data import synthetic
from repro.launch.mesh import make_mesh
from conftest import optional_hypothesis

# every suite in the interpret CI leg carries this marker: the
# matrix selects `-m kernel_parity` instead of a hand-kept file list
pytestmark = pytest.mark.kernel_parity


given, settings, st = optional_hypothesis()

K = 10
EF = 32
N = 260
NQ = 12
CFG = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)


@pytest.fixture(scope="module")
def case():
    x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", N)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, NQ)
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x, CFG)
    return x, q, pool


def _assert_same(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids),
                                  err_msg=f"{msg}/ids")
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists),
                                  err_msg=f"{msg}/dists")
    np.testing.assert_array_equal(np.asarray(a.n_expanded),
                                  np.asarray(b.n_expanded),
                                  err_msg=f"{msg}/n_expanded")


# ---------------------------------------------------------------------------
# packed adjacency: unit laws
# ---------------------------------------------------------------------------

def test_pack_is_stable_rank_preserving_compaction():
    g = np.array([[3, -1, 7, -1, 2],
                  [-1, -1, -1, -1, -1],
                  [1, 2, 3, 4, 5]], np.int32)
    assert LY.packed_degree(g) == 5
    p = LY.pack_adjacency(g)
    # interior holes squeezed out, rank order preserved, -1 tail pad
    np.testing.assert_array_equal(p, [[3, 7, 2, -1, -1],
                                      [-1, -1, -1, -1, -1],
                                      [1, 2, 3, 4, 5]])
    # explicit smaller degree truncates by rank; larger degree pads
    np.testing.assert_array_equal(LY.pack_adjacency(g, 2),
                                  [[3, 7], [-1, -1], [1, 2]])
    assert LY.pack_adjacency(g, 7).shape == (3, 7)


def test_unpack_roundtrip_fixed():
    g = np.array([[5, -1, 1], [-1, 2, -1]], np.int32)
    np.testing.assert_array_equal(
        LY.unpack_adjacency(LY.pack_adjacency(g, 2), 3),
        LY.pack_adjacency(g, 3))
    with pytest.raises(AssertionError):
        LY.unpack_adjacency(LY.pack_adjacency(g, 2), 1)  # r < d


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_pack_unpack_roundtrip_property(data):
    """For any pool whose rows all fit in degree D, packing to D and
    unpacking to the original width R is the canonical packed form at R —
    no edge is lost, duplicated, or reordered."""
    n = data.draw(st.integers(1, 12))
    r = data.draw(st.integers(1, 9))
    d = data.draw(st.integers(1, r))
    rows = data.draw(st.lists(
        st.lists(st.integers(0, n - 1), min_size=0, max_size=d,
                 unique=True),
        min_size=n, max_size=n))
    g = np.full((n, r), -1, np.int32)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    for i, edges in enumerate(rows):
        # scatter the ≤ d edges into random columns (holes anywhere)
        cols = rng.choice(r, size=len(edges), replace=False)
        g[i, np.sort(cols)] = edges
    np.testing.assert_array_equal(
        LY.unpack_adjacency(LY.pack_adjacency(g, d), r),
        LY.pack_adjacency(g, r))


def test_order_permutations_are_bijections(case):
    x, _, pool = case
    g = np.asarray(pool.ids)
    valid = np.ones(N, bool)
    valid[::7] = False
    for order in LY.ORDERS:
        for v in (None, valid):
            perm = LY.order_permutation(g, order, entry=3, valid=v)
            assert np.array_equal(np.sort(perm), np.arange(N)), order
    # identity really is the identity; bfs puts the entry first
    np.testing.assert_array_equal(
        LY.order_permutation(g, "identity"), np.arange(N))
    assert LY.order_permutation(g, "bfs", entry=17)[17] == 0


# ---------------------------------------------------------------------------
# bitwise equivalence: optimized == unoptimized, per precision rung
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ["bfs", "hub"])
@pytest.mark.parametrize("precision", VS.PRECISIONS)
def test_optimized_search_bitwise_equal(case, precision, order):
    """The acceptance core: renumbering + packing changes NOTHING the
    caller can observe — ids (in original numbering), dists, and the
    n_expanded trajectory are bitwise identical on every precision rung,
    with the int8 rung exercising the fp32 rescore tier through the
    permutation as well."""
    x, q, pool = case
    vs = x if precision == "fp32" else VS.encode(x, precision)
    rescore = None if precision == "fp32" else x
    base = search(vs, pool.ids, q, k=K, ef=EF, rescore=rescore)
    opt = LY.optimize(vs, pool, order=order, rescore=rescore)
    assert opt.order == order and not opt.pruned
    assert opt.degree == LY.packed_degree(pool.ids)
    _assert_same(base, opt.search(q, k=K, ef=EF), f"{precision}/{order}")


def test_optimized_search_filtered_bitwise_equal(case):
    """Filtered search: the label words permute with the vertices and the
    per-query predicate is row-independent, so the filtered result set is
    bitwise unchanged too."""
    x, q, pool = case
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(3), (N,), 0, 20), 20)
    fw = L.random_query_filters(jax.random.PRNGKey(4), NQ, 20, 0.25)
    base = search(x, pool.ids, q, k=K, ef=EF, labels=store, filter=fw)
    opt = LY.optimize(x, pool, order="bfs", labels=store)
    got = opt.search(q, k=K, ef=EF, filter=fw)
    _assert_same(base, got, "filtered")
    assert L.predicate_fraction(got.ids, fw, store.words) == 1.0


def test_optimized_search_filtered_int8_rescore_bitwise_equal(case):
    """The full stack at once: int8 traversal + fp32 rescore + filter +
    tombstones, through a hub renumbering."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    valid = jax.random.bernoulli(jax.random.PRNGKey(5), 0.85, (N,))
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(6), (N,), 0, 12), 12)
    fw = L.random_query_filters(jax.random.PRNGKey(7), NQ, 12, 0.3)
    base = search(vs, pool.ids, q, k=K, ef=EF, valid=valid, rescore=x,
                  labels=store, filter=fw)
    opt = LY.optimize(vs, pool, order="hub", valid=valid, rescore=x,
                      labels=store)
    _assert_same(base, opt.search(q, k=K, ef=EF, filter=fw), "full-stack")


@pytest.mark.parametrize("visited,cap", [("dense", None), ("hashed", 512)])
def test_optimized_search_visited_modes_bitwise_equal(case, visited, cap):
    """Dense visited is positional (trivially permutation-safe); the
    hashed table is bitwise-safe at cap ≥ N, where identity-mod probing
    is injective — the contract DESIGN.md §10 documents."""
    x, q, pool = case
    base = search(x, pool.ids, q, k=K, ef=EF, visited=visited,
                  visited_cap=cap)
    opt = LY.optimize(x, pool, order="bfs")
    _assert_same(base, opt.search(q, k=K, ef=EF, visited=visited,
                                  visited_cap=cap), visited)


_PROP = {}


def _prop_case():
    """Self-contained (no pytest fixture) corpus for the hypothesis
    property — hypothesis re-runs the test body per example and must not
    interact with fixture lifecycles."""
    if not _PROP:
        x = synthetic.make_preset(jax.random.PRNGKey(8), "tiny", 160)
        q = synthetic.queries_from(jax.random.PRNGKey(9), x, 8)
        pool = grnnd.build_graph(
            jax.random.PRNGKey(10), x,
            grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2, pairs_per_vertex=8))
        _PROP["case"] = (x, q, pool, search(x, pool.ids, q, k=5, ef=16))
    return _PROP["case"]


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_search_invariant_under_any_permutation(seed):
    """The property behind the whole pass: not just the bfs/hub orders —
    ANY bijection on [0, N) leaves the search bitwise invariant once the
    inverse map is applied to the returned ids."""
    x, q, pool, base = _prop_case()
    perm = np.random.default_rng(seed).permutation(x.shape[0])
    opt = LY.optimize(x, pool, permutation=perm)
    assert opt.order == "custom"
    _assert_same(base, opt.search(q, k=5, ef=16), f"perm-seed{seed}")


def test_optimize_rejects_non_bijection(case):
    x, _, pool = case
    bad = np.zeros(N, np.int64)
    with pytest.raises(AssertionError):
        LY.optimize(x, pool, permutation=bad)


# ---------------------------------------------------------------------------
# detour pruning (opt-in; intentionally NOT bitwise)
# ---------------------------------------------------------------------------

def test_pruned_index_degree_subset_and_recall(case):
    x, q, pool = case
    d = LY.packed_degree(pool.ids)
    target = max(2, d // 2)
    opt = LY.optimize(x, pool, order="bfs", prune=True, degree=target)
    assert opt.pruned and opt.degree == target
    # pruning only ever KEEPS edges: every optimized row's ids, mapped
    # back to original numbering, are a subset of the original pool row
    g_opt = np.asarray(opt.graph_ids)
    inv = np.asarray(opt.inv)
    g_orig = np.asarray(pool.ids)
    for new in range(N):
        old = inv[new]
        kept = g_opt[new][g_opt[new] >= 0]
        assert set(inv[kept].tolist()) <= set(
            g_orig[old][g_orig[old] >= 0].tolist()), old
    gt = recall.brute_force_knn(x, q, K)
    rec = recall.recall_at_k(opt.search(q, k=K, ef=EF).ids, gt)
    assert rec >= 0.9, rec


def test_detour_counts_chain():
    """Hand-checkable 3-vertex chain 0–1–2: the two long edges (0→2 and
    2→0, both rank 1, d=4) are detourable through the middle vertex 1
    (both hops d=1); the middle vertex's own edges are not."""
    ids = np.array([[1, 2], [0, 2], [1, 0]], np.int32)
    dists = np.array([[1.0, 4.0], [1.0, 1.0], [1.0, 4.0]], np.float32)
    counts = LY.detour_counts(ids, dists)
    np.testing.assert_array_equal(counts, [[0, 1], [0, 0], [0, 1]])
    pruned = LY.prune_adjacency(ids, dists, 1)
    np.testing.assert_array_equal(pruned, [[1], [0], [1]])


# ---------------------------------------------------------------------------
# sharded parity: ids_map through distributed_search
# ---------------------------------------------------------------------------

def test_distributed_optimized_matches_and_keys_cache(case):
    """Single-shard mesh in-process: the optimized distributed search is
    bitwise-identical to the in-process optimized search, and `has_map`
    is part of the shard_map executable cache key — an unmapped compile
    of identical shapes is never reused for a mapped call."""
    from repro.core import distributed
    from repro.core.distributed import _sharded_search_fn
    x, q, pool = case
    mesh = make_mesh((1,), ("lay",))
    opt = LY.optimize(x, pool, order="bfs")
    want = opt.search(q, k=K, ef=EF)
    _ = distributed.distributed_search(mesh, ("lay",), opt.x, opt.graph_ids,
                                       q, k=K, ef=EF, entry=opt.entry)
    before = _sharded_search_fn.cache_info().currsize
    got = opt.distributed_search(mesh, ("lay",), q, k=K, ef=EF)
    after = _sharded_search_fn.cache_info().currsize
    assert after == before + 1  # has_map keys the executable
    _assert_same(want, got, "dist-1shard")
    _assert_same(search(x, pool.ids, q, k=K, ef=EF), got, "dist-vs-base")


@pytest.mark.slow
def test_distributed_optimized_shard_count_invariance():
    """2/4-shard subprocess (forced host devices): the optimized
    distributed search stays bitwise-identical to BOTH the single-device
    optimized search and the unoptimized baseline, per precision rung —
    the ids_map shards as replicated state, so shard count is invisible."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        from repro.core import grnnd, layout as LY
        from repro.core import vecstore as VS
        from repro.core.search import search
        from repro.data import synthetic
        from repro.launch.mesh import make_mesh

        x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", 300)
        q = synthetic.queries_from(jax.random.PRNGKey(1), x, 18)  # 18 % 4 != 0
        cfg = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)
        pool = grnnd.build_graph(jax.random.PRNGKey(2), x, cfg)

        out = {}
        for prec in VS.PRECISIONS:
            vs = x if prec == "fp32" else VS.encode(x, prec)
            rescore = None if prec == "fp32" else x
            base = search(vs, pool.ids, q, k=10, ef=32, rescore=rescore)
            opt = LY.optimize(vs, pool, order="bfs", rescore=rescore)
            single = opt.search(q, k=10, ef=32)
            for s in (1, 2, 4):
                m = make_mesh((s,), ("data",),
                                  devices=jax.devices()[:s])
                got = opt.distributed_search(m, ("data",), q, k=10, ef=32)
                out[f"{prec}-shards{s}"] = {
                    "vs_single": (
                        np.array_equal(np.asarray(single.ids),
                                       np.asarray(got.ids))
                        and np.array_equal(np.asarray(single.dists),
                                           np.asarray(got.dists))),
                    "vs_base": (
                        np.array_equal(np.asarray(base.ids),
                                       np.asarray(got.ids))
                        and np.array_equal(np.asarray(base.dists),
                                           np.asarray(got.dists))),
                    "shape_ok": got.ids.shape == base.ids.shape,
                }
        print("RESULT" + json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    for key, r in res.items():
        assert r["shape_ok"], key
        assert r["vs_single"], key
        assert r["vs_base"], key
