"""Corpus-sharded index suite: the ISSUE 7 shard-count-invariance tier.

The corpus-sharded layout (core/corpus_shard.py, DESIGN.md §11) slices
every O(N) operand — vectors, graph rows, validity, rescore tier, label
words, id map — into S contiguous owner partitions and runs the SAME
beam loop as `core.search.search` with per-step owner-combines.  The
combines are order-free (min/max/or with identity fill; exactly one
owner contributes per slot), so the whole safety argument is a bitwise
one, and this suite locks it:

  * **shard-count invariance** — `sharded_search` returns bitwise-
    identical ids, dists AND n_expanded to the replicated search for
    S ∈ {1, 2, 3, 4} (including the uneven last-shard padding), on all
    three precision rungs (fp32/bf16/int8 + fp32 rescore), filtered and
    unfiltered, dense and hashed (small-cap, real-collision) visited
    sets, tombstoned, and composed with the PR 6 optimized layout;
  * **id-map laws** — global→(shard, local)→global is the identity for
    any (N, S) including padded last shards (hypothesis property), and
    cross-shard `topr_merge` of per-shard top-k equals top-k of the
    concatenation for ANY partition of the candidates (the reduction
    the per-shard result merge relies on; hypothesis property);
  * **sharded-build quality** — the divide-and-conquer build
    (per-partition GRNND + cross-boundary merge-refine) clears the
    tests/test_recall.py floor through the sharded search itself;
  * **mutation routing** — a corpus-sharded `DynamicIndex.corpus_search`
    is bitwise `search()` in label space through insert/delete/compact
    churn, and the mesh-routed insert staging is exactly the in-process
    staging;
  * **cache-key regression** — the shard_map executable cache
    (`distributed._corpus_search_fn`) keys on every operand-presence
    flag: an unfiltered compile is never reused for a filtered call of
    identical shapes.

Fast tier runs in BOTH CI legs (REPRO_KERNEL_BACKEND=ref and
=interpret); the multi-device shard_map matrix and the quality tier are
subprocess/scale-bound and ride the nightly `slow` tier.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus_shard as CS
from repro.core import grnnd, labels as L, layout as LY, recall
from repro.core import vecstore as VS
from repro.core.search import search
from repro.data import synthetic
from repro.kernels import ops
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
# id-map laws
# ---------------------------------------------------------------------------

def _assert_id_map_laws(n: int, s: int) -> None:
    """shard_of/local_of/global_of round-trip the full corpus and stay in
    range, including when the last shard is padded (n % s != 0)."""
    row0s, n_loc = CS.shard_bounds(n, s)
    assert len(row0s) == s and row0s[0] == 0
    assert n_loc == -(-n // s)          # ceil(n / s): minimal equal slices
    assert row0s == tuple(i * n_loc for i in range(s))
    g = np.arange(n, dtype=np.int64)
    sh, loc = CS.shard_of(g, n_loc), CS.local_of(g, n_loc)
    assert sh.min(initial=0) >= 0 and sh.max(initial=0) < s
    assert loc.min(initial=0) >= 0 and loc.max(initial=0) < n_loc
    np.testing.assert_array_equal(CS.global_of(sh, loc, n_loc), g)
    # ownership is contiguous: shard s owns exactly [row0, row0 + n_own)
    for i, row0 in enumerate(row0s):
        n_own = min(n_loc, n - row0)
        np.testing.assert_array_equal(sh == i,
                                      (g >= row0) & (g < row0 + n_own))


@pytest.mark.parametrize("n,s", [(1, 1), (7, 2), (260, 4), (100, 3),
                                 (64, 64), (5, 8)])
def test_id_map_round_trip(n, s):
    _assert_id_map_laws(n, s)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4096), st.integers(1, 16))
def test_id_map_round_trip_property(n, s):
    """For ANY corpus size and shard count — padding or not, more shards
    than rows or not — the global→(shard, local)→global map is the
    identity and ownership stays contiguous."""
    _assert_id_map_laws(n, s)


def _assert_merge_partition_law(ids: np.ndarray, dists: np.ndarray,
                                bounds: list, r: int) -> None:
    """topr_merge over a concatenation == topr_merge over per-group
    topr_merge outputs, for the given partition boundaries (the reduction
    the cross-shard result merge performs; groups here mirror disjoint
    shard ownership, padded with the (-1, +inf) identity fill)."""
    ids_j = jnp.asarray(ids[None], jnp.int32)
    d_j = jnp.asarray(dists[None], jnp.float32)
    want = ops.topr_merge(ids_j, d_j, r)
    parts_i, parts_d = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if lo == hi:
            continue  # an empty cell contributes the (-1, +inf) identity
        gi, gd = ops.topr_merge(ids_j[:, lo:hi], d_j[:, lo:hi], r)
        parts_i.append(gi)
        parts_d.append(gd)
    if not parts_i:
        parts_i = [jnp.full((1, r), -1, jnp.int32)]
        parts_d = [jnp.full((1, r), jnp.inf, jnp.float32)]
    got = ops.topr_merge(jnp.concatenate(parts_i, axis=1),
                         jnp.concatenate(parts_d, axis=1), r)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))


def test_cross_shard_merge_partition_fixed():
    ids = np.array([5, 0, 3, -1, 7, 2, 9], np.int32)
    dists = np.array([3., 1., 4., np.inf, 0.5, 2., 6.], np.float32)
    for bounds in ([0, 3, 7], [0, 1, 4, 7], [0, 7], [0, 0, 7]):
        _assert_merge_partition_law(ids, dists, bounds, r=4)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_cross_shard_merge_partition_property(data):
    """Merging per-shard top-r results is exact for ANY partition: the
    two-level reduction equals the single-level top-r of the full
    candidate set.  Distinct ids carry distinct distances (shard
    ownership is disjoint, and dedup-by-min makes the rest order-free),
    with empty slots at the (-1, +inf) identity."""
    w = data.draw(st.integers(1, 24))
    r = data.draw(st.integers(1, 12))
    seed = data.draw(st.integers(0, 2**16))
    n_cuts = data.draw(st.integers(0, min(4, w)))
    rng = np.random.default_rng(seed)
    ids = rng.permutation(2 * w)[:w].astype(np.int32)   # distinct ids
    dists = rng.permutation(4 * w)[:w].astype(np.float32)  # distinct dists
    empty = rng.random(w) < 0.25
    ids[empty] = -1
    dists[empty] = np.inf
    cuts = sorted(rng.choice(w + 1, size=n_cuts, replace=True).tolist())
    _assert_merge_partition_law(ids, dists, [0] + cuts + [w], r)


# ---------------------------------------------------------------------------
# shard-count invariance: sharded == replicated, bitwise (reference executor)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
@pytest.mark.parametrize("precision", VS.PRECISIONS)
def test_sharded_search_bitwise_equal(case, precision, n_shards):
    """The acceptance core: slicing the corpus changes NOTHING the caller
    can observe — ids, dists, and the n_expanded trajectory are bitwise
    identical for any shard count (S=3 leaves the last shard padded), on
    every precision rung, the quantized rungs rescoring through the
    owner-sliced fp32 tier."""
    x, q, pool = case
    vs = x if precision == "fp32" else VS.encode(x, precision)
    rescore = None if precision == "fp32" else x
    base = search(vs, pool.ids, q, k=K, ef=EF, rescore=rescore)
    idx = CS.shard(vs, pool.ids, n_shards, rescore=rescore)
    assert idx.n_shards == n_shards and idx.n == N
    _assert_same(base, idx.search(q, k=K, ef=EF),
                 f"{precision}/S{n_shards}")


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_search_filtered_bitwise_equal(case, n_shards):
    """Filtered search: vertex label words shard with their owners, the
    per-query predicate stays replicated — the route-through result set
    is bitwise unchanged and every returned id obeys its predicate."""
    x, q, pool = case
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(3), (N,), 0, 20), 20)
    fw = L.random_query_filters(jax.random.PRNGKey(4), NQ, 20, 0.25)
    base = search(x, pool.ids, q, k=K, ef=EF, labels=store, filter=fw)
    idx = CS.shard(x, pool.ids, n_shards, labels=store)
    got = idx.search(q, k=K, ef=EF, filter=fw)
    _assert_same(base, got, f"filtered/S{n_shards}")
    assert L.predicate_fraction(got.ids, fw, store.words) == 1.0


@pytest.mark.parametrize("n_shards", [2, 3])
def test_sharded_search_hashed_visited_bitwise_equal(case, n_shards):
    """The hashed visited set is replicated on GLOBAL ids outside the
    kernel (the kernel probes a dummy table), so even a small-cap table
    with real collisions — where which-id-wins depends on insertion
    order — stays bitwise shard-count-invariant."""
    x, q, pool = case
    base = search(x, pool.ids, q, k=K, ef=EF, visited="hashed",
                  visited_cap=64)
    idx = CS.shard(x, pool.ids, n_shards)
    _assert_same(base, idx.search(q, k=K, ef=EF, visited="hashed",
                                  visited_cap=64), f"hashed/S{n_shards}")


def test_sharded_search_tombstones_bitwise_equal(case):
    """The validity mask shards with its owners; the entry's own flag is
    captured at shard() time."""
    x, q, pool = case
    valid = jax.random.bernoulli(jax.random.PRNGKey(5), 0.85, (N,))
    base = search(x, pool.ids, q, k=K, ef=EF, valid=valid)
    idx = CS.shard(x, pool.ids, 2, valid=valid)
    _assert_same(base, idx.search(q, k=K, ef=EF), "tombstones")


def test_shard_optimized_composition_bitwise_equal(case):
    """The PR 6 composition contract: sharding an OptimizedIndex slices
    the PERMUTED rows and the inverse map, so the corpus-sharded search
    over the optimized layout still answers in the caller's original
    numbering — bitwise equal to both the optimized and the raw search,
    with the full stack (int8 + rescore + filter) on top."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(6), (N,), 0, 12), 12)
    fw = L.random_query_filters(jax.random.PRNGKey(7), NQ, 12, 0.3)
    opt = LY.optimize(vs, pool, order="hub", rescore=x, labels=store)
    want = opt.search(q, k=K, ef=EF, filter=fw)
    for s in (2, 4):
        idx = CS.shard_optimized(opt, s)
        _assert_same(want, idx.search(q, k=K, ef=EF, filter=fw),
                     f"opt/S{s}")
    _assert_same(search(vs, pool.ids, q, k=K, ef=EF, rescore=x,
                        labels=store, filter=fw), want, "opt-vs-raw")


def test_memory_report_scales_down(case):
    """The N-ceiling claim at unit scale: per-shard O(N) bytes shrink as
    ~1/S while the replicated baseline stays put."""
    x, _, pool = case
    per, repl = [], []
    for s in (1, 2, 4):
        m = CS.memory_report(CS.shard(x, pool.ids, s, rescore=None))
        per.append(m["per_shard_bytes"])
        repl.append(m["replicated_bytes"])
    assert repl[0] == repl[1] == repl[2]
    assert per[0] == repl[0]            # S=1 holds everything
    assert per[0] > per[1] > per[2]     # and the slices shrink with S
    assert per[1] <= repl[1] // 2 + 1024  # ~1/S plus replicated entry row


def test_mesh_executor_single_device_and_cache_key(case):
    """In-process 1-device mesh: the shard_map executor is bitwise the
    reference executor, and the executable cache keys on the filter
    operands — an unfiltered compile of identical shapes is never reused
    for a filtered call."""
    from repro.core.distributed import _corpus_search_fn
    x, q, pool = case
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(8), (N,), 0, 16), 16)
    fw = L.random_query_filters(jax.random.PRNGKey(9), NQ, 16, 0.3)
    mesh = make_mesh((1,), ("corp",))
    idx = CS.shard(x, pool.ids, 1, labels=store)
    got_u = idx.search(q, k=K, ef=EF, mesh=mesh, axes=("corp",))
    before = _corpus_search_fn.cache_info().currsize
    got_f = idx.search(q, k=K, ef=EF, filter=fw, mesh=mesh, axes=("corp",))
    after = _corpus_search_fn.cache_info().currsize
    assert after == before + 1  # has_filter keys the executable
    _assert_same(search(x, pool.ids, q, k=K, ef=EF), got_u, "mesh-u")
    _assert_same(search(x, pool.ids, q, k=K, ef=EF, labels=store,
                        filter=fw), got_f, "mesh-f")


def test_sharded_build_single_shard_is_plain_build(case):
    """S=1 short-circuits to build_graph: same key, same pool, bitwise."""
    x, _, pool = case
    p1 = CS.sharded_build(jax.random.PRNGKey(2), x, CFG, 1)
    np.testing.assert_array_equal(np.asarray(pool.ids), np.asarray(p1.ids))


def test_sharded_build_pool_invariants(case):
    """Structural contract of the divide-and-conquer build (the recall
    floor is the slow quality tier): the merged pool is a standard global
    (N, R) pool — ids in range, no self-edges, ascending per-row dists —
    that contains cross-boundary edges (the whole point of the
    merge-refine rounds) and searches correctly end to end."""
    x, q, _ = case
    pool = CS.sharded_build(jax.random.PRNGKey(3), x, CFG, 2,
                            merge_rounds=1)
    ids = np.asarray(pool.ids)
    dists = np.asarray(pool.dists)
    assert ids.shape == (N, CFG.r)
    assert ids.max() < N and ids.min() >= -1
    row0 = CS.shard_bounds(N, 2)[1]
    crossing = 0
    for v in range(N):
        row = ids[v][ids[v] >= 0]
        assert v not in row, v
        assert len(set(row.tolist())) == len(row), v
        dv = dists[v][ids[v] >= 0]
        assert np.all(np.diff(dv) >= 0), v
        crossing += int(np.any((row >= row0) != (v >= row0)))
    assert crossing > N // 4, crossing  # boundaries actually stitched
    res = CS.shard(x, pool.ids, 2).search(q, k=K, ef=EF)
    assert np.asarray(res.ids)[:, 0].min() >= 0


# ---------------------------------------------------------------------------
# quality + scale: nightly tier
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_build_reaches_recall_floor():
    """The divide-and-conquer build (independent per-partition GRNND +
    cross-boundary merge-refine) must clear the tests/test_recall.py
    floor within the default bounded merge rounds — searched through the
    corpus-sharded path itself, so the whole stack is on the hook."""
    if ops.effective_backend() == "interpret":
        pytest.skip("quality tier needs the n=1200 corpus; interpret "
                    "kernels step the grid from Python")
    cfg = grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3, pairs_per_vertex=16,
                            order="disordered")
    x = synthetic.make_preset(jax.random.PRNGKey(0), "sift-like", 1200)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, 128)
    gt = recall.brute_force_knn(x, q, K)
    for s in (2, 4):
        pool = CS.sharded_build(jax.random.PRNGKey(2), x, cfg, s)
        idx = CS.shard(x, pool.ids, s)
        rec = recall.recall_at_k(idx.search(q, k=K, ef=48).ids, gt)
        assert rec >= 0.86, (s, rec)


@pytest.mark.slow
def test_dynamic_corpus_search_label_stability():
    """Insert/delete/compact churn on a DynamicIndex, then corpus_search
    at S ∈ {1, 2, 4}: bitwise `search()` in label space — external-label
    stability composes with the global→(shard, local) map."""
    from repro.core.dynamic import DynamicConfig, DynamicIndex
    x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", 300)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, 16)
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x[:240], CFG)
    idx = DynamicIndex(x[:240], pool,
                       DynamicConfig(refine_rounds=1, compact_threshold=0.2))
    idx.insert(x[240:])
    idx.delete(np.arange(0, 240, 5))    # 48 tombstones -> triggers compact
    base = idx.search(q, k=K, ef=EF)
    for s in (1, 2, 4):
        _assert_same(base, idx.corpus_search(q, s, k=K, ef=EF),
                     f"dyn/S{s}")
    # deleted labels stay gone through the sharded path too
    got = np.asarray(idx.corpus_search(q, 2, k=K, ef=EF).ids)
    assert not (set(got[got >= 0].tolist())
                & set(range(0, 240, 5))), "deleted label returned"


_SLOW_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import corpus_shard as CS
    from repro.core import grnnd, labels as L, layout as LY
    from repro.core import vecstore as VS
    from repro.core.distributed import _corpus_search_fn
    from repro.core.search import search
    from repro.data import synthetic
    from repro.launch.mesh import make_mesh

    N, NQ, K, EF = 300, 18, 10, 32
    x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", N)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, NQ)
    cfg = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x, cfg)
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(3), (N,), 0, 20), 20)
    fw = L.random_query_filters(jax.random.PRNGKey(4), NQ, 20, 0.25)

    def same(a, b):
        return (np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
                and np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
                and np.array_equal(np.asarray(a.n_expanded),
                                   np.asarray(b.n_expanded)))

    out = {}
    for s in (2, 4):
        mesh = make_mesh((s,), ("data",), devices=jax.devices()[:s])
        idx = CS.shard(x, pool.ids, s)
        out[f"fp32-S{s}"] = same(
            search(x, pool.ids, q, k=K, ef=EF),
            idx.search(q, k=K, ef=EF, mesh=mesh))
        out[f"hashed-S{s}"] = same(
            search(x, pool.ids, q, k=K, ef=EF, visited="hashed",
                   visited_cap=64),
            idx.search(q, k=K, ef=EF, visited="hashed", visited_cap=64,
                       mesh=mesh))
        vs = VS.encode(x, "int8")
        idx8 = CS.shard(vs, pool.ids, s, rescore=x, labels=store)
        out[f"int8-S{s}"] = same(
            search(vs, pool.ids, q, k=K, ef=EF, rescore=x),
            idx8.search(q, k=K, ef=EF, mesh=mesh))
        out[f"filtered-S{s}"] = same(
            search(vs, pool.ids, q, k=K, ef=EF, rescore=x, labels=store,
                   filter=fw),
            idx8.search(q, k=K, ef=EF, filter=fw, mesh=mesh))
        opt = LY.optimize(x, pool, order="bfs")
        out[f"layout-S{s}"] = same(
            opt.search(q, k=K, ef=EF),
            CS.shard_optimized(opt, s).search(q, k=K, ef=EF, mesh=mesh))

    # cache-key regression on the multi-device executor
    mesh2 = make_mesh((2,), ("ck",), devices=jax.devices()[:2])
    idxf = CS.shard(x, pool.ids, 2, labels=store)
    _ = idxf.search(q, k=K, ef=EF, mesh=mesh2, axes=("ck",))
    before = _corpus_search_fn.cache_info().currsize
    got = idxf.search(q, k=K, ef=EF, filter=fw, mesh=mesh2, axes=("ck",))
    after = _corpus_search_fn.cache_info().currsize
    out["cache_key"] = {
        "grew": after == before + 1,
        "pred_ok": float(L.predicate_fraction(got.ids, fw, store.words)),
        "matches": same(search(x, pool.ids, q, k=K, ef=EF, labels=store,
                               filter=fw), got),
    }

    # mesh-routed insert staging == in-process staging, then a sharded
    # mesh search over the churned index
    from repro.core.dynamic import DynamicConfig, DynamicIndex
    dc = DynamicConfig(refine_rounds=1)
    plain = DynamicIndex(x[:260], pool_b := grnnd.build_graph(
        jax.random.PRNGKey(5), x[:260], cfg), dc)
    mesh3 = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    routed = DynamicIndex(x[:260], pool_b, dc, mesh=mesh3)
    lp = plain.insert(x[260:])
    lr = routed.insert(x[260:])
    out["dyn_insert"] = {
        "labels": np.array_equal(lp, lr),
        "pool_ids": np.array_equal(np.asarray(plain.pool.ids),
                                   np.asarray(routed.pool.ids)),
        "pool_dists": np.array_equal(np.asarray(plain.pool.dists),
                                     np.asarray(routed.pool.dists)),
    }
    m2 = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    out["dyn_mesh_search"] = same(
        routed.search(q, k=K, ef=EF),
        routed.corpus_search(q, 2, k=K, ef=EF, mesh=m2))
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SLOW_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("mode", ["fp32", "hashed", "int8", "filtered",
                                  "layout"])
def test_mesh_shard_count_invariance(mesh_results, shards, mode):
    """2/4-shard shard_map over forced host devices — each device holding
    only its slice — stays bitwise-identical to the replicated search:
    plain fp32, small-cap hashed visited, int8 + fp32 rescore, the
    filtered full stack, and the optimized-layout composition."""
    assert mesh_results[f"{mode}-S{shards}"]


@pytest.mark.slow
def test_mesh_filter_operands_key_executable_cache(mesh_results):
    res = mesh_results["cache_key"]
    assert res["grew"]
    assert res["pred_ok"] == 1.0
    assert res["matches"]


@pytest.mark.slow
def test_mesh_routed_insert_matches_in_process(mesh_results):
    """Owner-shard mutation routing (DESIGN.md §11.3): the mesh-routed
    symmetric-edge staging produces the identical pool — same labels,
    same ids, same dists — as the in-process staging, and a corpus-
    sharded mesh search over the churned index matches its own search."""
    res = mesh_results["dyn_insert"]
    assert res["labels"] and res["pool_ids"] and res["pool_dists"]
    assert mesh_results["dyn_mesh_search"]
