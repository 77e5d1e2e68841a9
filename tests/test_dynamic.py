"""Dynamic-index suite (PR CI fast tier): ISSUE 3 acceptance contracts.

Four contracts:

  * **incremental quality** — inserting 10% new vectors through
    `DynamicIndex` lands within 2 recall points of a from-scratch build on
    the same final corpus, at < 25% of the rebuild's propagation-round
    count (the acceptance bound; fig10 measures the same quantities);
  * **delete-mask parity** — the fused `search_expand` kernel (interpret
    mode) matches the ref.py oracle bitwise with a tombstone mask, per the
    same common-jit-context convention as tests/test_search_parity.py;
  * **deletion semantics** — tombstoned vertices vanish from results
    immediately and exactly (no routing through them either: the result
    equals a search over a physically rebuilt live graph's validity view);
  * **compaction** — `compact()` preserves search results exactly, in
    label space (parametrized sweep + hypothesis property test).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import grnnd, recall
from repro.core.dynamic import DynamicConfig, DynamicIndex
from repro.core.pools import insert_requests, Requests
from repro.core.search import _table_insert, medoid, search
from repro.data import synthetic
from repro.kernels import ref
from repro.kernels.search_expand import search_expand_pallas
from repro.launch.mesh import make_mesh
from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

K = 10
EF = 48
# the fast-tier preset (tests/test_recall.py): 9 propagation rounds/build
CFG = grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3, pairs_per_vertex=16)


@pytest.fixture(scope="module")
def corpus():
    x = synthetic.make_preset(jax.random.PRNGKey(0), "sift-like", 1200)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, 128)
    gt = recall.brute_force_knn(x, q, K)
    return x, q, gt


@pytest.fixture(scope="module")
def churned(corpus):
    """90% base build + 10% online insert, plus the rebuild baseline."""
    x, _, _ = corpus
    n_base = int(x.shape[0] * 0.9)
    pool_base = grnnd.build_graph(jax.random.PRNGKey(2), x[:n_base], CFG)
    pool_full = grnnd.build_graph(jax.random.PRNGKey(2), x, CFG)
    idx = DynamicIndex(
        x[:n_base], pool_base,
        DynamicConfig(seed_k=8, seed_ef=EF, refine_rounds=2,
                      pairs_per_vertex=CFG.pairs_per_vertex))
    idx.insert(x[n_base:])
    return idx, pool_full


# ---------------------------------------------------------------------------
# acceptance: insert-then-search recall vs from-scratch rebuild
# ---------------------------------------------------------------------------

def test_insert_recall_within_two_points_of_rebuild(corpus, churned):
    x, q, gt = corpus
    idx, pool_full = churned
    rec_rebuild = recall.recall_at_k(
        search(x, pool_full.ids, q, k=K, ef=EF).ids, gt)
    # labels coincide with x-row indices here, so gt applies unchanged
    rec_dyn = recall.recall_at_k(idx.search(q, k=K, ef=EF).ids, gt)
    assert rec_dyn >= rec_rebuild - 0.02, (rec_dyn, rec_rebuild)


def test_insert_cost_under_quarter_of_rebuild_rounds(churned):
    idx, _ = churned
    rebuild_rounds = CFG.t1 * CFG.t2
    assert idx.rounds_run < 0.25 * rebuild_rounds, (
        idx.rounds_run, rebuild_rounds)


def test_insert_returns_monotone_labels_and_grows_capacity(corpus):
    x, _, _ = corpus
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x[:200], CFG)
    idx = DynamicIndex(x[:200], pool,
                       DynamicConfig(refine_rounds=1, min_capacity=64))
    assert idx.capacity == 256  # next pow2 >= 200
    labs = idx.insert(x[200:280])
    assert labs.tolist() == list(range(200, 280))
    assert idx.capacity == 512  # doubled, not re-sized per insert
    assert idx.n_live == 280 and len(idx) == 280
    # searching still returns live labels only
    res = idx.search(x[:4], k=5, ef=16)
    assert np.asarray(res.ids).max() < 280


# ---------------------------------------------------------------------------
# delete-mask parity: fused kernel vs oracle, bitwise
# ---------------------------------------------------------------------------

def _expand_case(seed, qn, r, n, d, h, live_frac):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5 = jax.random.split(k, 5)
    x = synthetic.vector_dataset(k1, n, d, n_clusters=max(2, n // 16))
    q = synthetic.queries_from(k2, x, qn)
    nbrs = jax.random.randint(k3, (qn, r), -1, n)
    tab = _table_insert(
        jnp.full((qn, h), -1, jnp.int32),
        jnp.where(jax.random.bernoulli(k4, 0.5, (qn, r)), nbrs, -1))
    valid = jax.random.bernoulli(k5, live_frac, (n,))
    return x, q, nbrs, tab, valid


@pytest.mark.parametrize("qn,r,n,d,h,live_frac", [
    (8, 10, 64, 12, 32, 0.7),
    (5, 7, 50, 33, 16, 0.5),    # D not lane-aligned, odd shapes
    (4, 8, 40, 16, 1, 0.9),     # H = 1: the dense-path dummy table
    (3, 6, 30, 8, 3, 0.0),      # everything tombstoned
    (3, 6, 30, 8, 256, 1.0),    # nothing tombstoned == legacy path
])
def test_expand_delete_mask_matches_oracle(qn, r, n, d, h, live_frac):
    x, q, nbrs, tab, valid = _expand_case(17, qn, r, n, d, h, live_frac)
    got = search_expand_pallas(x, q, nbrs, tab, valid, interpret=True)
    want = jax.jit(ref.search_expand_ref)(x, q, nbrs, tab, valid)
    for name, g, w in zip(("ids", "dists", "fresh"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_expand_all_ones_mask_is_legacy_bitwise():
    x, q, nbrs, tab, _ = _expand_case(19, 6, 8, 48, 16, 32, 1.0)
    legacy = search_expand_pallas(x, q, nbrs, tab, None, interpret=True)
    masked = search_expand_pallas(x, q, nbrs, tab,
                                  jnp.ones((48,), bool), interpret=True)
    for g, w in zip(legacy, masked):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# deletion semantics + compaction exactness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_index(corpus):
    x, _, _ = corpus
    x = x[:600]
    pool = grnnd.build_graph(jax.random.PRNGKey(3), x, CFG)
    return x, pool


def _fresh_index(small_index):
    x, pool = small_index
    return DynamicIndex(x, pool, DynamicConfig(refine_rounds=1,
                                               compact_threshold=0.9))


def test_deleted_labels_never_returned(small_index, corpus):
    _, q, _ = corpus
    idx = _fresh_index(small_index)
    dels = np.arange(0, 600, 5)          # 20%
    assert idx.delete(dels) == dels.size
    assert idx.delete(dels) == 0         # idempotent no-op
    with pytest.raises(KeyError):
        idx.delete(np.array([10_000]))
    res = idx.search(q, k=K, ef=EF)
    got = set(np.asarray(res.ids).ravel().tolist()) - {-1}
    assert not (got & set(dels.tolist()))
    # quality against the LIVE ground truth stays high
    rec = recall.recall_at_k(res.ids, idx.exact_knn(q, K))
    assert rec >= 0.80, rec


@pytest.mark.parametrize("seed,frac", [(0, 0.1), (1, 0.33), (2, 0.6)])
def test_compact_preserves_search_exactly(small_index, corpus, seed, frac):
    _, q, _ = corpus
    idx = _fresh_index(small_index)
    rng = np.random.default_rng(seed)
    dels = rng.choice(600, size=int(600 * frac), replace=False)
    idx.delete(np.sort(dels))
    before = idx.search(q, k=K, ef=EF)
    gt_before = idx.exact_knn(q, K)
    idx.compact()
    assert idx.size == idx.n_live == 600 - dels.size
    after = idx.search(q, k=K, ef=EF)
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))
    np.testing.assert_array_equal(np.asarray(gt_before),
                                  np.asarray(idx.exact_knn(q, K)))


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_compact_preserves_search_property(data):
    """Hypothesis sweep of (delete set, query set) — compaction may never
    change a result, for any mutation history the strategy generates."""
    x = synthetic.make_preset(jax.random.PRNGKey(4), "tiny", 220)
    pool = grnnd.build_graph(jax.random.PRNGKey(5),  x,
                             grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2,
                                               pairs_per_vertex=8))
    idx = DynamicIndex(x, pool, DynamicConfig(refine_rounds=1,
                                              compact_threshold=0.95))
    dels = data.draw(st.sets(st.integers(0, 219), min_size=1, max_size=80))
    qseed = data.draw(st.integers(0, 2**16))
    idx.delete(np.sort(np.fromiter(dels, np.int64)))
    q = synthetic.queries_from(jax.random.PRNGKey(qseed), x, 16)
    before = idx.search(q, k=5, ef=16)
    idx.compact()
    after = idx.search(q, k=5, ef=16)
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))


def test_delete_retry_after_compact_is_noop(small_index):
    """At-least-once delivery: re-deleting a batch whose rows a compaction
    already reclaimed must return 0, not raise — only labels this index
    never issued are errors."""
    idx = _fresh_index(small_index)
    dels = np.arange(40)
    assert idx.delete(dels) == 40
    idx.compact()
    assert idx.delete(dels) == 0          # physically gone -> still a no-op
    with pytest.raises(KeyError):
        idx.delete(np.array([idx._next_label]))  # never issued -> error


def test_unrelated_delete_keeps_cached_entry_and_results(small_index, corpus):
    """The entry-cache regression (ISSUE 9 satellite): deleting vertices
    OTHER than the entry must leave the cached entry slot in place — no
    O(N·D) medoid recompute, and no silent reseed of later searches from
    a different vertex.  The delete set is chosen so the live-set medoid
    actually moves (the pre-fix blanket `_entry = None` would therefore
    have changed which vertex seeds the beam), and the post-delete search
    is pinned bitwise to the cached-entry traversal."""
    _, q, _ = corpus
    idx = _fresh_index(small_index)
    idx.search(q[:4], k=K, ef=EF)                  # warm the entry cache
    e = int(idx._entry)
    x600, _ = small_index
    # keep only the entry plus the 99 vertices FARTHEST from it (83%
    # tombstones, under the 0.9 auto-compact threshold): the live
    # centroid lands inside the far cluster, so a recomputed live-medoid
    # provably differs from the cached one
    dist_e = np.linalg.norm(np.asarray(x600) - np.asarray(x600)[e], axis=1)
    keep = set(np.argsort(dist_e)[-99:].tolist()) | {e}
    dels = np.array(sorted(set(range(600)) - keep))
    live = np.ones(600, bool)
    live[dels] = False
    e_live = int(medoid(x600, jnp.asarray(live)))
    assert e_live != e, "delete set must move the live medoid"
    idx.delete(dels)
    assert idx._entry is not None and int(idx._entry) == e
    got = idx.search(q, k=K, ef=EF)
    want = search(x600, idx.pool.ids[:600], q, k=K, ef=EF,
                  entry=jnp.int32(e), valid=idx.valid[:600])
    np.testing.assert_array_equal(np.asarray(got.ids),
                                  np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(want.dists))


def test_deleting_the_entry_slot_invalidates_cache(small_index, corpus):
    """The other half of the contract: when the tombstone DOES hit the
    cached entry slot, the cache must drop — the next search reseeds
    from the live medoid instead of a dead vertex."""
    _, q, _ = corpus
    idx = _fresh_index(small_index)
    idx.search(q[:4], k=K, ef=EF)
    e = int(idx._entry)
    idx.delete(np.array([e]))
    assert idx._entry is None
    res = idx.search(q, k=K, ef=EF)                # reseeds, still works
    assert int(idx._entry) != e
    assert bool(idx.valid[int(idx._entry)])
    got = set(np.asarray(res.ids).ravel().tolist())
    assert e not in got


def test_insert_into_emptied_index_rebootstraps():
    """Delete everything, compact to size 0, insert again: the batch must
    seed off itself (no live graph exists) and stay fully searchable — a
    sliding-window corpus that turns over completely must recover."""
    x = synthetic.make_preset(jax.random.PRNGKey(9), "tiny", 120)
    pool = grnnd.build_graph(jax.random.PRNGKey(10), x[:100],
                             grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2,
                                               pairs_per_vertex=8))
    idx = DynamicIndex(x[:100], pool,
                       DynamicConfig(refine_rounds=2, compact_threshold=0.5,
                                     seed_k=6))
    idx.delete(np.arange(100))            # auto-compacts to size 0
    assert idx.size == 0
    labs = idx.insert(x[100:120])
    assert labs.tolist() == list(range(100, 120))
    q = synthetic.queries_from(jax.random.PRNGKey(11), x[100:120], 16)
    res = idx.search(q, k=5, ef=16)
    rec = recall.recall_at_k(res.ids, idx.exact_knn(q, 5))
    assert rec >= 0.8, rec                # the new corpus is reachable


def test_insert_after_compact_roundtrip(small_index, corpus):
    """Labels survive the full mutate/compact/mutate cycle."""
    x, q, _ = corpus
    idx = _fresh_index(small_index)
    idx.delete(np.arange(100))
    idx.compact()
    labs = idx.insert(x[600:650])
    assert labs.tolist() == list(range(600, 650))
    res = idx.search(q[:16], k=K, ef=EF)
    got = set(np.asarray(res.ids).ravel().tolist())
    assert not (got & set(range(100)))   # deleted stay gone
    rec = recall.recall_at_k(res.ids, idx.exact_knn(q[:16], K))
    assert rec >= 0.80, rec


def test_all_dead_index_returns_empty_results():
    """Tombstoning everything must yield all -1 ids / +inf dists — in
    particular the (dead) entry vertex is dropped by the first beam merge,
    never returned (core/search.py entry guard)."""
    x = synthetic.make_preset(jax.random.PRNGKey(6), "tiny", 64)
    ids = jax.random.randint(jax.random.PRNGKey(7), (64, 8), -1, 64)
    q = synthetic.queries_from(jax.random.PRNGKey(8), x, 4)
    res = search(x, ids, q, k=5, ef=16, valid=jnp.zeros((64,), bool))
    assert bool(jnp.all(res.ids == -1))
    assert not bool(jnp.any(jnp.isfinite(res.dists)))
    # a single survivor is the only thing ever returned
    res1 = search(x, ids, q, k=5, ef=16,
                  valid=jnp.zeros((64,), bool).at[7].set(True))
    assert set(np.asarray(res1.ids).ravel().tolist()) <= {-1, 7}


# ---------------------------------------------------------------------------
# optimized layout on the dynamic index (ISSUE 6): external-label stability
# ---------------------------------------------------------------------------

def _paired_indices(small_index, order="bfs"):
    """The same corpus/graph as two DynamicIndexes: raw slot layout vs
    `DynamicConfig(layout=...)` (renumbered at construction and after
    every compaction)."""
    x, pool = small_index
    plain = DynamicIndex(x, pool, DynamicConfig(refine_rounds=1,
                                                compact_threshold=0.9))
    laid = DynamicIndex(x, pool, DynamicConfig(refine_rounds=1,
                                               compact_threshold=0.9,
                                               layout=order))
    return plain, laid


def test_layout_index_bitwise_equal_at_construction(small_index, corpus):
    """Before any mutation the layout is pure renumbering: label-space
    results are bitwise identical to the raw-slot index."""
    _, q, _ = corpus
    plain, laid = _paired_indices(small_index)
    a = plain.search(q, k=K, ef=EF)
    b = laid.search(q, k=K, ef=EF)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_layout_index_label_stability_under_churn(small_index, corpus):
    """Insert/delete on an optimized index issues the SAME external labels
    as the raw-slot index, deleted labels stay gone, and recall against
    the live ground truth holds — the layout must be invisible to the
    label-space API across mutations."""
    x, q, _ = corpus
    plain, laid = _paired_indices(small_index)
    for rnd in range(3):
        lo = 600 + 30 * rnd
        la = plain.insert(x[lo:lo + 30])
        lb = laid.insert(x[lo:lo + 30])
        np.testing.assert_array_equal(la, lb)       # identical new labels
        dels = np.arange(5 * rnd, 600, 37)
        assert plain.delete(dels) == laid.delete(dels)
    def live(idx):
        v = np.asarray(idx.valid[:idx.size])
        return set(np.asarray(idx.labels[:idx.size])[v].tolist())

    assert live(plain) == live(laid)
    np.testing.assert_array_equal(np.asarray(plain.exact_knn(q, K)),
                                  np.asarray(laid.exact_knn(q, K)))
    res = laid.search(q, k=K, ef=EF)
    got = set(np.asarray(res.ids).ravel().tolist()) - {-1}
    assert got <= live(laid)                        # deleted never returned
    rec = recall.recall_at_k(res.ids, laid.exact_knn(q, K))
    assert rec >= 0.80, rec


def test_layout_compact_reoptimizes_exactly(small_index, corpus):
    """compact() on a layout-configured index re-runs the layout pass on
    the survivors — and must STILL preserve label-space results exactly,
    the test_compact_preserves_search_exactly contract through a second
    renumbering."""
    _, q, _ = corpus
    _, laid = _paired_indices(small_index)
    rng = np.random.default_rng(12)
    dels = rng.choice(600, size=200, replace=False)
    laid.delete(np.sort(dels))
    before = laid.search(q, k=K, ef=EF)
    gt_before = laid.exact_knn(q, K)
    laid.compact()
    assert laid.cfg.layout == "bfs"                 # sticky re-optimize
    assert laid.size == laid.n_live == 400
    after = laid.search(q, k=K, ef=EF)
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))
    np.testing.assert_array_equal(np.asarray(gt_before),
                                  np.asarray(laid.exact_knn(q, K)))


def test_optimize_layout_is_idempotent_bitwise(small_index, corpus):
    """Re-running the layout pass on an already-optimized index permutes
    slots again but may never change label-space results."""
    _, q, _ = corpus
    _, laid = _paired_indices(small_index, order="hub")
    a = laid.search(q, k=K, ef=EF)
    laid.optimize_layout("hub")
    b = laid.search(q, k=K, ef=EF)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


# ---------------------------------------------------------------------------
# distributed routing: owner-shard insert == single-device insert
# ---------------------------------------------------------------------------

def test_sharded_apply_requests_matches_single_device(small_index):
    from repro.core.distributed import sharded_apply_requests
    x, pool = small_index
    mesh = make_mesh((1,), ("data",))
    kd, ks = jax.random.split(jax.random.PRNGKey(7))
    req = Requests(
        dst=jax.random.randint(kd, (64,), -1, 600),
        src=jax.random.randint(ks, (64,), 0, 600),
        dist=jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (64,))),
    )
    want = insert_requests(pool, req)
    got = sharded_apply_requests(mesh, ("data",), pool, req)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.dists),
                                  np.asarray(got.dists))


@pytest.mark.slow
def test_sharded_apply_requests_multi_shard_parity():
    """4 shards, adversarial requests: true self-inserts (dst == src, must
    drop) and cross-space collisions (global src == shard-LOCAL dst row,
    must keep) — the self filter has to run in global id space before
    re-basing (core/distributed._filter_to_local)."""
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import grnnd
        from repro.core.distributed import sharded_apply_requests
        from repro.core.pools import Requests, insert_requests
        from repro.data import synthetic
        from repro.launch.mesh import make_mesh

        x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", 256)
        cfg = grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2, pairs_per_vertex=8)
        pool = grnnd.build_graph(jax.random.PRNGKey(1), x, cfg)
        kd, ks = jax.random.split(jax.random.PRNGKey(2))
        dst = jax.random.randint(kd, (200,), -1, 256)
        src = jax.random.randint(ks, (200,), 0, 64)  # all < n_loc: collisions
        dst = dst.at[:20].set(src[:20])              # true self-inserts
        req = Requests(dst=dst, src=src,
                       dist=jnp.abs(jax.random.normal(
                           jax.random.PRNGKey(3), (200,))))
        want = insert_requests(pool, req)
        mesh = make_mesh((4,), ("data",))
        got = sharded_apply_requests(mesh, ("data",), pool, req)
        same = (np.array_equal(np.asarray(want.ids), np.asarray(got.ids))
                and np.array_equal(np.asarray(want.dists),
                                   np.asarray(got.dists)))
        print("RESULT", int(same))
    """)
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    assert line == "RESULT 1", proc.stdout
