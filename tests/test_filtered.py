"""Filtered-search suite (PR CI fast tier): ISSUE 5 acceptance contracts.

Five contracts:

  * **filter-operand parity** — the fused `search_expand` kernel
    (interpret mode) matches the ref.py oracle bitwise WITH the predicate
    operands, on all three precision rungs (fp32/bf16/int8), per the same
    common-jit-context convention as the `valid`-mask suite in
    tests/test_dynamic.py;
  * **trace cleanliness** — the unfiltered path compiles WITHOUT the
    filter operands (trace-time flag, same idiom as `masked`): asserted
    on the pallas_call equation's operand/output counts in the jaxpr;
  * **route-through semantics** — a filtered-out vertex stays traversable
    (the only path to an allowed vertex may run through disallowed ones),
    in contrast to the tombstone mask, which severs it;
  * **saturating-ef exactness** — with ef >= N the filtered result set
    equals brute force over each query's allowed subset (hypothesis
    property over label assignments/predicates, plus fixed-seed cases
    that run without hypothesis installed);
  * **predicate invariant** — every returned id satisfies its query's
    predicate, across single-label, multi-label, and packed predicate
    forms, and across visited representations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import grnnd, labels as L, vecstore as VS
from repro.core.search import _table_insert, search
from repro.data import synthetic
from repro.kernels import ops, ref
from repro.kernels.search_expand import search_expand_pallas
from conftest import optional_hypothesis

# every suite in the interpret CI leg carries this marker: the
# matrix selects `-m kernel_parity` instead of a hand-kept file list
pytestmark = pytest.mark.kernel_parity


given, settings, st = optional_hypothesis()


# ---------------------------------------------------------------------------
# label packing
# ---------------------------------------------------------------------------

def test_pack_ids_roundtrip():
    ids = jnp.array([0, 31, 32, 63, 64, -1, 5], jnp.int32)
    words = L.pack_ids(ids, 70)
    assert words.shape == (7, 3)
    w = np.asarray(words)
    for i, v in enumerate(np.asarray(ids)):
        if v < 0:
            assert not w[i].any()
        else:
            assert (w[i, v // 32] >> (v % 32)) & 1
            assert bin(int(np.uint32(w[i, v // 32]))).count("1") == 1


def test_pack_bits_matches_pack_ids_on_onehot():
    ids = jnp.arange(40, dtype=jnp.int32)
    member = jnp.zeros((40, 40), bool).at[jnp.arange(40), ids].set(True)
    np.testing.assert_array_equal(np.asarray(L.pack_bits(member)),
                                  np.asarray(L.pack_ids(ids, 40)))


def test_query_words_forms_agree():
    """(Q,) id, (Q, L) bool, and (Q, W) packed predicates all normalize to
    the same operand."""
    idsq = jnp.array([3, 17, 0], jnp.int32)
    w = L.n_words(20)
    packed = L.pack_ids(idsq, 20)
    member = jnp.zeros((3, 20), bool).at[jnp.arange(3), idsq].set(True)
    np.testing.assert_array_equal(np.asarray(L.query_words(idsq, w)),
                                  np.asarray(packed))
    np.testing.assert_array_equal(np.asarray(L.query_words(member, w)),
                                  np.asarray(packed))
    np.testing.assert_array_equal(np.asarray(L.query_words(packed, w)),
                                  np.asarray(packed))


def test_encode_labels_freezes_space():
    store = L.encode_labels(jnp.array([0, 2, 5], jnp.int32), 33)
    assert store.w == 2 and store.capacity == 64
    with pytest.raises(AssertionError):
        L.encode_labels(jnp.array([40], jnp.int32), 33)


# ---------------------------------------------------------------------------
# kernel/oracle bitwise parity with the filter operand, per precision rung
# ---------------------------------------------------------------------------

def _expand_case(seed, qn, r, n, d, h, n_labels, sel):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(k, 7)
    x = synthetic.vector_dataset(k1, n, d, n_clusters=max(2, n // 16))
    q = synthetic.queries_from(k2, x, qn)
    nbrs = jax.random.randint(k3, (qn, r), -1, n)
    tab = _table_insert(
        jnp.full((qn, h), -1, jnp.int32),
        jnp.where(jax.random.bernoulli(k4, 0.5, (qn, r)), nbrs, -1))
    valid = jax.random.bernoulli(k5, 0.8, (n,))
    store = L.encode_labels(jax.random.randint(k6, (n,), 0, n_labels),
                            n_labels)
    fw = L.random_query_filters(k7, qn, n_labels, sel)
    return x, q, nbrs, tab, valid, store.words, fw


@pytest.mark.parametrize("precision", VS.PRECISIONS)
@pytest.mark.parametrize("qn,r,n,d,h,n_labels,sel", [
    (8, 10, 64, 12, 32, 40, 0.2),
    (5, 7, 50, 33, 16, 70, 0.05),   # D not lane-aligned, 3 bitset words
    (4, 8, 40, 16, 1, 8, 0.5),      # H = 1: the dense-path dummy table
    (3, 6, 30, 8, 3, 100, 0.01),    # H < PROBES, 1-label predicates
])
def test_expand_filter_matches_oracle(precision, qn, r, n, d, h,
                                      n_labels, sel):
    x, q, nbrs, tab, valid, vw, fw = _expand_case(
        23, qn, r, n, d, h, n_labels, sel)
    vs = VS.encode(x, precision)
    got = search_expand_pallas(vs.data, q, nbrs, tab, valid,
                               vs.scale, vs.offset, vw, fw, interpret=True)
    want = jax.jit(ref.search_expand_ref)(vs.data, q, nbrs, tab, valid,
                                          vs.scale, vs.offset, vw, fw)
    assert len(got) == len(want) == 4
    for name, g, w in zip(("ids", "dists", "fresh", "allowed"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{precision}/{name}")


def test_expand_filter_route_through_outputs():
    """The predicate must not perturb ids/dists/fresh — only add `allowed`."""
    x, q, nbrs, tab, valid, vw, fw = _expand_case(29, 6, 8, 48, 16, 32,
                                                  20, 0.2)
    plain = search_expand_pallas(x, q, nbrs, tab, valid, interpret=True)
    filt = search_expand_pallas(x, q, nbrs, tab, valid, None, None, vw, fw,
                                interpret=True)
    assert len(plain) == 3 and len(filt) == 4
    for g, w in zip(plain, filt[:3]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # allowed <= live, and matches the label store exactly
    allowed = np.asarray(filt[3])
    ids = np.asarray(filt[0])
    want = np.asarray(L.allowed_mask(jnp.asarray(ids), fw, vw))
    np.testing.assert_array_equal(allowed, want)


# ---------------------------------------------------------------------------
# trace cleanliness: unfiltered paths compile WITHOUT the filter operand
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr):
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for v in e.params.values():
            if hasattr(v, "jaxpr"):
                out.extend(_pallas_eqns(v.jaxpr))
            elif hasattr(v, "eqns"):
                out.extend(_pallas_eqns(v))
    return out


def test_unfiltered_trace_has_no_filter_operand():
    """The `filtered` flag is trace-time, same idiom as `masked`: the
    unfiltered kernel trace carries neither predicate operand nor the
    `allowed` output; the filtered trace carries exactly both operands
    and one extra output."""
    x = synthetic.vector_dataset(jax.random.PRNGKey(0), 40, 16)
    q = x[:4]
    nbrs = jnp.zeros((4, 6), jnp.int32)
    tab = jnp.full((4, 8), -1, jnp.int32)
    vw = L.encode_labels(jnp.zeros((40,), jnp.int32), 5).words
    fw = L.pack_ids(jnp.zeros((4,), jnp.int32), 5)

    plain = jax.make_jaxpr(
        lambda *a: search_expand_pallas(*a, interpret=True))(x, q, nbrs, tab)
    filt = jax.make_jaxpr(
        lambda *a: search_expand_pallas(a[0], a[1], a[2], a[3], None, None,
                                        None, a[4], a[5], interpret=True)
    )(x, q, nbrs, tab, vw, fw)
    (ep,), (ef_,) = _pallas_eqns(plain.jaxpr), _pallas_eqns(filt.jaxpr)
    assert len(ef_.invars) == len(ep.invars) + 2, (
        len(ep.invars), len(ef_.invars))
    assert len(ef_.outvars) == len(ep.outvars) + 1

    # end-to-end: the full `search` trace shows the same structure — every
    # per-step pallas expansion carries exactly 2 more operands and 1 more
    # output under a filter, and none of them exist without one
    g = jnp.zeros((40, 6), jnp.int32)
    with ops.backend("interpret"):
        sp = jax.make_jaxpr(
            lambda xx, gg, qq: search(xx, gg, qq, k=2, ef=4,
                                      entry=jnp.int32(0)))(x, g, q)
        sf = jax.make_jaxpr(
            lambda xx, gg, qq, v, f: search(xx, gg, qq, k=2, ef=4,
                                            entry=jnp.int32(0), labels=v,
                                            filter=f, overfetch=2)
        )(x, g, q, vw, fw)
    # the expand kernels (dense visited: the bitmap is their last output)
    ep2 = [e for e in _pallas_eqns(sp.jaxpr) if len(e.outvars) >= 3]
    ef2 = [e for e in _pallas_eqns(sf.jaxpr) if len(e.outvars) >= 3]
    assert ep2 and ef2
    assert all(len(e.outvars) == 4 for e in ep2)
    assert all(len(e.outvars) == 5 for e in ef2)
    assert all(len(e.invars) == len(ep2[0].invars) + 2 for e in ef2)


# ---------------------------------------------------------------------------
# route-through semantics vs the exclude (tombstone) mask
# ---------------------------------------------------------------------------

def test_route_through_vs_exclude():
    """Chain graph 0-1-2-3 with 1, 2 filtered out: the filter must ROUTE
    THROUGH them to return 3; the tombstone mask on the same vertices must
    sever the path (3 unreachable) — the two masks are different features.
    """
    xs = jnp.array([[0., 0.], [1., 0.], [2., 0.], [3., 0.]])
    g = jnp.array([[1, -1], [0, 2], [1, 3], [2, -1]], jnp.int32)
    q = jnp.array([[3.1, 0.]])
    store = L.encode_labels(jnp.array([0, 1, 1, 0], jnp.int32), 2)
    fw = L.pack_ids(jnp.array([0], jnp.int32), 2)

    res = search(xs, g, q, k=2, ef=4, entry=jnp.int32(0),
                 labels=store, filter=fw)
    assert np.asarray(res.ids)[0].tolist() == [3, 0]

    sev = search(xs, g, q, k=2, ef=4, entry=jnp.int32(0),
                 valid=jnp.array([True, False, False, True]))
    assert np.asarray(sev.ids)[0].tolist() == [0, -1]


def test_filter_composes_with_tombstones():
    """valid excludes from traversal; filter excludes from results only —
    a returned id must be live AND allowed."""
    x = synthetic.make_preset(jax.random.PRNGKey(3), "tiny", 150)
    pool = grnnd.build_graph(jax.random.PRNGKey(4), x,
                             grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2,
                                               pairs_per_vertex=8))
    q = synthetic.queries_from(jax.random.PRNGKey(5), x, 12)
    valid = jax.random.bernoulli(jax.random.PRNGKey(6), 0.7, (150,))
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(7), (150,), 0, 10), 10)
    fw = L.random_query_filters(jax.random.PRNGKey(8), 12, 10, 0.3)
    res = search(x, pool.ids, q, k=5, ef=32, valid=valid,
                 labels=store, filter=fw)
    ids = np.asarray(res.ids)
    ok = np.asarray(L.allowed_mask(jnp.asarray(ids), fw, store.words))
    live = np.asarray(valid)[np.clip(ids, 0, None)]
    assert ((ids < 0) | (ok & live)).all()


@pytest.mark.parametrize("visited", ["dense", "hashed"])
def test_predicate_invariant_all_visited_modes(visited):
    x = synthetic.make_preset(jax.random.PRNGKey(10), "tiny", 200)
    pool = grnnd.build_graph(jax.random.PRNGKey(11), x,
                             grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3,
                                               pairs_per_vertex=16))
    q = synthetic.queries_from(jax.random.PRNGKey(12), x, 16)
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(13), (200,), 0, 25), 25)
    fw = L.random_query_filters(jax.random.PRNGKey(14), 16, 25, 0.2)
    res = search(x, pool.ids, q, k=10, ef=48, visited=visited,
                 labels=store, filter=fw)
    assert L.predicate_fraction(res.ids, fw, store.words) == 1.0
    gt = L.filtered_brute_force(x, q, fw, store.words, 10)
    assert L.filtered_recall_at_k(res.ids, gt) >= 0.9


def test_multi_label_store_end_to_end():
    """Vertices carrying SETS of labels (encode_label_sets): a result is
    allowed iff its label set intersects the query's allowed set."""
    n, n_labels = 150, 16
    x = synthetic.make_preset(jax.random.PRNGKey(60), "tiny", n)
    pool = grnnd.build_graph(jax.random.PRNGKey(61), x,
                             grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2,
                                               pairs_per_vertex=8))
    q = synthetic.queries_from(jax.random.PRNGKey(62), x, 10)
    member = jax.random.bernoulli(jax.random.PRNGKey(63), 0.15,
                                  (n, n_labels))
    store = L.encode_label_sets(member)
    assert store.labels is None  # multi-label: the bitset is the identity
    fw = L.random_query_filters(jax.random.PRNGKey(64), 10, n_labels, 0.2)
    res = search(x, pool.ids, q, k=5, ef=32, labels=store, filter=fw)
    ids = np.asarray(res.ids)
    mem = np.asarray(member)
    allow = np.asarray(fw)
    for qi in range(10):
        # which labels does query qi allow?
        lab_ok = [(allow[qi, l // 32] >> (l % 32)) & 1
                  for l in range(n_labels)]
        for v in ids[qi]:
            if v >= 0:
                assert any(mem[v, l] and lab_ok[l]
                           for l in range(n_labels)), (qi, v)
    gt = L.filtered_brute_force(x, q, fw, store.words, 5)
    assert L.filtered_recall_at_k(res.ids, gt) >= 0.9


def test_filtered_backend_parity_end_to_end():
    """Interpret-backend filtered search (fused kernel) == ref-backend,
    bitwise, mirroring test_search_parity.test_search_backend_parity."""
    x = synthetic.make_preset(jax.random.PRNGKey(20), "tiny", 120)
    pool = grnnd.build_graph(jax.random.PRNGKey(21), x,
                             grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2,
                                               pairs_per_vertex=8))
    q = synthetic.queries_from(jax.random.PRNGKey(22), x, 8)
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(23), (120,), 0, 12), 12)
    fw = L.random_query_filters(jax.random.PRNGKey(24), 8, 12, 0.3)
    with ops.backend("ref"):
        a = search(x, pool.ids, q, k=5, ef=16, labels=store, filter=fw)
    with ops.backend("interpret"):
        b = search(x, pool.ids, q, k=5, ef=16, labels=store, filter=fw)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


# ---------------------------------------------------------------------------
# saturating ef: the filtered result set == brute force over the allowed set
# ---------------------------------------------------------------------------

def _reachable(graph_ids: np.ndarray, entry: int) -> np.ndarray:
    """BFS over the directed neighbor graph (the set a saturating-ef beam
    visits exactly)."""
    n = graph_ids.shape[0]
    seen = np.zeros((n,), bool)
    stack = [entry]
    seen[entry] = True
    while stack:
        v = stack.pop()
        for u in graph_ids[v]:
            if u >= 0 and not seen[u]:
                seen[u] = True
                stack.append(int(u))
    return seen


def _check_saturating_equals_brute_force(label_seed: int, filter_seed: int,
                                         sel: float):
    n, n_labels = 160, 24
    x = synthetic.make_preset(jax.random.PRNGKey(30), "tiny", n)
    pool = grnnd.build_graph(jax.random.PRNGKey(31), x,
                             grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3,
                                               pairs_per_vertex=16))
    q = synthetic.queries_from(jax.random.PRNGKey(32), x, 12)
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(label_seed), (n,), 0,
                           n_labels), n_labels)
    fw = L.random_query_filters(jax.random.PRNGKey(filter_seed), 12,
                                n_labels, sel)

    # the equality claim is about TRAVERSABLE vertices: restrict the truth
    # to the entry's reachable set (on these builds it is virtually always
    # everything; the guard keeps the property honest if it is not)
    from repro.core.search import medoid
    entry = int(medoid(x))
    reach = _reachable(np.asarray(pool.ids), entry)
    vw = jnp.where(jnp.asarray(reach)[:, None], store.words, 0)

    res = search(x, pool.ids, q, k=10, ef=n, max_steps=2 * n,
                 labels=store, filter=fw)
    gt = L.filtered_brute_force(x, q, fw, vw, 10)
    got = np.sort(np.asarray(res.ids), axis=1)
    want = np.sort(np.asarray(gt), axis=1)
    np.testing.assert_array_equal(got, want)
    assert L.filtered_recall_at_k(res.ids, gt) == 1.0


@pytest.mark.parametrize("label_seed,filter_seed,sel", [
    (40, 41, 0.05), (42, 43, 0.2), (44, 45, 0.6)])
def test_saturating_ef_equals_brute_force(label_seed, filter_seed, sel):
    _check_saturating_equals_brute_force(label_seed, filter_seed, sel)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_saturating_ef_equals_brute_force_property(data):
    """Hypothesis sweep over label assignments and predicate draws: at
    saturating ef, filtered search may never return anything other than
    the exact allowed-subset brute force."""
    label_seed = data.draw(st.integers(0, 2**16))
    filter_seed = data.draw(st.integers(0, 2**16))
    sel = data.draw(st.sampled_from([0.04, 0.1, 0.25, 0.5, 1.0]))
    _check_saturating_equals_brute_force(label_seed, filter_seed, sel)


# ---------------------------------------------------------------------------
# over-fetch policy
# ---------------------------------------------------------------------------

def test_overfetch_widens_working_ef():
    """At low selectivity, ef=k alone starves the result heap; the default
    over-fetch floor (4k) must recover a full result set when enough
    allowed vertices exist near the query."""
    x = synthetic.make_preset(jax.random.PRNGKey(50), "tiny", 200)
    pool = grnnd.build_graph(jax.random.PRNGKey(51), x,
                             grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3,
                                               pairs_per_vertex=16))
    q = synthetic.queries_from(jax.random.PRNGKey(52), x, 16)
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(53), (200,), 0, 4), 4)
    fw = L.random_query_filters(jax.random.PRNGKey(54), 16, 4, 0.25)
    starved = search(x, pool.ids, q, k=10, ef=10, labels=store, filter=fw,
                     overfetch=1)
    wide = search(x, pool.ids, q, k=10, ef=10, labels=store, filter=fw)
    n_starved = int((np.asarray(starved.ids) >= 0).sum())
    n_wide = int((np.asarray(wide.ids) >= 0).sum())
    assert n_wide >= n_starved
    gt = L.filtered_brute_force(x, q, fw, store.words, 10)
    assert (L.filtered_recall_at_k(wide.ids, gt)
            >= L.filtered_recall_at_k(starved.ids, gt))
