"""Parity suite for the production query path (DESIGN.md §6).

Three contracts:

  * hashed-visited search is BITWISE identical to the dense-visited
    reference whenever `visited_cap >= N` — identity-mod hashing is
    injective there, so no collisions and no capacity misses exist;
  * at realistic caps (the `default_visited_cap` serving configuration)
    recall matches the dense baseline to within 1e-3 — collisions only
    cause harmless re-expansions, never false skips;
  * the fused `search_expand` kernel (interpret mode) matches the ref.py
    oracle bitwise, per the same common-jit-context convention as
    tests/test_rng_round.py, with either visited set: the hashed table
    or the dense bitmap, which the kernel also updates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import grnnd, recall
from repro.core import labels as L
from repro.core import vecstore as VS
from repro.core.search import _table_insert, search
from repro.data import synthetic
from repro.kernels import ops, ref
from repro.kernels.search_expand import search_expand_pallas
from conftest import optional_hypothesis

# every suite in the interpret CI leg carries this marker: the
# matrix selects `-m kernel_parity` instead of a hand-kept file list
pytestmark = pytest.mark.kernel_parity


given, settings, st = optional_hypothesis()


@pytest.fixture(scope="module")
def built():
    x = synthetic.make_preset(jax.random.PRNGKey(0), "tiny", 900)
    q = synthetic.queries_from(jax.random.PRNGKey(1), x, 96)
    gt = recall.brute_force_knn(x, q, 10)
    cfg = grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3, pairs_per_vertex=16)
    pool = grnnd.build_graph(jax.random.PRNGKey(2), x, cfg)
    return x, pool.ids, q, gt


# ---------------------------------------------------------------------------
# hashed visited set vs the dense reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ef", [16, 48])
def test_hashed_bitwise_identical_at_full_cap(built, ef):
    """visited_cap >= N: zero collisions -> the exact same trajectory."""
    x, ids, q, _ = built
    d = search(x, ids, q, k=10, ef=ef, visited="dense")
    h = search(x, ids, q, k=10, ef=ef, visited="hashed",
               visited_cap=x.shape[0])
    np.testing.assert_array_equal(np.asarray(d.ids), np.asarray(h.ids))
    np.testing.assert_array_equal(np.asarray(d.dists), np.asarray(h.dists))
    np.testing.assert_array_equal(np.asarray(d.n_expanded),
                                  np.asarray(h.n_expanded))


def test_hashed_recall_at_realistic_cap(built):
    """Default serving cap (O(ef), independent of N): recall within 1e-3."""
    x, ids, q, gt = built
    r_d = recall.recall_at_k(
        search(x, ids, q, k=10, ef=48, visited="dense").ids, gt)
    r_h = recall.recall_at_k(
        search(x, ids, q, k=10, ef=48, visited="hashed").ids, gt)
    assert abs(r_d - r_h) <= 1e-3, (r_d, r_h)


def test_hashed_tiny_cap_still_correct_distances(built):
    """A deliberately undersized table (many capacity misses) may cost
    work, but every returned (id, dist) pair must still be exact."""
    x, ids, q, _ = built
    res = search(x, ids, q[:8], k=5, ef=16, visited="hashed", visited_cap=32)
    r_ids, r_d = np.asarray(res.ids), np.asarray(res.dists)
    xs, qs = np.asarray(x), np.asarray(q[:8])
    for qi in range(8):
        row = r_ids[qi][r_ids[qi] >= 0]
        assert len(row) == len(set(row.tolist()))  # merge dedup held
        for slot, v in enumerate(r_ids[qi]):
            if v < 0:
                continue
            want = float(((qs[qi] - xs[v]) ** 2).sum())
            np.testing.assert_allclose(r_d[qi, slot], want, rtol=1e-4,
                                       atol=1e-5)


def _check_saturated_cap(built, cap, ef, qseed):
    """The visited-table SATURATION contract (DESIGN.md §6.1): when
    `visited_cap` is forced far below the true visited count, capacity
    misses flood the probe path — yet the search must still terminate
    (the beam's own dedup-and-expanded bookkeeping bounds the walk, not
    the table), return exact deduped (id, dist) pairs, and hold recall
    within 0.05 of the dense baseline (the documented degraded-recall
    floor; empirically the loss is ~0 — saturation costs re-expansion
    WORK, visible as an inflated n_expanded, not correctness)."""
    x, ids, q, _ = built
    q = synthetic.queries_from(jax.random.PRNGKey(qseed), x, 32)
    gt = recall.brute_force_knn(x, q, 10)
    d = search(x, ids, q, k=10, ef=ef, visited="dense")
    h = search(x, ids, q, k=10, ef=ef, visited="hashed", visited_cap=cap)
    # the table is saturated: far more fresh sightings than it can store
    assert float(jnp.sum(h.n_expanded)) > float(jnp.sum(d.n_expanded))
    r_ids, r_d = np.asarray(h.ids), np.asarray(h.dists)
    xs, qs = np.asarray(x), np.asarray(q)
    for qi in range(q.shape[0]):
        row = r_ids[qi][r_ids[qi] >= 0]
        assert len(row) == len(set(row.tolist()))     # merge dedup held
        for slot, v in enumerate(r_ids[qi]):
            if v >= 0:
                want = float(((qs[qi] - xs[v]) ** 2).sum())
                np.testing.assert_allclose(r_d[qi, slot], want, rtol=1e-4,
                                           atol=1e-5)
    r_dense = recall.recall_at_k(d.ids, gt)
    r_hash = recall.recall_at_k(h.ids, gt)
    assert r_hash >= r_dense - 0.05, (cap, ef, r_dense, r_hash)


@pytest.mark.parametrize("cap,ef", [(1, 16), (8, 48), (24, 48)])
def test_saturated_cap_terminates_and_holds_recall_floor(built, cap, ef):
    _check_saturated_cap(built, cap, ef, qseed=77)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_saturated_cap_property(built, data):
    """Hypothesis sweep of (cap, ef, query draw) deep inside saturation:
    no table size may break termination, exactness, or the recall floor."""
    cap = data.draw(st.integers(1, 64))
    ef = data.draw(st.sampled_from([16, 48]))
    qseed = data.draw(st.integers(0, 2**16))
    _check_saturated_cap(built, cap, ef, qseed)


def test_table_insert_then_probe_roundtrip():
    """Inserted ids are found; non-inserted ids are not (no false
    positives even under heavy collision load)."""
    key = jax.random.PRNGKey(3)
    ids = jax.random.randint(key, (4, 12), -1, 200)
    tab = _table_insert(jnp.full((4, 64), -1, jnp.int32), ids)
    pos = ref.visited_probe_positions(ids, 64)
    vals = np.asarray(tab)[np.arange(4)[:, None, None], np.asarray(pos)]
    found = np.any(vals == np.asarray(ids)[..., None], axis=-1)
    table_np = np.asarray(tab)
    for qi in range(4):
        stored = set(table_np[qi][table_np[qi] >= 0].tolist())
        for v, f in zip(np.asarray(ids)[qi], found[qi]):
            if v < 0:
                continue
            # found <-> actually stored (misses are allowed, lies are not)
            assert f == (int(v) in stored)


# ---------------------------------------------------------------------------
# fused expand kernel vs oracle
# ---------------------------------------------------------------------------

def _expand_case(seed, qn, r, n, d, h, fill):
    """(x, q, nbrs, visited): h > 0 gives an H-slot hashed table, h = 0
    the dense bitmap; `fill` marks about half the neighbour ids visited
    so probes hit and miss.  The bitmap case repeats slot 0's id in slot
    1 of every row: a duplicated id is fresh in both slots."""
    k = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(k, 4)
    x = synthetic.vector_dataset(k1, n, d, n_clusters=max(2, n // 16))
    q = synthetic.queries_from(k2, x, qn)
    nbrs = jax.random.randint(k3, (qn, r), -1, n)
    half = jnp.where(jax.random.bernoulli(k4, 0.5, (qn, r)), nbrs, -1)
    if h == 0:
        nbrs = nbrs.at[:, 1].set(nbrs[:, 0])
        bm = ref.visited_bitmap(qn, n, 0)
        if fill:
            bm = jax.jit(ref.search_expand_ref)(x, q, half, None,
                                                bitmap=bm)[-1]
        return x, q, nbrs, bm
    tab = jnp.full((qn, h), -1, jnp.int32)
    if fill:
        tab = _table_insert(tab, half)
    return x, q, nbrs, tab


def _expand(x, q, nbrs, vis, **kw):
    """Kernel (interpret) and oracle on the same operands, either visited
    set: `vis` is a bitmap when 3-D."""
    args = (None, kw.pop("valid", None)) if vis.ndim == 3 else (
        vis, kw.pop("valid", None))
    if vis.ndim == 3:
        kw["bitmap"] = vis
    got = search_expand_pallas(x, q, nbrs, *args, **kw, interpret=True)
    want = jax.jit(lambda *a: ref.search_expand_ref(*a, **kw))(
        x, q, nbrs, *args)
    return got, want


def _assert_outputs_equal(got, want, tag=""):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{tag} output {i}")


@pytest.mark.parametrize("qn,r,n,d,h,fill", [
    (8, 10, 64, 12, 32, True),
    (5, 7, 50, 33, 16, True),    # D not lane-aligned, odd shapes
    (4, 8, 40, 16, 1, False),    # H = 1: corpus sharding's dummy table
    (3, 6, 30, 8, 3, True),      # H < PROBES: multi-wrap probe windows
    (3, 6, 30, 8, 256, True),    # sparse table, wide H
    (8, 10, 64, 12, 0, True),    # dense bitmap, one tile
    (5, 7, 5000, 33, 0, True),   # bitmap: Q % 8 != 0, N % 4096 != 0
    (3, 6, 20, 8, 0, True),      # bitmap: N < 32, one word
    (9, 12, 40000, 8, 0, True),  # bitmap: two tiles, Q > one block
    (4, 8, 40, 16, 0, False),    # bitmap: only the entry set
])
def test_expand_matches_oracle(qn, r, n, d, h, fill):
    x, q, nbrs, vis = _expand_case(11, qn, r, n, d, h, fill)
    _assert_outputs_equal(*_expand(x, q, nbrs, vis))


@pytest.mark.parametrize("precision", VS.PRECISIONS)
@pytest.mark.parametrize("masked,filtered", [(True, False), (False, True),
                                             (True, True)])
def test_expand_bitmap_operands_match_oracle(precision, masked, filtered):
    """The bitmap variant beside every other operand: tombstones, the
    filter predicate, and each precision rung's stored rows."""
    x, q, nbrs, bm = _expand_case(31, 6, 9, 300, 24, 0, True)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    kw = {}
    if masked:
        kw["valid"] = jax.random.bernoulli(k1, 0.7, (300,))
    if filtered:
        kw["vwords"] = L.encode_labels(
            jax.random.randint(k2, (300,), 0, 40), 40).words
        kw["fwords"] = L.random_query_filters(k3, 6, 40, 0.2)
    vs = VS.encode(x, precision)
    got, want = _expand(vs.data, q, nbrs, bm, scale=vs.scale,
                        offset=vs.offset, **kw)
    assert len(got) == 4 + filtered
    _assert_outputs_equal(got, want, precision)


@pytest.mark.parametrize("qn,n,live", [(8, 64, 1.0), (5, 5000, 0.6),
                                       (3, 20, 0.5)])
def test_expand_bitmap_sets_exactly_the_fresh_bits(qn, n, live):
    """One call sets the bits of its fresh ids and no others: as many new
    bits as fresh slots, once a duplicated id is counted once."""
    x, q, nbrs, bm = _expand_case(37, qn, 8, n, 8, 0, True)
    valid = jax.random.bernoulli(jax.random.PRNGKey(9), live, (n,))
    ids, _, fresh, new = search_expand_pallas(
        x, q, nbrs, None, valid, bitmap=bm, interpret=True)

    def bits(b):
        return np.unpackbits(np.asarray(b).view(np.uint8),
                             bitorder="little").reshape(qn, -1)

    added = bits(new) & ~bits(bm)
    assert not np.any(bits(bm) & ~bits(new))         # nothing cleared
    want = np.zeros_like(added)
    ids, fresh = np.asarray(ids), np.asarray(fresh)
    for qi in range(qn):
        want[qi, ids[qi][fresh[qi]]] = 1
    np.testing.assert_array_equal(added, want)
    first = [[v not in ids[qi][:j] for j, v in enumerate(ids[qi])]
             for qi in range(qn)]
    assert added.sum() == (fresh & np.asarray(first)).sum()
    assert added.sum() > 0


@pytest.mark.parametrize("h", [16, 0], ids=["hashed", "bitmap"])
def test_expand_all_invalid_rows_inert(h):
    x, q, _, vis = _expand_case(13, 4, 6, 32, 8, h, h > 0)
    nbrs = jnp.full((4, 6), -1, jnp.int32)
    got, want = _expand(x, q, nbrs, vis)
    _assert_outputs_equal(got, want)
    ids, d, fresh = got[:3]
    assert bool(jnp.all(ids == -1))
    assert bool(jnp.all(jnp.isinf(d)))
    assert not bool(jnp.any(fresh))
    if h == 0:  # the bitmap comes back unchanged
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(vis))


def _expand_calls(jaxpr):
    """The search_expand pallas_call equations anywhere in a jaxpr."""
    out = []
    for e in jaxpr.eqns:
        if (e.primitive.name == "pallas_call" and "_search_expand_kernel"
                in e.params["jaxpr"].debug_info.func_src_info):
            out.append(e)
        for v in e.params.values():
            for j in (v if isinstance(v, (tuple, list)) else [v]):
                if hasattr(j, "jaxpr"):
                    out.extend(_expand_calls(j.jaxpr))
                elif hasattr(j, "eqns"):
                    out.extend(_expand_calls(j))
    return out


def test_only_dense_search_carries_the_bitmap(built):
    """The bitmap is a trace-time operand: the hashed search's expansion
    kernel has no bitmap operand, no fourth output and no alias; the
    dense one takes the bitmap and returns it in place."""
    x, ids, q, _ = built
    calls = {}
    with ops.backend("interpret"):
        for visited in ("dense", "hashed"):
            jx = jax.make_jaxpr(lambda a, b, c: search(
                a, b, c, k=5, ef=16, visited=visited))(x, ids, q[:8])
            (calls[visited],) = _expand_calls(jx.jaxpr)
    dense, hashed = calls["dense"], calls["hashed"]
    assert len(hashed.outvars) == 3
    assert not hashed.params["input_output_aliases"]
    assert len(dense.outvars) == 4
    assert dense.outvars[3].aval.shape == (8, 1, 8, 128)   # one tile
    assert dict(dense.params["input_output_aliases"])


def test_search_backend_parity_end_to_end(built):
    """Interpret-backend search (fused kernels) == ref-backend search,
    bitwise, for both visited representations."""
    x, ids, q, _ = built
    for visited in ("dense", "hashed"):
        with ops.backend("ref"):
            a = search(x, ids, q[:16], k=5, ef=16, visited=visited)
        with ops.backend("interpret"):
            b = search(x, ids, q[:16], k=5, ef=16, visited=visited)
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids),
                                      err_msg=visited)
        np.testing.assert_array_equal(np.asarray(a.dists),
                                      np.asarray(b.dists), err_msg=visited)
