"""Tiered-storage suite: the ISSUE 9 placement-invariance tier.

Tier placement (core/vecstore.py `HostTier`, DESIGN.md §13) moves the
fp32 rescore tier off the accelerator: traversal stays on the
device-resident quantized tier, and the post-beam re-rank becomes an
explicit cross-boundary gather — top-ef ids out, ef·D fp32 bytes back —
finished by the same jitted `_rescore_merge` formula the in-jit rescore
tail runs.  Placement must be INVISIBLE to the caller, and this suite
locks that as a bitwise claim:

  * **placement invariance** — host-cold search returns bitwise-identical
    ids, dists AND n_expanded to device-hot on every quantized rung,
    composed with filtering, hashed (small-cap, real-collision) visited
    sets, and the PR 6 optimized layout (ids_map applied AFTER the
    re-rank, same order as in-jit);
  * **every consumer** — replicated `search`, `CorpusShardedIndex`
    (S ∈ {1, 2} + the 1-device mesh executor), `distributed_search`
    (incl. the filtered pre-widened path), `DynamicIndex` through
    insert/delete churn, and the batching engine's `StaticWorker`;
  * **the memory claim** — `memory_report` attributes ZERO device bytes
    to a host-placed rescore tier (the N-ceiling lift fig15 measures),
    with the replicated-entry keys unchanged;
  * **the satellite regressions** — the pad-slot gather mask (no fp32
    row crosses the boundary for a -1 slot), the cached-entry delete
    invalidation interplay, and the empty-corpus quantizer path growing
    into a searchable host-tier index.

Runs in BOTH CI legs (REPRO_KERNEL_BACKEND=ref and =interpret) via the
`kernel_parity` marker.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus_shard as CS
from repro.core import grnnd, labels as L, layout as LY
from repro.core import vecstore as VS
from repro.core.dynamic import DynamicConfig, DynamicIndex
from repro.core.search import medoid, search
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.kernel_parity

K = 10
EF = 32
N = 260
NQ = 12
CFG = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)
QUANTIZED = tuple(p for p in VS.PRECISIONS if p != "fp32")


@pytest.fixture(scope="module")
def case():
    from repro.data import synthetic
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
# the HostTier object itself
# ---------------------------------------------------------------------------

def test_host_tier_placement_and_accounting(case):
    """The pinned tier lives on the CPU backend, reports zero device
    bytes and full host bytes, and dequantizes through the SAME formula
    as the in-jit rescore path (the parity precondition)."""
    x, _, _ = case
    vs = VS.encode(x, "int8")
    ht = VS.HostTier(vs)
    assert ht.data.devices() == {VS.host_device()}
    assert ht.shape == (N, x.shape[1]) and ht.n == N
    assert ht.device_bytes() == 0
    assert ht.host_bytes() == N * x.shape[1] * 4
    np.testing.assert_array_equal(np.asarray(ht.data),
                                  np.asarray(VS.dequant(vs)))
    assert VS.is_host(ht) and not VS.is_host(x) and not VS.is_host(vs)


def test_host_tier_gather_masks_pad_slots(case):
    """The satellite-3 regression: a -1 pad slot must contribute ZERO
    bytes to the cross-boundary transfer — not row 0's D floats, which
    the in-jit path's `clip(ids, 0)` harmlessly gathers on-device but a
    host tier would ship across the boundary.  Pad rows come back
    all-zero and `fetched_rows` counts only real rows."""
    x, _, _ = case
    ht = VS.HostTier(x)
    ids = jnp.asarray([[3, -1, 7], [-1, -1, 0]], jnp.int32)
    out = np.asarray(ht.gather(ids))
    assert out.shape == (2, 3, x.shape[1])
    xn = np.asarray(x)
    np.testing.assert_array_equal(out[0, 0], xn[3])
    np.testing.assert_array_equal(out[0, 2], xn[7])
    np.testing.assert_array_equal(out[1, 2], xn[0])
    assert not out[0, 1].any() and not out[1, 0].any() and not out[1, 1].any()
    assert ht.fetched_rows == 3  # -1 slots never cross the boundary


# ---------------------------------------------------------------------------
# placement invariance: host-cold == device-hot, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", QUANTIZED)
def test_host_tier_search_bitwise_equal(case, precision):
    """The acceptance core: moving the fp32 tier off-device changes
    NOTHING the caller can observe, on every quantized rung."""
    x, q, pool = case
    vs = VS.encode(x, precision)
    dev = search(vs, pool.ids, q, k=K, ef=EF, rescore=x)
    host = search(vs, pool.ids, q, k=K, ef=EF, rescore=VS.HostTier(x))
    _assert_same(dev, host, precision)


def test_host_tier_filtered_bitwise_equal(case):
    """Filtered search: route-through masking happens in the traversal
    tier; the predicate never touches the rescore placement."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    store = L.encode_labels(
        jax.random.randint(jax.random.PRNGKey(3), (N,), 0, 20), 20)
    fw = L.random_query_filters(jax.random.PRNGKey(4), NQ, 20, 0.25)
    dev = search(vs, pool.ids, q, k=K, ef=EF, rescore=x,
                 labels=store, filter=fw)
    host = search(vs, pool.ids, q, k=K, ef=EF, rescore=VS.HostTier(x),
                  labels=store, filter=fw)
    _assert_same(dev, host, "filtered")
    assert L.predicate_fraction(host.ids, fw, store.words) == 1.0


def test_host_tier_hashed_visited_bitwise_equal(case):
    """A small-cap hashed visited set with real collisions changes which
    candidates reach the final ef — both placements must re-rank the
    same candidate set identically."""
    x, q, pool = case
    vs = VS.encode(x, "bf16")
    dev = search(vs, pool.ids, q, k=K, ef=EF, rescore=x,
                 visited="hashed", visited_cap=64)
    host = search(vs, pool.ids, q, k=K, ef=EF, rescore=VS.HostTier(x),
                  visited="hashed", visited_cap=64)
    _assert_same(dev, host, "hashed")


def test_host_tier_layout_optimized_bitwise_equal(case):
    """The PR 6 composition: under an optimized layout the host re-rank
    runs in PERMUTED id space and the inverse map is applied after the
    k-slice — the same order as in-jit — so original-numbering results
    stay bitwise equal."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    opt = LY.optimize(vs, pool, order="hub", rescore=x)
    dev = opt.search(q, k=K, ef=EF)
    host = opt._replace(rescore=VS.HostTier(opt.rescore)).search(q, k=K, ef=EF)
    _assert_same(dev, host, "layout")


# ---------------------------------------------------------------------------
# corpus-sharded + distributed consumers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2])
def test_corpus_sharded_host_tier_bitwise_equal(case, n_shards):
    """`shard(tier='host')` keeps one UNSTACKED host tier indexed by
    global id; the post-combine re-rank (flat ids_map fold) is bitwise
    the owner-sliced on-device rescore — and bitwise the replicated
    search, transitively."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    dev = CS.shard(vs, pool.ids, n_shards, rescore=x)
    host = CS.shard(vs, pool.ids, n_shards, rescore=x, tier="host")
    assert VS.is_host(host.rescores)
    got = host.search(q, k=K, ef=EF)
    _assert_same(dev.search(q, k=K, ef=EF), got, f"S{n_shards}")
    _assert_same(search(vs, pool.ids, q, k=K, ef=EF, rescore=x), got,
                 f"S{n_shards}-vs-replicated")


def test_corpus_sharded_host_tier_mesh_executor(case):
    """The shard_map executor never sees the host tier (it is stripped
    before the mesh dispatch); the host re-rank applies after the
    owner-combine, bitwise the reference executor."""
    x, q, pool = case
    vs = VS.encode(x, "int8")
    mesh = make_mesh((1,), ("corp",))
    host = CS.shard(vs, pool.ids, 1, rescore=x, tier="host")
    got = host.search(q, k=K, ef=EF, mesh=mesh, axes=("corp",))
    _assert_same(search(vs, pool.ids, q, k=K, ef=EF, rescore=x), got,
                 "mesh-host")


def test_corpus_sharded_host_tier_memory_report(case):
    """The N-ceiling lift: a host-placed rescore tier contributes ZERO
    device bytes per shard (vs N·D·4/S device-resident), the bytes
    reappear host-side, and the pre-existing report keys are unchanged
    by the placement axis."""
    x, _, pool = case
    vs = VS.encode(x, "int8")
    dev = CS.memory_report(CS.shard(vs, pool.ids, 2, rescore=x))
    host = CS.memory_report(CS.shard(vs, pool.ids, 2, rescore=x,
                                     tier="host"))
    assert dev["rescore_device_bytes"] > 0
    assert host["rescore_device_bytes"] == 0
    assert host["rescore_host_bytes"] == N * x.shape[1] * 4
    assert dev["rescore_host_bytes"] == 0
    assert host["per_shard_bytes"] < dev["per_shard_bytes"]
    # the lift shows up in BOTH layouts: exactly the fp32 tier's bytes
    # leave the replicated-per-device footprint too (N=260, S=2 divides
    # evenly, so the true-N fraction is 1 and the delta is exact)
    assert (dev["replicated_bytes"] - host["replicated_bytes"]
            == N * x.shape[1] * 4)


@pytest.mark.parametrize("filtered", [False, True])
def test_distributed_search_host_tier_bitwise_equal(case, filtered):
    """Query-sharded mesh search under the host tier: shards traverse
    WITHOUT the rescore operand (full-ef results, ids_map deferred) and
    the re-rank crosses the boundary once per batch.  The filtered leg
    exercises the pre-widened ef path (the inner search's overfetch is
    folded into ef_run so route-through refills are identical)."""
    from repro.core.distributed import distributed_search
    x, q, pool = case
    vs = VS.encode(x, "int8")
    mesh = make_mesh((1,), ("q",))
    kw = {}
    if filtered:
        store = L.encode_labels(
            jax.random.randint(jax.random.PRNGKey(5), (N,), 0, 16), 16)
        kw = dict(labels=store,
                  filter=L.random_query_filters(jax.random.PRNGKey(6),
                                                NQ, 16, 0.3))
    dev = search(vs, pool.ids, q, k=K, ef=EF, rescore=x, **kw)
    got = distributed_search(mesh, ("q",), vs, pool.ids, q, k=K, ef=EF,
                             rescore=VS.HostTier(x), **kw)
    _assert_same(dev, got, f"dist/filtered={filtered}")


# ---------------------------------------------------------------------------
# DynamicIndex + engine consumers
# ---------------------------------------------------------------------------

def _dyn_pair(x, pool, **cfg_kw):
    dev = DynamicIndex(x, pool, DynamicConfig(precision="int8",
                                              refine_rounds=1, **cfg_kw))
    host = DynamicIndex(x, pool, DynamicConfig(precision="int8",
                                               refine_rounds=1,
                                               tier="host", **cfg_kw))
    return dev, host


def test_dynamic_host_tier_bitwise_through_churn(case):
    """A host-tier DynamicIndex answers bitwise like its device twin —
    at rest, after an insert batch (the cached HostTier is invalidated
    by the buffer swap), and after deletes — and its fp32 buffer stays
    committed to the CPU backend through the mutations."""
    x, q, pool = case
    dev, host = _dyn_pair(x, pool)
    assert host.x.devices() == {VS.host_device()}
    _assert_same(dev.search(q, k=K, ef=EF), host.search(q, k=K, ef=EF),
                 "rest")
    extra = np.asarray(jax.random.normal(jax.random.PRNGKey(7),
                                         (8, x.shape[1]), jnp.float32))
    dev.insert(extra)
    host.insert(extra)
    assert host.x.devices() == {VS.host_device()}
    _assert_same(dev.search(q, k=K, ef=EF), host.search(q, k=K, ef=EF),
                 "post-insert")
    dev.delete(np.arange(0, 40, 3))
    host.delete(np.arange(0, 40, 3))
    _assert_same(dev.search(q, k=K, ef=EF), host.search(q, k=K, ef=EF),
                 "post-delete")


def test_dynamic_host_tier_corpus_search(case):
    """`corpus_search` inherits the index's placement: the sharded path
    under tier='host' matches the index's own search in label space."""
    x, q, pool = case
    _, host = _dyn_pair(x, pool)
    base = host.search(q, k=K, ef=EF)
    for s in (1, 2):
        _assert_same(base, host.corpus_search(q, s, k=K, ef=EF),
                     f"dyn-corpus/S{s}")


def test_engine_static_worker_host_tier_bitwise(case):
    """The batching engine under the host tier: a StaticWorker handed a
    HostTier rescore answers every request bitwise like the direct
    host-tier search on the same batch shapes."""
    from repro.serve.ann_engine import AnnEngine, EngineConfig, StaticWorker
    x, q, pool = case
    vs = VS.encode(x, "int8")
    ht = VS.HostTier(x)
    entry = medoid(vs)
    worker = StaticWorker(vs, pool.ids, entry=entry, rescore=ht)
    eng = AnnEngine(worker, EngineConfig(ef_menu=(EF,), max_batch=8))
    qn = np.asarray(q)
    rids = [eng.submit(qn[i], k=K, ef=EF) for i in range(NQ)]
    eng.run()
    direct = search(vs, pool.ids, q, k=K, ef=EF, entry=entry, rescore=ht)
    for i, rid in enumerate(rids):
        res = eng.take_result(rid)
        np.testing.assert_array_equal(res.ids, np.asarray(direct.ids)[i])
        np.testing.assert_array_equal(res.dists,
                                      np.asarray(direct.dists)[i])


# ---------------------------------------------------------------------------
# satellite regressions: empty-corpus quantizer + host tier end to end
# ---------------------------------------------------------------------------

def test_empty_corpus_grows_into_searchable_host_index():
    """The satellite-2 integration: an EMPTY (0, D) int8 host-tier index
    constructs (quantizer freezes scale=1/offset=0 instead of crashing
    on the empty reduction) and grows into a searchable index whose
    results match its device twin bitwise."""
    from repro.core.pools import Pool
    d = 16
    empty = jnp.zeros((0, d), jnp.float32)
    pool0 = Pool(jnp.zeros((0, 8), jnp.int32), jnp.zeros((0, 8), jnp.float32))
    dev, host = _dyn_pair(empty, pool0)
    assert host.n_live == 0
    xs = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (24, d),
                                      jnp.float32))
    dev.insert(xs)
    host.insert(xs)
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (4, d),
                                     jnp.float32))
    res_d = dev.search(q, k=4, ef=8)
    res_h = host.search(q, k=4, ef=8)
    _assert_same(res_d, res_h, "empty-grow")
    assert np.asarray(res_h.ids)[:, 0].min() >= 0


@pytest.mark.parametrize("n", [0, 1])
def test_quantizer_edge_corpus_well_defined(n):
    """N ∈ {0, 1} quantization: finite scale/offset (no empty-reduction
    crash, no 0-range division), exact shapes, and a lossless N=1
    round-trip through the frozen affine map."""
    d = 8
    x = jnp.arange(n * d, dtype=jnp.float32).reshape(n, d)
    vs = VS.quantize_int8(x)
    assert vs.data.shape == (n, d) and vs.data.dtype == jnp.int8
    assert np.isfinite(np.asarray(vs.scale)).all()
    assert np.isfinite(np.asarray(vs.offset)).all()
    deq = np.asarray(VS.dequant(vs))
    assert deq.shape == (n, d)
    if n == 1:
        np.testing.assert_allclose(deq, np.asarray(x), atol=1e-5)
    ht = VS.HostTier(vs)  # and the host tier wraps the edge case too
    assert ht.host_bytes() == n * d * 4 and ht.device_bytes() == 0
