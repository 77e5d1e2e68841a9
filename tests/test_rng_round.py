"""Fused propagation-round kernel (interpret mode) vs the ref.py oracle.

The contract is BITWISE parity under a common jit context: the slot-pair
samples are drawn outside the kernel, and the kernel's distance math
follows the same subtract-square-reduce order as the oracle, so kill
masks, redirect requests, distances, and the top-R merged pools must be
identical — not just close.  (The oracle is jitted for the comparison
because XLA:CPU's jitted reduction codegen differs from eager dispatch by
~1e-7 for some D — a jit-vs-eager artifact, not a kernel-vs-oracle one;
the production pipeline always runs jitted.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import grnnd, pools
from repro.data import synthetic
from repro.kernels import ops, ref
from repro.kernels.rng_round import rng_round_pallas

# every suite in the interpret CI leg carries this marker: the
# matrix selects `-m kernel_parity` instead of a hand-kept file list
pytestmark = pytest.mark.kernel_parity



def _pool_and_pairs(seed, n, d, r, p, s=None):
    x = synthetic.vector_dataset(jax.random.PRNGKey(seed), n, d,
                                 n_clusters=max(2, n // 16))
    pool = pools.init_random(jax.random.PRNGKey(seed + 1), x,
                             s=s or min(6, r), r=r)
    ki, kj = jax.random.split(jax.random.PRNGKey(seed + 2))
    si = jax.random.randint(ki, (n, p), 0, r, jnp.int32)
    sj = jax.random.randint(kj, (n, p), 0, r, jnp.int32)
    return x, pool, si, sj


def _assert_round_parity(x, pool, si, sj):
    got = rng_round_pallas(x, pool.ids, pool.dists, si, sj, interpret=True)
    want = jax.jit(ref.rng_round_ref)(x, pool.ids, pool.dists, si, sj)
    for name, g, w in zip(("dst", "src", "dij", "kill"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    return got


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_round_outputs_match_oracle_across_seeds(seed):
    x, pool, si, sj = _pool_and_pairs(seed, n=48, d=16, r=8, p=8)
    _assert_round_parity(x, pool, si, sj)


@pytest.mark.parametrize("n,d,r,p", [
    (50, 33, 12, 16),   # D not lane-aligned, R not a power of two
    (40, 130, 7, 5),    # D just past one lane tile, odd R/P
    (30, 16, 1, 3),     # R = 1: no valid pair can ever hit
    (16, 8, 8, 1),      # single sampled pair per vertex
])
def test_round_edge_shapes(n, d, r, p):
    x, pool, si, sj = _pool_and_pairs(7, n=n, d=d, r=r, p=p)
    dst, _, _, kill = _assert_round_parity(x, pool, si, sj)
    if r == 1:
        assert not bool(jnp.any(kill))
        assert bool(jnp.all(dst == -1))


def test_round_empty_pool_is_inert():
    x = synthetic.vector_dataset(jax.random.PRNGKey(9), 20, 8, n_clusters=2)
    ep = pools.empty_pool(20, 6)
    si = jax.random.randint(jax.random.PRNGKey(1), (20, 4), 0, 6, jnp.int32)
    sj = jax.random.randint(jax.random.PRNGKey(2), (20, 4), 0, 6, jnp.int32)
    dst, _, _, kill = _assert_round_parity(x, ep, si, sj)
    assert bool(jnp.all(dst == -1))
    assert not bool(jnp.any(kill))


def test_partially_filled_pool_kills_only_live_slots():
    """s < r leaves empty tail slots; kills must never land on them."""
    x, pool, si, sj = _pool_and_pairs(11, n=64, d=12, r=16, p=16, s=4)
    _, _, _, kill = _assert_round_parity(x, pool, si, sj)
    assert not bool(jnp.any(jnp.asarray(kill) & (pool.ids < 0)))


@pytest.mark.parametrize("seed", [0, 3])
def test_merged_pools_identical_across_backends(seed):
    """End-to-end: update_round under the interpret backend must produce the
    SAME top-R merged pools as under the ref backend (sampling is shared, the
    distance math is bitwise-parallel, and the staging sort is common)."""
    x = synthetic.vector_dataset(jax.random.PRNGKey(seed), 96, 12,
                                 n_clusters=6)
    cfg = grnnd.GRNNDConfig(s=6, r=8, t1=1, t2=1, pairs_per_vertex=8)
    pool = pools.init_random(jax.random.PRNGKey(seed + 1), x, cfg.s, cfg.r)
    key = jax.random.PRNGKey(seed + 2)

    prev = ops.get_backend()
    try:
        ops.set_backend("ref")
        p_ref = jax.jit(grnnd.update_round, static_argnames="cfg")(
            x, pool, key, cfg)
        ops.set_backend("interpret")
        p_int = jax.jit(grnnd.update_round, static_argnames="cfg")(
            x, pool, key, cfg)
    finally:
        ops.set_backend(prev)

    np.testing.assert_array_equal(np.asarray(p_ref.ids), np.asarray(p_int.ids))
    np.testing.assert_array_equal(np.asarray(p_ref.dists),
                                  np.asarray(p_int.dists))


def test_chunked_round_matches_unchunked_matrices():
    """The lax.map chunked plan must reproduce the one-shot fused outputs."""
    x = synthetic.vector_dataset(jax.random.PRNGKey(5), 64, 8, n_clusters=4)
    cfg = grnnd.GRNNDConfig(s=6, r=8, t1=1, t2=1, pairs_per_vertex=6)
    pool = pools.init_random(jax.random.PRNGKey(6), x, cfg.s, cfg.r)
    key = jax.random.PRNGKey(7)
    # chunking changes the key->pair mapping (keys are split per chunk), so
    # compare each chunk against a direct call with the same chunk key
    cfg_c = cfg._replace(chunk_size=16)
    dst, src, dij, kill = grnnd._round_pair_matrices(x, pool, key, cfg_c)
    keys = jax.random.split(key, 64 // 16)
    for i in range(4):
        sl = slice(16 * i, 16 * (i + 1))
        want = grnnd._pair_matrices_chunk(
            x, pool.ids[sl], pool.dists[sl], keys[i], cfg_c)
        np.testing.assert_array_equal(np.asarray(dst[sl]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(kill[sl]),
                                      np.asarray(want[3]))


def test_ragged_last_chunk_matches_oracle():
    """n % chunk_size != 0: the last chunk is padded with empty vertices
    (never a one-shot fallback), each real row sees exactly the pairs a
    direct call on its chunk would, and the whole build through the
    interpret kernels stays bitwise equal to the ref build."""
    x = synthetic.vector_dataset(jax.random.PRNGKey(3), 70, 16, n_clusters=4)
    cfg = grnnd.GRNNDConfig(s=6, r=8, t1=2, t2=2, pairs_per_vertex=8,
                            chunk_size=32)
    pool = pools.init_random(jax.random.PRNGKey(4), x, cfg.s, cfg.r)
    key = jax.random.PRNGKey(5)
    plan = grnnd._chunked(pool, key, cfg)
    assert plan is not None and plan[0].shape == (3, 32, 8)
    dst, _, _, kill = grnnd._round_pair_matrices(x, pool, key, cfg)
    assert dst.shape == (70, 8) and kill.shape == (70, 8)
    keys = jax.random.split(key, 3)
    ids_pad = jnp.pad(pool.ids, ((0, 26), (0, 0)), constant_values=-1)
    d_pad = jnp.pad(pool.dists, ((0, 26), (0, 0)), constant_values=jnp.inf)
    want = grnnd._pair_matrices_chunk(x, ids_pad[64:], d_pad[64:], keys[2],
                                      cfg)
    np.testing.assert_array_equal(np.asarray(dst[64:]),
                                  np.asarray(want[0][:6]))
    assert bool(jnp.all(want[0][6:] == -1)) and not bool(jnp.any(want[3][6:]))

    built = {}
    for b in ("ref", "interpret"):
        with ops.backend(b):
            built[b] = grnnd.build_graph(jax.random.PRNGKey(6), x, cfg)
    np.testing.assert_array_equal(np.asarray(built["ref"].ids),
                                  np.asarray(built["interpret"].ids))
    np.testing.assert_array_equal(np.asarray(built["ref"].dists),
                                  np.asarray(built["interpret"].dists))


def test_pallas_backend_raises_off_tpu(monkeypatch):
    """'pallas' never degrades: off-TPU it raises; interpret runs only
    when asked for by name."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    ids = jnp.array([[3, 1, -1]], jnp.int32)
    d = jnp.array([[0.3, 0.1, jnp.inf]], jnp.float32)
    with ops.backend("pallas"):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ops.effective_backend()
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ops.topr_merge(ids, d, 2)
    with ops.backend("interpret"):
        assert ops.effective_backend() == "interpret"
        got = ops.topr_merge(ids, d, 2)
    np.testing.assert_array_equal(np.asarray(got[0]), [[1, 3]])


def test_env_var_selects_backend(monkeypatch):
    """REPRO_KERNEL_BACKEND is honored at import time; 'xla' aliases 'ref'."""
    assert ops._normalize("xla") == "ref"
    assert ops._normalize("pallas") == "pallas"
    with pytest.raises(AssertionError):
        ops._normalize("cuda")
