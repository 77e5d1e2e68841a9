"""Distributed (shard_map) GRNND build: multi-device correctness.

Runs on 8 forced host devices in a subprocess (device count must be set
before jax initializes, so these tests shell out — the same pattern the
dry-run uses).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Subprocess multi-device build (~14 s) — nightly tier.
pytestmark = pytest.mark.slow

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import grnnd, recall, distributed
    from repro.core import pools as P
    from repro.core.search import search
    from repro.data import synthetic
    from repro.launch.mesh import make_mesh

    key = jax.random.PRNGKey(0)
    x = synthetic.make_preset(key, "tiny", 2048)
    cfg = grnnd.GRNNDConfig(s=8, r=16, t1=3, t2=3, pairs_per_vertex=16)
    q = synthetic.queries_from(jax.random.PRNGKey(2), x, 200)
    gt = recall.brute_force_knn(x, q, 10)

    out = {}
    mesh = make_mesh((8,), ("data",))
    for comm in ("allgather", "a2a"):
        pool = distributed.sharded_build_graph(
            mesh, ("data",), jax.random.PRNGKey(1), x, cfg, comm=comm)
        ids = jax.device_get(pool.ids)
        res = search(x, jnp.asarray(ids), q, k=10, ef=32)
        out[comm] = recall.recall_at_k(res.ids, gt)

    mesh2 = make_mesh((2, 4), ("pod", "data"))
    pool = distributed.sharded_build_graph(
        mesh2, ("pod", "data"), jax.random.PRNGKey(1), x, cfg)
    res = search(x, jnp.asarray(jax.device_get(pool.ids)), q, k=10, ef=32)
    out["two_axis"] = recall.recall_at_k(res.ids, gt)

    # single-device baseline with identical cfg/key for quality comparison
    pool1 = grnnd.build_graph(jax.random.PRNGKey(1), x, cfg)
    res1 = search(x, pool1.ids, q, k=10, ef=32)
    out["single"] = recall.recall_at_k(res1.ids, gt)

    # one reverse-edge round on a skewed pool (every neighbour on shard 0):
    # its requests overflow the a2a buckets, so the round must take the
    # exact exchange and equal the all-gather round bit for bit
    n_loc = 2048 // 8
    keys = jax.random.split(jax.random.PRNGKey(3), 2048)
    skew = jax.vmap(lambda k: jax.random.choice(
        k, n_loc, (cfg.r,), replace=False))(keys).astype(jnp.int32)
    sd = jnp.sum((x[:, None, :] - x[skew]) ** 2, -1)
    order = jnp.argsort(sd, axis=-1)
    skew_pool = P.Pool(jnp.take_along_axis(skew, order, 1),
                       jnp.take_along_axis(sd, order, 1))
    rev = {}
    for comm in ("allgather", "a2a"):
        fn = jax.jit(distributed.make_sharded_builder(
            mesh, ("data",), cfg, comm=comm))
        rev[comm] = np.asarray(
            fn(x, skew_pool, jax.random.PRNGKey(4), True).ids)
    out["reverse_equal"] = bool(np.array_equal(rev["a2a"], rev["allgather"]))
    # shard 0's rows took in reverse edges from every shard
    out["reverse_inserted"] = int(np.sum(
        rev["allgather"][:n_loc] >= n_loc))
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def dist_results():
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


def test_allgather_build_quality(dist_results):
    assert dist_results["allgather"] > 0.9


def test_a2a_matches_allgather(dist_results):
    assert abs(dist_results["a2a"] - dist_results["allgather"]) < 0.02


def test_multi_axis_mesh_build(dist_results):
    assert dist_results["two_axis"] > 0.9


def test_sharded_parity_with_single_device(dist_results):
    assert dist_results["allgather"] >= dist_results["single"] - 0.05


def test_a2a_reverse_round_equals_allgather(dist_results):
    assert dist_results["reverse_inserted"] > 0
    assert dist_results["reverse_equal"]
