"""Expert-parallel MoE (shard_map) must match the dense reference path.

Runs in a subprocess with 8 forced host devices (mesh must exist before
shard_map traces).  This is the §Perf iteration A1 correctness lock.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Subprocess with 8 forced host devices (~12 s) — nightly tier.
pytestmark = pytest.mark.slow

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ArchConfig
    from repro.models import moe as M
    from repro.distributed import hints as H
    from repro.launch.mesh import make_mesh

    out = {}
    for ncfg, (e, k, shared) in {
        "plain": (8, 2, 0),
        "shared": (8, 2, 1),
        "finegrained": (16, 4, 2),
    }.items():
        cfg = ArchConfig(
            name="t", family="moe", n_layers=2, d_model=32, n_heads=4,
            n_kv_heads=2, d_head=8, d_ff=64, vocab=64,
            n_experts=e, top_k=k, d_expert=16, n_shared_experts=shared,
            moe_capacity_factor=16.0)
        params = M.init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        dense, aux_d = M.moe_block(params, cfg, x)
        mesh = make_mesh((2, 4), ("data", "model"))
        with H.use_hints(mesh), mesh:
            ep, aux_e = jax.jit(
                lambda p, v: M.moe_block(p, cfg, v))(params, x)
        out[ncfg] = {
            "err": float(jnp.max(jnp.abs(dense - ep))),
            "scale": float(jnp.max(jnp.abs(dense))),
            "drop_dense": float(aux_d["moe_drop_frac"]),
            "drop_ep": float(aux_e["moe_drop_frac"]),
        }
        # gradient parity through the EP path
        def loss(p, path):
            with H.use_hints(mesh) if path == "ep" else _null():
                y, _ = M.moe_block(p, cfg, x)
            return jnp.sum(y ** 2)
        import contextlib
        def _null():
            return contextlib.nullcontext()
        g_d = jax.grad(lambda p: jnp.sum(M.moe_block(p, cfg, x)[0] ** 2))(
            params)
        with H.use_hints(mesh), mesh:
            g_e = jax.jit(jax.grad(
                lambda p: jnp.sum(M.moe_block(p, cfg, x)[0] ** 2)))(params)
        gerr = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(g_d),
                                   jax.tree.leaves(g_e)))
        out[ncfg]["grad_err"] = gerr
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def ep_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("variant", ["plain", "shared", "finegrained"])
def test_ep_matches_dense(ep_results, variant):
    r = ep_results[variant]
    assert r["err"] < 1e-5 * max(r["scale"], 1.0), r


@pytest.mark.parametrize("variant", ["plain", "shared", "finegrained"])
def test_ep_gradients_match_dense(ep_results, variant):
    assert ep_results[variant]["grad_err"] < 1e-3, ep_results[variant]  # fp reduction-order tolerance


def test_no_drops_at_high_capacity(ep_results):
    for r in ep_results.values():
        assert r["drop_dense"] == 0.0
        assert r["drop_ep"] == 0.0
