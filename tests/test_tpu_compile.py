"""Main-path kernels compile for a TPU v5e at SIFT1M widths.

Interpret mode (the CPU parity suites) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, scratch or SMEM beyond the
chip's budget, primitives Mosaic cannot lower.  Each test here lowers one
kernel variant for a chip that is described, not attached
(`jax.experimental.topologies`, a v5e:2x2 host), compiles it with the
installed TPU compiler, and checks that the kernel made it into the
program.  Nothing runs, so these say nothing about results or time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under pytest-xdist
every worker imports this file.  The persistent compile cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gather_l2 import gather_sqdist_pallas
from repro.kernels.pairwise_l2 import pairwise_sqdist_pallas
from repro.kernels.rng_round import rng_round_pallas
from repro.kernels.search_expand import search_expand_pallas
from repro.kernels.topr_merge import topr_merge_pallas

# SIFT1M (configs/grnnd_paper.py): N=1M, D=128, R=P=48, build chunk 4096;
# a 1,024-query batch at ef=128 with an 8·ef hashed visited table
N, D, R, P, CHUNK, Q, EF, W = 1_000_000, 128, 48, 48, 4096, 1024, 128, 4


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "the Pallas kernel is not in the program"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
def test_rng_round_compiles(chip, dtype):
    quant = [((D,), jnp.float32)] * 2 if dtype == jnp.int8 else []
    _compile(chip, lambda *a: rng_round_pallas(*a),
             ((N, D), dtype), ((CHUNK, R), jnp.int32),
             ((CHUNK, R), jnp.float32), ((CHUNK, P), jnp.int32),
             ((CHUNK, P), jnp.int32), *quant)


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["plain", "filtered"])
def test_search_expand_compiles(chip, filtered):
    shapes = [((N, D), jnp.float32), ((Q, D), jnp.float32),
              ((Q, R), jnp.int32), ((Q, 8 * EF), jnp.int32)]
    if filtered:
        shapes += [((N, W), jnp.int32), ((Q, W), jnp.int32)]

        def fn(x, q, nb, tab, vw, fw):
            return search_expand_pallas(x, q, nb, tab, vwords=vw, fwords=fw)
    else:
        def fn(x, q, nb, tab):
            return search_expand_pallas(x, q, nb, tab)
    _compile(chip, fn, *shapes)


@pytest.mark.parametrize("q", [Q, 64], ids=["q1024", "q64"])
def test_search_expand_bitmap_compiles(chip, q):
    """The dense visited bitmap streams through VMEM a block of 8
    queries at a time, (8, ⌈N/32768⌉, 8, 128) int32 in and out, each
    double-buffered: at N = 1M that is 4 x 1 MB, inside the 16 MiB the
    compiler scopes for a kernel on a v5e without a vmem limit."""
    rows = 8 * -(-N // 32768)
    block = 8 * rows * 128 * 4
    assert 4 * block + 8 * R * D * 4 < 16 * 2**20
    _compile(chip, lambda x, qv, nb, bm: search_expand_pallas(
        x, qv, nb, None, bitmap=bm),
        ((N, D), jnp.float32), ((q, D), jnp.float32), ((q, R), jnp.int32),
        ((q, rows, 128), jnp.int32))


def test_gather_l2_compiles(chip):
    m = 24 * CHUNK  # one chunk's S=24 initial neighbors
    _compile(chip, gather_sqdist_pallas, ((N, D), jnp.float32),
             ((m,), jnp.int32), ((m,), jnp.int32))


def test_topr_merge_compiles(chip):
    _compile(chip, lambda i, d: topr_merge_pallas(i, d, EF),
             ((Q, EF + R), jnp.int32), ((Q, EF + R), jnp.float32))


def test_pairwise_l2_compiles(chip):
    _compile(chip, pairwise_sqdist_pallas, ((Q, D), jnp.float32),
             ((64 * 1024, D), jnp.float32))
