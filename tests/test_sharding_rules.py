"""Sharding-rule unit tests: divisibility fallbacks, policy selection,
cache layouts — pure spec logic, no device mesh needed beyond a stub."""
import jax
import pytest
from jax.sharding import PartitionSpec as PSpec

from repro.configs import get_arch
from repro.configs.base import SHAPES


@pytest.fixture(scope="module")
def mesh16():
    # a (4, 4) stand-in mesh with the production axis names
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single CPU device replicated into an abstract mesh is not allowed;
    # use AbstractMesh for pure spec logic
    from jax.sharding import AbstractMesh
    return AbstractMesh((4, 4), ("data", "model"))


class TestParamSpecs:
    def _spec(self, mesh, name, shape, stacked=False):
        from repro.distributed.sharding import _param_spec
        return _param_spec(name, shape, mesh, stacked)

    def test_attention_heads_shard_when_divisible(self, mesh16):
        s = self._spec(mesh16, "attn/wq", (1024, 8, 128))
        assert s == PSpec(None, "model", None)

    def test_small_head_count_falls_back_to_head_dim(self, mesh16):
        # 2 heads cannot shard over 4-way model; Dh=128 can
        s = self._spec(mesh16, "attn/wq", (1024, 2, 128))
        assert s == PSpec(None, None, "model")

    def test_single_kv_head_falls_back(self, mesh16):
        s = self._spec(mesh16, "attn/wk", (1152, 1, 256))
        assert s == PSpec(None, None, "model")

    def test_stacked_leading_axis_never_sharded(self, mesh16):
        s = self._spec(mesh16, "segments/0/attn/wq", (24, 1024, 8, 128),
                       stacked=True)
        assert s[0] is None
        assert "model" in tuple(s)

    def test_norms_replicate(self, mesh16):
        s = self._spec(mesh16, "ln1", (1024,))
        assert s == PSpec(None)

    def test_experts_shard_over_model(self, mesh16):
        s = self._spec(mesh16, "moe/wi_gate", (64, 2048, 1408))
        assert s == PSpec("model", None, None)

    def test_vocab_shards(self, mesh16):
        s = self._spec(mesh16, "embed", (256000, 2304))
        assert s == PSpec("model", None)

    def test_fsdp_extends_over_data(self, mesh16):
        from repro.distributed.sharding import _extend_fsdp
        base = PSpec("model", None)
        s = _extend_fsdp(base, (256000, 2304), mesh16, stacked=False)
        assert s == PSpec("model", ("data",))


class TestPolicy:
    def _policy(self, arch, shape="train_4k"):
        # policy only reads mesh.shape; fake it
        class FakeMesh:
            shape = {"data": 16, "model": 16}
        from repro.launch.specs import parallelism_policy
        return parallelism_policy(get_arch(arch), SHAPES[shape], FakeMesh())

    def test_tiny_model_dp_only(self):
        assert self._policy("mamba2-130m") == "dp_only"

    def test_mid_model_tp(self):
        assert self._policy("gemma2-2b") == "tp"

    def test_27b_zero1(self):
        assert self._policy("gemma3-27b") == "zero1"

    def test_235b_fsdp(self):
        assert self._policy("qwen3-moe-235b-a22b") == "fsdp"

    def test_dp_only_requires_divisible_batch(self):
        # decode batch 128 is not divisible by 256 chips -> not dp_only
        assert self._policy("mamba2-130m", "decode_32k") in ("tp",)
