"""Sharding-rule unit tests (pure logic, no multi-device init) plus one
subprocess integration test that lowers a sharded train step on 8 forced
host devices (the dry-run covers the full 512-device matrix)."""

import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import MeshConfig, get_arch
from repro.sharding.cache_specs import kv_cache_layout
from repro.sharding.rules import param_partition_spec

MESH = MeshConfig()          # 16 x 16
MESH_MP = MeshConfig(multi_pod=True)


class TestParamSpecs:
    def test_attention_tp(self):
        assert param_partition_spec("layers/attn/wq", (40, 4096, 4096), MESH) == P(None, None, "model")
        assert param_partition_spec("layers/attn/wo", (40, 4096, 4096), MESH) == P(None, "model", None)

    def test_kv_not_divisible_replicates(self):
        # glm4 kv_dim = 256 -> divisible; a 40-wide kv would not be
        assert param_partition_spec("layers/attn/wk", (40, 4096, 40), MESH) == P(None, None, None)

    def test_moe_expert_parallel(self):
        spec = param_partition_spec("layers/moe/w1", (48, 128, 2048, 768), MESH)
        assert spec == P(None, "model", None, None)

    def test_quantized_leaves_inherit(self):
        assert param_partition_spec("layers/attn/wq/q", (40, 4096, 4096), MESH) == P(None, None, "model")
        assert param_partition_spec("layers/attn/wq/s", (40, 4096), MESH)[-1] == "model"

    def test_fsdp_shards_largest_free_dim(self):
        spec = param_partition_spec(
            "layers/mlp/w1", (88, 6144, 24576), MESH, fsdp=True
        )
        assert spec == P(None, "data", "model")

    def test_dp_preset_pure_fsdp(self):
        spec = param_partition_spec(
            "layers/mlp/w1", (38, 2048, 8192), MESH, preset="dp"
        )
        # largest divisible dim sharded over ("data","model") = 256
        assert spec == P(None, None, ("data", "model"))

    def test_small_params_replicated(self):
        assert param_partition_spec("final_w", (4096,), MESH, fsdp=True) == P(None)


class TestKVCacheLayout:
    def test_kv_divisible_uses_model(self):
        cfg = get_arch("qwen2-moe-a2.7b")     # kv = 16
        lay = kv_cache_layout(cfg, MESH, batch=128, length=32768)
        assert lay["cache_kv"] == "model"
        assert lay["cache_batch"] == "data"
        assert lay["kv_seq"] is None

    def test_kv_not_divisible_shards_seq_on_model(self):
        cfg = get_arch("glm4-9b")             # kv = 2
        lay = kv_cache_layout(cfg, MESH, batch=128, length=32768)
        assert lay["cache_kv"] is None
        assert lay["kv_seq"] == "model"

    def test_batch1_long_context_seq_parallel(self):
        cfg = get_arch("h2o-danube-1.8b")     # kv = 8, window 4096
        lay = kv_cache_layout(cfg, MESH, batch=1, length=4096, seq_shard=True)
        assert lay["cache_batch"] is None
        assert lay["kv_seq"] == ("data", "model")

    def test_multipod_batch_axes(self):
        cfg = get_arch("qwen2-moe-a2.7b")
        lay = kv_cache_layout(cfg, MESH_MP, batch=128, length=32768)
        assert lay["cache_batch"] == ("pod", "data")


@pytest.mark.slow
def test_sharded_lowering_subprocess():
    """Own process so the forced device count can't leak into other tests."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, "src")
        import jax
        from repro.config import MeshConfig, RunConfig, ShapeConfig, get_smoke
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_for_shape
        mesh_cfg = MeshConfig(multi_pod=True, pods=2, data=2, model=2)
        mesh = make_mesh(mesh_cfg)
        shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="train")
        run = RunConfig(model=get_smoke("glm4-9b"), shape=shape, mesh=mesh_cfg)
        with mesh:
            compiled = build_for_shape(run, mesh).fn.lower(
                *build_for_shape(run, mesh).arg_specs
            ).compile()
        print("LOWER_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, cwd=".",
    )
    assert "LOWER_OK" in out.stdout, out.stderr[-2000:]
