"""Launch-layer units: sharding rules, roofline extraction, shapes."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.core.distributed import rows_view, shardedps_state_size
from repro.launch import roofline
from repro.launch.sharding import param_specs, shard_axis_hints
from repro.models.model import abstract_params


class TestShardingRules:
    def test_dense_projections(self):
        cfg = get_arch("command-r-35b")
        shapes = abstract_params(cfg)
        specs = param_specs(cfg, shapes, 16)
        # stacked unit params get a leading None
        up = specs["units"]["b0"]["mlp"]["up"]["w"]
        assert tuple(up) == (None, None, "model")
        down = specs["units"]["b0"]["mlp"]["down"]["w"]
        assert tuple(down) == (None, "model", None)
        wq = specs["units"]["b0"]["attn"]["wq"]["w"]
        assert tuple(wq) == (None, None, "model")

    def test_kv_heads_guard(self):
        """kv < model_size -> K/V projections replicated (rope-safety)."""
        cfg = get_arch("chatglm3-6b")  # kv=2
        specs = param_specs(cfg, abstract_params(cfg), 16)
        wk = specs["units"]["b0"]["attn"]["wk"]["w"]
        assert tuple(wk) == (None, None, None)
        cfg2 = get_arch("musicgen-large")  # kv=32 >= 16
        specs2 = param_specs(cfg2, abstract_params(cfg2), 16)
        wk2 = specs2["units"]["b0"]["attn"]["wk"]["w"]
        assert tuple(wk2) == (None, None, "model")

    def test_moe_expert_parallel(self):
        cfg = get_arch("dbrx-132b")
        specs = param_specs(cfg, abstract_params(cfg), 16)
        up = specs["units"]["b0"]["moe"]["up"]
        assert tuple(up) == (None, "model", None, None)
        router = specs["units"]["b0"]["moe"]["router"]["w"]
        assert "model" not in tuple(router)

    @pytest.mark.parametrize("arch", sorted(ARCHS))
    def test_hints_match_specs(self, arch):
        cfg = get_arch(arch)
        shapes = abstract_params(cfg)
        hints = shard_axis_hints(cfg, shapes, 16)
        leaves = jax.tree.leaves(shapes)
        assert len(hints) == len(leaves)
        for h, l in zip(hints, leaves):
            if h is not None:
                assert 0 <= h < l.ndim
                assert l.shape[h] % 16 == 0


class TestRowsView:
    def test_flat(self):
        assert rows_view((100,), None) == (1, 100, None)

    def test_sharded_axis(self):
        S, rest, ax = rows_view((64, 128), 1)
        assert (S, rest, ax) == (128, 64, 1)

    def test_folding_large(self):
        # (94 units, 128 experts, 4096, 1536): shard axis 1, rest folded
        S, rest, ax = rows_view((94, 128, 4096, 1536), 1)
        assert S * rest == 94 * 128 * 4096 * 1536
        assert rest <= (1 << 22) or S == 128 * 94 * 4096
        assert shardedps_state_size((94, 128, 4096, 1536), 1, 16) >= \
            94 * 128 * 4096 * 1536 // 16


class TestRoofline:
    HLO = """
  %ag = bf16[32,1024]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[512]{0} all-reduce(%y), to_apply=%sum
  %a2a = (f32[16,64]{1,0}, f32[16,64]{1,0}) all-to-all(%a, %b)
  %cp = bf16[8]{0} collective-permute(%z)
  %ags = bf16[4,4]{1,0} all-gather-start(%w)
"""

    def test_collective_stats(self):
        stats = roofline.collective_stats(self.HLO)
        assert stats["all-gather"]["count"] == 2
        assert stats["all-gather"]["out_bytes"] == 32 * 1024 * 2 + 16 * 2
        assert stats["all-reduce"]["count"] == 1
        assert stats["all-reduce"]["wire_bytes"] == 2.0 * 512 * 4
        assert stats["all-to-all"]["out_bytes"] == 2 * 16 * 64 * 4
        assert stats["collective-permute"]["count"] == 1

    def test_model_flops(self):
        from repro.configs import SHAPES
        cfg = get_arch("chatglm3-6b")
        f_train = roofline.model_flops(cfg, SHAPES["train_4k"])
        assert f_train == pytest.approx(
            6 * cfg.param_count() * 4096 * 256, rel=1e-6)
        f_dec = roofline.model_flops(cfg, SHAPES["decode_32k"])
        assert f_dec == pytest.approx(2 * cfg.param_count() * 128, rel=1e-6)

    def test_moe_active_params(self):
        from repro.configs import SHAPES
        cfg = get_arch("qwen3-moe-235b-a22b")
        f = roofline.model_flops(cfg, SHAPES["train_4k"])
        n_active_implied = f / (6 * 4096 * 256)
        # ~22B active for the 235B model
        assert 1.5e10 < n_active_implied < 3.5e10


class TestTrainLauncher:
    def test_smoke_is_a_real_switch(self):
        from repro.launch import train
        assert train.parse_args([]).smoke is True
        args = train.parse_args(["--no-smoke"])
        assert args.smoke is False and args.devices is None

    def test_host_devices_forced_only_for_cpu(self, monkeypatch):
        from repro.launch import train
        # setenv first, so teardown restores whatever was there before
        monkeypatch.setenv("XLA_FLAGS", "")
        monkeypatch.delenv("XLA_FLAGS")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        train.force_host_devices(None)
        assert "XLA_FLAGS" not in os.environ
        train.force_host_devices(3)
        env = os.environ
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=3"

    def test_compile_cache_defers_to_env(self, monkeypatch, tmp_path):
        from repro.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
