"""Mesh-distributed DGS exchange: numerical equivalence and end-to-end
training on a multi-device host mesh.

These tests need >1 device, so each runs in a subprocess with
--xla_force_host_platform_device_count set BEFORE jax import (the main
pytest process keeps the default single device, per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8) -> str:
    src = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_mesh_allgather_matches_flat_reference():
    """The mesh allgather exchange (per-worker SAMomentum + sparse gather)
    must aggregate to the same update as a serial per-worker reference."""
    out = _run("""
        from repro.core import distributed as D
        from repro.core.samomentum import leaf_update
        from repro.launch import mesh as mesh_lib

        W = 8
        mesh = mesh_lib.make_mesh((W,), ("data",))
        n = 64
        key = jax.random.PRNGKey(0)
        grads_w = jax.random.normal(key, (W, n))     # per-worker grads
        u0 = jnp.zeros((W, n))
        cfg = D.ExchangeConfig(mode="allgather", density=0.25, momentum=0.5)

        def inner(u, g):
            u = u[0]
            upd, state = D.allgather_exchange(
                D.ExchangeState(velocity=[u], m_shard=[], v_shard=[]),
                [g[0]], cfg=cfg, lr=0.1, axis_names=("data",), n_workers=W)
            return upd[0], state.velocity[0][None]

        upd, u1 = jax.shard_map(
            inner, mesh=mesh, axis_names={"data"},
            in_specs=(P("data"), P("data")), out_specs=(P(), P("data")),
            check_vma=False)(u0, grads_w)
        # serial reference
        k = max(1, round(0.25 * n))
        agg = np.zeros(n)
        for w in range(W):
            msg, _ = leaf_update(jnp.zeros(n), grads_w[w], momentum=0.5,
                                 lr=0.1, k=k)
            np.add.at(agg, np.asarray(msg.indices), np.asarray(msg.values))
        np.testing.assert_allclose(np.asarray(upd), agg / W, atol=1e-5)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_mesh_allgather_auto_engine_dispatch_matches_exact():
    """engine="auto" with sampled_threshold_above=1 forces every leaf
    through the SAMPLED engine; the aggregated update must still match the
    exact engine (the sampled threshold only skips the sort, the selected
    support is identical) — behavioural proof the ExchangeConfig knob is
    respected on the mesh path."""
    out = _run("""
        from repro.core import distributed as D
        from repro.launch import mesh as mesh_lib

        W = 4
        mesh = mesh_lib.make_mesh((W,), ("data",))
        n = 256
        key = jax.random.PRNGKey(7)
        grads_w = jax.random.normal(key, (W, n))
        u0 = jnp.zeros((W, n))

        def run_with(cfg):
            def inner(u, g):
                upd, st = D.allgather_exchange(
                    D.ExchangeState(velocity=[u[0]], m_shard=[], v_shard=[]),
                    [g[0]], cfg=cfg, lr=0.1, axis_names=("data",),
                    n_workers=W)
                return upd[0], st.velocity[0][None]
            return jax.shard_map(
                inner, mesh=mesh, axis_names={"data"},
                in_specs=(P("data"), P("data")), out_specs=(P(), P("data")),
                check_vma=False)(u0, grads_w)

        upd_e, u_e = run_with(D.ExchangeConfig(
            mode="allgather", density=0.1, momentum=0.5, engine="exact"))
        # auto below the cutoff == exact, bit for bit
        upd_a, u_a = run_with(D.ExchangeConfig(
            mode="allgather", density=0.1, momentum=0.5, engine="auto",
            sampled_threshold_above=1 << 30))
        np.testing.assert_array_equal(np.asarray(upd_a), np.asarray(upd_e))
        np.testing.assert_array_equal(np.asarray(u_a), np.asarray(u_e))
        # auto above the cutoff routes through the (approximate, sort-free)
        # sampled engine: still <= W*k shipped slots and most of the exact
        # update's mass recovered
        upd_s, u_s = run_with(D.ExchangeConfig(
            mode="allgather", density=0.1, momentum=0.5, engine="auto",
            sampled_threshold_above=1))
        upd_s = np.asarray(upd_s)
        k = max(1, round(0.1 * n))
        assert np.count_nonzero(upd_s) <= W * k
        mass_s = np.abs(upd_s).sum()
        mass_e = np.abs(np.asarray(upd_e)).sum()
        assert mass_s > 0.5 * mass_e, (mass_s, mass_e)
        assert np.all(np.isfinite(np.asarray(u_s)))
        print("AUTO_DISPATCH_MATCH")
    """, devices=4)
    assert "AUTO_DISPATCH_MATCH" in out


def test_mesh_train_step_loss_decreases():
    """End-to-end: reduced arch trains on a (4 data x 2 model) mesh with the
    sparse exchange and the loss goes down."""
    out = _run("""
        from repro.configs import get_arch
        from repro.configs.shapes import InputShape, input_specs
        from repro.core.distributed import ExchangeConfig
        from repro.data.synthetic import TokenStream
        from repro.launch import mesh as mesh_lib
        from repro.launch.steps import build_train_step, init_exchange_state
        from repro.models import init_params

        cfg = get_arch("chatglm3-6b").reduced()
        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        shape = InputShape("smoke", 64, 8, "train")
        ex_cfg = ExchangeConfig(mode="allgather", density=0.1, momentum=0.9)
        bundle = build_train_step(cfg, mesh, ex_cfg, lr=0.2,
                                  batch_specs_abstract=input_specs(cfg, shape),
                                  remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        ex_state = init_exchange_state(params, ex_cfg, 4)
        stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=64,
                             batch_size=8, seed=0)
        with mesh:
            step = bundle.jit()
            losses = []
            for i in range(30):
                params, ex_state, loss = step(params, ex_state,
                                              stream.batch(i))
                losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses
        print("DECREASED", losses[0], losses[-1])
    """)
    assert "DECREASED" in out


def test_mesh_dense_mode_matches_single_device_msgd():
    """dense exchange on a 4-worker mesh == single-device momentum SGD on
    the concatenated batch (the classic DP equivalence)."""
    out = _run("""
        from repro.configs import get_arch
        from repro.configs.shapes import InputShape, input_specs
        from repro.core.distributed import ExchangeConfig
        from repro.launch import mesh as mesh_lib
        from repro.launch.steps import build_train_step, init_exchange_state
        from repro.models import init_params, loss_fn
        from repro.core.baselines import msgd_step

        cfg = get_arch("musicgen-large").reduced()
        cfg = __import__("dataclasses").replace(cfg, frontend_tokens=0)
        mesh = mesh_lib.make_mesh((4, 1), ("data", "model"))
        shape = InputShape("smoke", 32, 8, "train")
        ex_cfg = ExchangeConfig(mode="dense", momentum=0.7)
        bundle = build_train_step(cfg, mesh, ex_cfg, lr=0.1,
                                  batch_specs_abstract=input_specs(cfg, shape),
                                  remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        # params is donated into step(); keep an independent copy for the
        # serial reference
        ref_params = jax.tree.map(jnp.copy, params)
        ref_vel = jax.tree.map(jnp.zeros_like, params)
        ex_state = init_exchange_state(params, ex_cfg, 4)
        key = jax.random.PRNGKey(1)
        with mesh:
            step = bundle.jit()
            for i in range(3):
                tokens = jax.random.randint(jax.random.fold_in(key, i),
                                            (8, 32), 0, cfg.vocab_size)
                batch = {"tokens": tokens}
                params, ex_state, loss = step(params, ex_state, batch)
                # reference: grad over the same full batch
                g = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(ref_params)
                ref_params, ref_vel = msgd_step(ref_params, ref_vel, g,
                                                lr=0.1, momentum=0.7)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=2e-3)
        print("EQUIV")
    """)
    assert "EQUIV" in out


def test_multipod_mesh_axes():
    out = _run("""
        from repro.launch import mesh as mesh_lib
        m = mesh_lib.make_production_mesh(multi_pod=True)
        assert dict(m.shape) == {"pod": 2, "data": 16, "model": 16}
        assert mesh_lib.data_axis_names(m) == ("pod", "data")
        assert mesh_lib.n_data_workers(m) == 32
        assert mesh_lib.model_axis_size(m) == 16
        m1 = mesh_lib.make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        print("AXES_OK")
    """, devices=512)
    assert "AXES_OK" in out


def test_shardedps_equals_allgather_when_unconstrained():
    """With generous bucket capacity and a dense downward pass, the
    sharded-PS dual-way exchange delivers exactly the same aggregated update
    as the allgather exchange (nothing left in the M - v difference)."""
    out = _run("""
        from repro.core import distributed as D
        from repro.launch import mesh as mesh_lib

        W = 8
        mesh = mesh_lib.make_mesh((W,), ("data",))
        n = 64
        key = jax.random.PRNGKey(0)
        grads_w = jax.random.normal(key, (W, n))
        u0 = jnp.zeros((W, n))
        cfg_ag = D.ExchangeConfig(mode="allgather", density=0.25,
                                  momentum=0.5)
        cfg_sp = D.ExchangeConfig(mode="shardedps", density=0.25,
                                  momentum=0.5, bucket_factor=float(W),
                                  secondary_density=1.0)
        shard = n // W

        def inner_ag(u, g):
            upd, st = D.allgather_exchange(
                D.ExchangeState(velocity=[u[0]], m_shard=[], v_shard=[]),
                [g[0]], cfg=cfg_ag, lr=0.1, axis_names=("data",),
                n_workers=W)
            return upd[0], st.velocity[0][None]

        def inner_sp(u, g, m, v):
            upd, st = D.shardedps_exchange(
                D.ExchangeState(velocity=[u[0]], m_shard=[m[0]],
                                v_shard=[v[0]]),
                [g[0]], cfg=cfg_sp, lr=0.1, axis_names=("data",),
                n_workers=W)
            return (upd[0], st.velocity[0][None], st.m_shard[0][None],
                    st.v_shard[0][None])

        upd_ag, u_ag = jax.shard_map(
            inner_ag, mesh=mesh, axis_names={"data"},
            in_specs=(P("data"), P("data")), out_specs=(P(), P("data")),
            check_vma=False)(u0, grads_w)
        m0 = jnp.zeros((W, shard))
        upd_sp, u_sp, m1, v1 = jax.shard_map(
            inner_sp, mesh=mesh, axis_names={"data"},
            in_specs=(P("data"), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data"), P("data"), P("data")),
            check_vma=False)(u0, grads_w, m0, m0)
        np.testing.assert_allclose(np.asarray(upd_sp), np.asarray(upd_ag),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(u_sp), np.asarray(u_ag),
                                   atol=1e-5)
        # difference fully broadcast: M == v on every shard
        np.testing.assert_allclose(np.asarray(m1), np.asarray(v1), atol=1e-6)
        print("SPMATCH")
    """)
    assert "SPMATCH" in out


@pytest.mark.parametrize("arch", [
    "chatglm3-6b", "gemma3-12b", "zamba2-2.7b", "qwen2-vl-7b", "dbrx-132b",
    "musicgen-large", "mamba2-780m", "command-r-35b", "minicpm3-4b",
    "qwen3-moe-235b-a22b",
])
def test_mesh_serve_step_all_archs(arch):
    """Every reduced architecture's serve_step runs on a (2 data x 2 model)
    host mesh through the production step builder (shardings included)."""
    out = _run(f"""
        import dataclasses
        from repro.configs import get_arch
        from repro.configs.shapes import InputShape, concrete_inputs
        from repro.launch import mesh as mesh_lib
        from repro.launch.steps import build_serve_step
        from repro.models import init_params

        cfg = get_arch({arch!r}).reduced()
        mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
        shape = InputShape("smoke", 64, 4, "decode")
        bundle = build_serve_step(cfg, mesh, shape=shape)
        params = init_params(jax.random.PRNGKey(0), cfg)
        inputs = concrete_inputs(cfg, shape)
        with mesh:
            step = bundle.jit()
            logits, caches = step(params, inputs["caches"],
                                  inputs["token"], inputs["pos"])
        assert logits.shape == (4, 1, cfg.vocab_size), logits.shape
        assert not bool(jnp.any(jnp.isnan(logits)))
        print("SERVE_OK", logits.shape)
    """, devices=4)
    assert "SERVE_OK" in out
