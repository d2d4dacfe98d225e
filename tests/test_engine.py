"""Compression-engine parity: every engine against the pre-refactor oracle,
the SAMomentum telescoping invariant under every engine, auto-dispatch, and
uniform wire quantization (DESIGN.md §Compression-engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.core import engine as E
from repro.core import server as ps
from repro.core.baselines import make_strategy
from repro.core.distributed import ExchangeConfig
from repro.core.engine import CompressionSpec
from repro.core.sparsify import SparseLeaf


def _oracle_leaf_update(u_prev, grad, *, momentum, lr, k):
    """The pre-refactor SAMomentum step (samomentum.leaf_update +
    sparsify.topk_select, verbatim) — the bit-for-bit contract for the
    exact engine."""
    u = momentum * u_prev + lr * grad
    flat = u.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    vals = flat[idx]
    mask = jnp.zeros(flat.shape, dtype=bool).at[idx].set(True)
    u_new = jnp.where(mask, flat, flat / momentum).reshape(u.shape)
    return vals, idx, u_new


class TestExactParity:
    def test_exact_matches_prerefactor_oracle_bitforbit(self):
        key = jax.random.PRNGKey(0)
        for n, k in [(64, 8), (100, 1), (1000, 100), (16, 16)]:
            u = jax.random.normal(jax.random.fold_in(key, n), (n,))
            g = jax.random.normal(jax.random.fold_in(key, n + 1), (n,))
            msg, u1 = E.samomentum_step(
                u, g, momentum=0.7, lr=0.1, k=k,
                spec=CompressionSpec(engine="exact"))
            ov, oi, ou = _oracle_leaf_update(u, g, momentum=0.7, lr=0.1, k=k)
            np.testing.assert_array_equal(np.asarray(msg.values),
                                          np.asarray(ov))
            np.testing.assert_array_equal(np.asarray(msg.indices),
                                          np.asarray(oi))
            np.testing.assert_array_equal(np.asarray(u1), np.asarray(ou))

    def test_select_rows_exact_matches_topk(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 200))
        vals, idx = E.select_rows(x, 11, CompressionSpec(engine="exact"))
        _, ri = jax.lax.top_k(jnp.abs(x), 11)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
        np.testing.assert_array_equal(
            np.asarray(vals),
            np.asarray(jnp.take_along_axis(x, ri, axis=1)))


class TestBlockwise:
    def test_blockwise_exact_when_r_ge_k(self):
        """With block_r >= k every global winner is a block winner, so the
        blockwise support equals the exact support."""
        for n, k in [(512, 16), (3000, 64), (9000, 33)]:
            x = jax.random.normal(jax.random.PRNGKey(n + k), (n,))
            exact = E.select(x, k, CompressionSpec(engine="exact"))
            block = E.select(x, k, CompressionSpec(engine="blockwise"))
            assert set(np.asarray(block.indices).tolist()) == \
                set(np.asarray(exact.indices).tolist())
            np.testing.assert_allclose(
                np.sort(np.asarray(block.values)),
                np.sort(np.asarray(exact.values)), atol=0)

    def test_blockwise_samomentum_matches_exact_when_r_ge_k(self):
        u = jax.random.normal(jax.random.PRNGKey(2), (2000,))
        g = jax.random.normal(jax.random.PRNGKey(3), (2000,))
        msg_b, u_b = E.samomentum_step(
            u, g, momentum=0.6, lr=0.05, k=50,
            spec=CompressionSpec(engine="blockwise"))
        msg_e, u_e = E.samomentum_step(
            u, g, momentum=0.6, lr=0.05, k=50,
            spec=CompressionSpec(engine="exact"))
        assert set(np.asarray(msg_b.indices).tolist()) == \
            set(np.asarray(msg_e.indices).tolist())
        np.testing.assert_allclose(np.asarray(u_b), np.asarray(u_e),
                                   atol=1e-6)

    def test_blockwise_select_rows(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 1500))
        bv, bi = E.select_rows(x, 9, CompressionSpec(engine="blockwise"))
        ev, ei = E.select_rows(x, 9, CompressionSpec(engine="exact"))
        for r in range(3):
            assert set(np.asarray(bi[r]).tolist()) == \
                set(np.asarray(ei[r]).tolist())


@settings(max_examples=10, deadline=None)
@given(st.integers(32, 2048), st.floats(0.3, 0.95), st.integers(0, 2 ** 31))
def test_property_telescoping_invariant_every_engine(n, m, seed):
    """Alg. 3 line 11 under EVERY engine (including the approximate
    blockwise mode): sent coords keep the accumulated velocity, unsent are
    exactly divided by m — so no mass ever leaks out of the velocity.

    This is the invariant that makes Eq. (13) telescope; for blockwise with
    block_r < k it is only true because of the scatter_apply support repair
    (thresholded-but-unshipped coordinates must be rescaled too).
    """
    key = jax.random.PRNGKey(seed)
    u0 = jax.random.normal(key, (n,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    k = max(1, n // 8)
    specs = [
        CompressionSpec(engine="exact"),
        CompressionSpec(engine="sampled", sample_size=64),
        CompressionSpec(engine="blockwise"),
        CompressionSpec(engine="blockwise", block_r=1),  # approximate mode
    ]
    uacc = np.asarray(m * u0 + 0.1 * g, np.float64)
    for spec in specs:
        msg, u1 = E.samomentum_step(u0, g, momentum=m, lr=0.1, k=k,
                                    spec=spec)
        sent = np.zeros(n, bool)
        sent[np.asarray(msg.indices)] = True
        u1 = np.asarray(u1, np.float64)
        np.testing.assert_allclose(
            np.where(sent, u1, u1 * m), uacc, atol=5e-5,
            err_msg=f"engine spec {spec}")
        # and the decoded message carries exactly the accumulated velocity
        # of the sent support (sampled underflow pads with decode-neutral
        # zero-value duplicates, so compare through the scatter-add decode)
        decoded = np.zeros(n, np.float64)
        np.add.at(decoded, np.asarray(msg.indices),
                  np.asarray(msg.values, np.float64))
        np.testing.assert_allclose(
            decoded, np.where(sent, uacc, 0.0), atol=5e-5,
            err_msg=f"engine spec {spec}")


class TestSampledNoStarvation:
    def test_spike_ships_even_when_sample_misses_it(self):
        """Regression: a structurally sparse tensor (e.g. one embedding row
        touched) whose nonzeros the strided subsample misses entirely must
        still ship its mass — exact zeros never pass the thr=0 estimate,
        and candidates are top-k'd by magnitude, never index order."""
        x = jnp.zeros((64,)).at[17].set(5.0)
        leaf = E.select(x, 4, CompressionSpec(engine="sampled",
                                              sample_size=8))
        idx = np.asarray(leaf.indices)
        vals = np.asarray(leaf.values)
        assert 17 in idx.tolist()
        np.testing.assert_allclose(vals[idx == 17][0], 5.0)
        # padding slots are decode-neutral
        np.testing.assert_allclose(vals[idx != 17], 0.0)

    def test_repeated_steps_transmit_the_spike(self):
        """Iterating SAMomentum with engine='sampled' on a gradient the
        subsample never sees must not silently starve the coordinate."""
        spec = CompressionSpec(engine="sampled", sample_size=8)
        u = jnp.zeros((64,))
        shipped = 0.0
        for _ in range(5):
            g = jnp.zeros((64,)).at[17].set(1.0)
            msg, u = E.samomentum_step(u, g, momentum=0.5, lr=1.0, k=4,
                                       spec=spec)
            idx = np.asarray(msg.indices)
            shipped += float(np.asarray(msg.values)[idx == 17].sum())
        assert shipped > 4.0  # ~ lr * sum(g) across steps

    def test_underflow_padding_is_decode_neutral(self):
        """The zero-valued duplicate padding must decode to exactly the
        shipped tensor through BOTH decode paths (accumulating
        sparse_to_dense and the server's .add receive)."""
        from repro.core.sparsify import sparse_to_dense

        x = jnp.zeros((64,)).at[17].set(5.0)
        leaf = E.select(x, 4, CompressionSpec(engine="sampled",
                                              sample_size=8))
        np.testing.assert_allclose(np.asarray(sparse_to_dense(leaf)),
                                   np.asarray(x))

    def test_exact_when_passers_fit_candidate_cap(self):
        """The compaction is exact whenever <= 4k coordinates pass the
        sampled threshold (the common case: the estimator targets ~k)."""
        x = jax.random.normal(jax.random.PRNGKey(11), (4096,))
        sampled = E.select(x, 64, CompressionSpec(engine="sampled"))
        exact = E.select(x, 64, CompressionSpec(engine="exact"))
        # full-tensor sample -> exact threshold -> identical support
        assert set(np.asarray(sampled.indices).tolist()) == \
            set(np.asarray(exact.indices).tolist())


def _np_threshold_compact(x2d, thr, k, cap_factor=4):
    """NumPy oracle of ``engine._threshold_compact_rows``'s documented
    semantics: the first ``cap`` passers in index order (exact zeros never
    pass), a stable exact top-k of them by magnitude, and padding that
    duplicates the strongest candidate with value 0."""
    S, n = x2d.shape
    cap = min(n, cap_factor * k)
    vals = np.zeros((S, k), x2d.dtype)
    idx = np.zeros((S, k), np.int32)
    for s in range(S):
        mag = np.abs(x2d[s])
        cand = np.flatnonzero((mag >= thr[s, 0]) & (mag > 0.0))[:cap]
        chosen = cand[np.argsort(-mag[cand], kind="stable")[:k]]
        m = chosen.size
        idx[s, :m] = chosen
        idx[s, m:] = chosen[0] if m else 0
        vals[s, :m] = x2d[s, chosen]
    return vals, idx


def _thr_for_passers(x2d, count):
    """Per-row threshold that ``count`` magnitudes (plus ties) reach."""
    mag = -np.sort(-np.abs(x2d), axis=1)
    return mag[:, count - 1:count]


class TestThresholdCompaction:
    @pytest.mark.parametrize("n", [1000, 4096, 40000])
    @pytest.mark.parametrize("S", [1, 3])
    @pytest.mark.parametrize("case", [
        "fewer_than_k", "k_to_cap", "beyond_cap", "zero_thr"])
    def test_matches_numpy_oracle_bitforbit(self, case, S, n):
        k = 13
        cap = 4 * k
        rng = np.random.default_rng(n * 10 + S)
        x = rng.normal(size=(S, n)).astype(np.float32)
        if case == "zero_thr":
            # quarter steps (ties in magnitude), ~3 cap nonzeros, the rest
            # exact zeros
            x = np.round(x * 2.0) / 4.0
            x[rng.random((S, n)) > 3 * cap / n] = 0.0
        # strong passers at the ends and starts of the search's 128-value
        # rows and 16384-value blocks
        edges = [p for p in (127, 128, 255, 16383, 16384) if p < n]
        x[:, edges] = 5.0 + np.arange(len(edges), dtype=np.float32)
        if case == "zero_thr":
            thr = np.zeros((S, 1), np.float32)
        else:
            count = {"fewer_than_k": k // 2, "k_to_cap": 3 * k,
                     "beyond_cap": 6 * k}[case]
            thr = _thr_for_passers(x, count)
        passers = ((np.abs(x) >= thr) & (x != 0.0)).sum(axis=1)
        lo, hi = {"fewer_than_k": (1, k - 1), "k_to_cap": (k, cap),
                  "beyond_cap": (cap + 1, n), "zero_thr": (cap + 1, n)}[case]
        assert np.all((passers >= lo) & (passers <= hi)), passers
        if case == "zero_thr":
            assert np.all((x == 0.0).sum(axis=1) > 0)
        vals, idx = E._threshold_compact_rows(jnp.asarray(x),
                                              jnp.asarray(thr), k)
        ov, oi = _np_threshold_compact(x, thr, k)
        np.testing.assert_array_equal(np.asarray(idx), oi)
        np.testing.assert_array_equal(np.asarray(vals), ov)
        assert np.asarray(idx).dtype == np.int32

    @pytest.mark.parametrize("rows", [False, True])
    def test_sampled_selection_lowers_without_scatter(self, rows):
        """The candidate compaction gathers and searches; a per-element
        scatter into the candidate slots (n colliding updates) must not
        come back."""
        n, k = 1 << 16, 64           # n >> cap = 4k
        eng = E.SampledEngine()
        if rows:
            fn, x = eng.select_rows, jnp.zeros((3, n))
        else:
            fn, x = eng.select, jnp.zeros((n,))
        text = jax.jit(fn, static_argnums=1).lower(x, k).as_text()
        assert "scatter" not in text


class TestAutoDispatch:
    def test_auto_respects_sampled_threshold_above(self):
        spec = CompressionSpec(engine="auto", sampled_threshold_above=1000)
        assert E.resolve_engine(spec, 999).name == "exact"
        assert E.resolve_engine(spec, 1000).name == "sampled"
        assert E.resolve_engine(spec, 1 << 30).name == "sampled"

    def test_pinned_engine_ignores_threshold(self):
        spec = CompressionSpec(engine="exact", sampled_threshold_above=1)
        assert E.resolve_engine(spec, 1 << 30).name == "exact"

    def test_exchange_config_threads_the_knob(self):
        """The once-dead ExchangeConfig.sampled_threshold_above now drives
        the auto dispatch of every mesh selection."""
        cfg = ExchangeConfig(engine="auto", sampled_threshold_above=128)
        spec = cfg.spec()
        assert spec.sampled_threshold_above == 128
        assert E.resolve_engine(spec, 127).name == "exact"
        assert E.resolve_engine(spec, 128).name == "sampled"

    def test_unknown_engine_raises(self):
        with pytest.raises(ValueError, match="unknown engine"):
            E.get_engine("nope")
        with pytest.raises(ValueError, match="unknown engine"):
            E.select(jnp.ones((8,)), 2, CompressionSpec(engine="nope"))


class TestPluggability:
    def test_registered_custom_engine_is_usable_everywhere(self):
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class FirstK:
            """Degenerate selector: always ships coordinates 0..k-1."""
            name = "first_k"

            @classmethod
            def from_spec(cls, spec):
                return cls()

            def select(self, x, k):
                idx = jnp.arange(k, dtype=jnp.int32)
                return SparseLeaf(values=x[:k], indices=idx,
                                  size=x.shape[0])

            def select_rows(self, x2d, k):
                idx = jnp.broadcast_to(
                    jnp.arange(k, dtype=jnp.int32), (x2d.shape[0], k))
                return x2d[:, :k], idx

        E.register_engine(FirstK)
        try:
            spec = CompressionSpec(engine="first_k")
            msg, u1 = E.samomentum_step(
                jnp.zeros((10,)), jnp.arange(10.0), momentum=0.5, lr=1.0,
                k=3, spec=spec)
            np.testing.assert_array_equal(np.asarray(msg.indices), [0, 1, 2])
            # unsent coords rescaled by 1/m, sent kept
            np.testing.assert_allclose(np.asarray(u1)[3:],
                                       np.arange(3.0, 10.0) / 0.5)
        finally:
            del E.ENGINES["first_k"]


class TestUniformQuantization:
    def test_engine_level_tern_quantization(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (256,))
        leaf = E.select(x, 16, CompressionSpec(engine="exact",
                                               quantize="tern"))
        mags = np.unique(np.abs(np.asarray(leaf.values)))
        assert mags.size == 1  # sign * shared scale

    def test_non_dgs_strategies_quantize_too(self):
        """Quantization used to be DGS-only; it now composes with every
        sparse strategy through the engine layer."""
        params = {"w": jnp.zeros((32,))}
        grads = {"w": jax.random.normal(jax.random.PRNGKey(6), (32,))}
        for name in ("gd_async", "dgc_async", "dgs_plain"):
            s = make_strategy(name, density=0.25, quantize="int8")
            assert s.value_bits == 8
            st_, msg = s.step(s.init(params), grads, lr=0.1)
            assert isinstance(msg, SparseLeaf) and msg.k == 8

    def test_tern_scale_ignores_padding_zeros(self):
        """The shared tern magnitude is computed over nonzero entries only:
        the sampled engine's zero-valued padding must not dilute it."""
        x = jnp.zeros((64,)).at[17].set(5.0)
        leaf = E.select(x, 4, CompressionSpec(engine="sampled",
                                              sample_size=8,
                                              quantize="tern"))
        vals = np.asarray(leaf.values)
        nz = vals != 0.0
        np.testing.assert_allclose(vals[nz], 5.0)   # undiluted magnitude
        assert nz.sum() == 1

    def test_quantization_not_fed_back_into_velocity(self):
        """TernGrad-style unbiased wire: u_new must be computed from the
        UNquantized velocity, message values from the quantized one."""
        u = jax.random.normal(jax.random.PRNGKey(7), (64,))
        g = jax.random.normal(jax.random.PRNGKey(8), (64,))
        msg_q, u_q = E.samomentum_step(
            u, g, momentum=0.7, lr=0.1, k=8,
            spec=CompressionSpec(engine="exact", quantize="tern"))
        msg_f, u_f = E.samomentum_step(
            u, g, momentum=0.7, lr=0.1, k=8,
            spec=CompressionSpec(engine="exact"))
        np.testing.assert_array_equal(np.asarray(u_q), np.asarray(u_f))
        assert not np.array_equal(np.asarray(msg_q.values),
                                  np.asarray(msg_f.values))


class TestServerSecondaryCompression:
    def test_send_through_sampled_engine_is_thresholded(self):
        """Secondary compression through the sampled engine ships exactly k
        slots whose (nonzero) values all pass the sampled threshold, and
        the difference-tracking remainder conserves the unshipped mass."""
        from repro.core.sparsify import sampled_threshold

        params0 = {"w": jnp.zeros((64,))}
        state = ps.init(params0, n_workers=1)
        rng = np.random.default_rng(3)
        msg = SparseLeaf(jnp.asarray(rng.normal(size=8), jnp.float32),
                         jnp.asarray(rng.choice(64, 8, replace=False),
                                     jnp.int32), 64)
        state = ps.receive(state, msg)
        diff = np.asarray(state.M - state.v[0])
        _, G = ps.send(state, 0, secondary_density=0.1,
                       spec=CompressionSpec(engine="sampled",
                                            sample_size=16))
        leaf = G
        assert leaf.k == 6  # density_to_k(64, 0.1)
        thr = float(sampled_threshold(jnp.asarray(diff), 0.1,
                                      sample_size=16))
        vals = np.asarray(leaf.values)
        assert np.all((vals == 0.0) | (np.abs(vals) >= thr))
        # shipped values are the true diff values at their indices
        nz = vals != 0.0
        np.testing.assert_allclose(vals[nz],
                                   diff[np.asarray(leaf.indices)[nz]],
                                   atol=1e-6)


class TestStrategiesAcrossEngines:
    @pytest.mark.parametrize("engine", ["exact", "sampled", "blockwise"])
    def test_dgs_step_runs_and_ships_k(self, engine):
        from repro.core.paramspace import ParamSpace

        params = {"w": jnp.zeros((300,)), "b": jnp.zeros((40,))}
        grads = jax.tree.map(
            lambda p: jax.random.normal(jax.random.PRNGKey(9), p.shape),
            params)
        s = make_strategy("dgs", density=0.1, engine=engine)
        st_, msg = s.step(s.init(params), grads, lr=0.1)
        space = ParamSpace.from_tree(params)
        seg = s.message_seg(space)
        assert sorted(seg) == [4, 30]
        assert msg.k == 34 and msg.size == space.total
        parts = space.split(msg, seg)
        assert sorted(p.k for p in parts) == [4, 30]
