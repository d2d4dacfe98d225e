"""Pallas kernel validation: interpret=True vs the pure-jnp ref.py oracles,
swept over shapes and dtypes (as required per kernel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(100,), (4096,), (333, 7), (8, 1024), (2, 3, 1000)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_samomentum_fused_sweep(shape, dtype):
    key = jax.random.PRNGKey(hash(shape) % 2 ** 31)
    u = jax.random.normal(key, shape).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape).astype(dtype)
    thr = jnp.float32(0.5)
    out, unew = ops.samomentum_fused(u, g, thr, momentum=0.7, lr=0.1)
    r_out, r_unew, _ = ref.samomentum_ref(u, g, thr, momentum=0.7, lr=0.1)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    # elements exactly at the threshold may flip selection depending on FMA
    # ordering — exclude the boundary (measure-zero) set from comparison
    uacc = 0.7 * np.asarray(u, np.float32) + 0.1 * np.asarray(g, np.float32)
    interior = np.abs(np.abs(uacc) - 0.5) > 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32)[interior],
                               np.asarray(r_out, np.float32)[interior],
                               atol=tol)
    np.testing.assert_allclose(np.asarray(unew, np.float32)[interior],
                               np.asarray(r_unew, np.float32)[interior],
                               atol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("r", [1, 4, 16])
def test_block_topk_contract_sweep(shape, r):
    key = jax.random.PRNGKey((hash(shape) + r) % 2 ** 31)
    x = jax.random.normal(key, shape)
    cv, ci = ops.block_topk_candidates(x, r=r)
    rv, ri = ref.block_topk_ref(x, block=1024, r=r)
    nb = rv.shape[0]
    np.testing.assert_allclose(np.asarray(cv[:nb]), np.asarray(rv),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ci[:nb]), np.asarray(ri))


@pytest.mark.parametrize("n,k", [(512, 16), (3000, 64), (8192, 128)])
def test_hierarchical_topk_exact_when_r_ge_k(n, k):
    x = jax.random.normal(jax.random.PRNGKey(n + k), (n,))
    v, i = ops.hierarchical_topk(x, k=k)  # r defaults to k -> exact
    rv, _ = jax.lax.top_k(jnp.abs(x), k)
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(v)))[::-1],
                               np.asarray(rv), atol=1e-6)
    # indices point at the right values
    flat = np.asarray(x)
    for vi, ii in zip(np.asarray(v), np.asarray(i)):
        assert flat[ii] == vi


def test_hierarchical_topk_approx_quality():
    """Oversampled approximate mode recovers >=80% of true top-k mass on
    gaussian data."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1 << 16,))
    k = 655  # 1%
    v, _ = ops.hierarchical_topk(x, k=k, r=32)  # 64 blocks * 32 = 2048 cands
    true_mass = float(jnp.sum(jax.lax.top_k(jnp.abs(x), k)[0]))
    got_mass = float(jnp.sum(jnp.abs(v)))
    assert got_mass > 0.8 * true_mass


@settings(max_examples=15, deadline=None)
@given(st.integers(10, 5000), st.floats(0.1, 0.95), st.integers(0, 2 ** 31))
def test_property_samomentum_kernel_vs_oracle(n, m, seed):
    key = jax.random.PRNGKey(seed)
    u = jax.random.normal(key, (n,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    thr = jnp.float32(abs(float(jax.random.normal(
        jax.random.fold_in(key, 2), ()))))
    out, unew = ops.samomentum_fused(u, g, thr, momentum=m, lr=0.05)
    r_out, r_unew, _ = ref.samomentum_ref(u, g, thr, momentum=m, lr=0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r_out), atol=1e-5)
    np.testing.assert_allclose(np.asarray(unew), np.asarray(r_unew),
                               atol=1e-5)


def test_scatter_accumulate_ref_duplicates():
    dense = jnp.zeros((8,))
    idx = jnp.asarray([1, 1, 3], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 4.0])
    out = ref.scatter_accumulate_ref(dense, idx, vals)
    np.testing.assert_allclose(out, [0, 3, 0, 4, 0, 0, 0, 0])


@pytest.mark.parametrize("n,k", [(1000, 10), (5000, 200), (8192, 64)])
def test_scatter_apply_sweep(n, k):
    key = jax.random.PRNGKey(n + k)
    dense = jax.random.normal(key, (n,))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (k,), 0,
                             n).astype(jnp.int32)
    vals = jax.random.normal(jax.random.fold_in(key, 2), (k,))
    out = ops.scatter_apply(dense, idx, vals)
    exp = ref.scatter_accumulate_ref(dense, idx, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_scatter_apply_duplicates_and_cap():
    dense = jnp.zeros((4096,))
    idx = jnp.asarray([5, 5, 5, 5, 2100], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 4.0, 7.0])
    out = ops.scatter_apply(dense, idx, vals, cap=2)  # cap forces spill path
    exp = ref.scatter_accumulate_ref(dense, idx, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6)


# ------------------------------------------------ multi-row scatter (rows)

@pytest.mark.parametrize("n,n_rows,k", [(5000, 3, 40), (2048, 4, 64),
                                        (700, 2, 13)])
def test_scatter_add_rows_matches_row_loop(n, n_rows, k):
    """One fused multi-row scatter == any serial order of per-row
    scatters (disjoint rows), bit for bit — the batched commit contract."""
    rng = np.random.default_rng(n + k)
    dense = jnp.asarray(rng.normal(size=(n_rows + 2, n)).astype(np.float32))
    rows = jnp.asarray(rng.permutation(n_rows + 2)[:n_rows].astype(np.int32))
    idx = jnp.asarray(rng.integers(0, n, (n_rows, k)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(n_rows, k)).astype(np.float32))
    out = ops.scatter_add_rows(dense, rows, idx, vals)
    expect = dense
    for b in range(n_rows):
        expect = ops.scatter_add_row(expect, rows[b], idx[b], vals[b])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_scatter_apply_rows_interpret_matches_xla():
    """The blocked Pallas rows kernel (interpret mode) against the plain
    XLA scatter, duplicates included."""
    rng = np.random.default_rng(7)
    n_rows, n, k = 3, 5000, 120
    dense = jnp.asarray(rng.normal(size=(n_rows, n)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, (n_rows, k)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(n_rows, k)).astype(np.float32))
    out = ops.scatter_apply_rows(dense, idx, vals, interpret=True)
    expect = jnp.stack([dense[b].at[idx[b]].add(vals[b])
                        for b in range(n_rows)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-6)


def test_scatter_apply_rows_cap_spill():
    """cap smaller than the densest block: overflow updates must still be
    applied exactly (via the XLA spill), not dropped."""
    n_rows, n = 2, 4096
    idx = jnp.asarray(np.stack([np.full(32, 5, np.int32),
                                np.full(32, 4000, np.int32)]))
    vals = jnp.ones((n_rows, 32), jnp.float32)
    dense = jnp.zeros((n_rows, n), jnp.float32)
    out = ops.scatter_apply_rows(dense, idx, vals, cap=4, interpret=True)
    expect = jnp.stack([dense[b].at[idx[b]].add(vals[b])
                        for b in range(n_rows)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-6)


def test_scatter_apply_default_cap_spills_exactly():
    """More updates in one block than the kernel's bounded bucket
    (SCATTER_CAP): the surplus spills to the XLA scatter, and the result
    still equals the plain scatter bit for bit (unique indices)."""
    from repro.kernels.scatter_apply import BLOCK
    n = 3 * BLOCK + 77
    k = ops.SCATTER_CAP * 2 + 9
    rng = np.random.default_rng(3)
    idx = np.concatenate([rng.choice(BLOCK, k - 9, replace=False),
                          rng.choice(np.arange(2 * BLOCK, n), 9,
                                     replace=False)]).astype(np.int32)
    dense = jnp.asarray(rng.normal(size=n).astype(np.float32))
    vals = jnp.asarray(rng.normal(size=k).astype(np.float32))
    out = ops.scatter_apply(dense, jnp.asarray(idx), vals, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(dense.at[idx].add(vals)))


def test_scatter_apply_keeps_negative_zero():
    """Lanes a block's updates do not hit keep their exact bits (the
    kernel selects, it never adds 0.0 to them: -0.0 stays -0.0)."""
    dense = jnp.full((300,), -0.0, jnp.float32)
    out = ops.scatter_apply(dense, jnp.asarray([7], jnp.int32),
                            jnp.asarray([1.5], jnp.float32), interpret=True)
    assert np.signbit(np.asarray(out)[[0, 8, 299]]).all()
    assert float(out[7]) == 1.5


def test_block_topk_full_block_sweep():
    """r == BLOCK: one loop of BLOCK selection sweeps sorts every block
    by magnitude, matching the reference exactly."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2 * 1024 + 300,))
    cv, ci = ops.block_topk_candidates(x, r=1024, interpret=True)
    rv, ri = ref.block_topk_ref(x, block=1024, r=1024)
    nb = rv.shape[0]
    np.testing.assert_array_equal(np.asarray(cv[:nb]), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(ci[:nb]), np.asarray(ri))


def test_interpret_default_follows_backend():
    from repro.kernels import resolve_interpret
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
