"""Top-k gradient sparsification primitives (static-shape, jit-safe).

The paper selects "the top (100-R)% of |v|" per parameter tensor (Algorithm 1
line 8: ``thr <- R% of |v[j]|``).  XLA requires static shapes, so we express
the same operator as a static ``k = max(1, round(density * size))`` per tensor
and exchange fixed-size ``(values, indices)`` pairs — the static-shape COO of
DESIGN.md §3.

Two selection primitives live here and are composed into the pluggable
engines of ``core/engine.py`` (DESIGN.md §10 Compression-engine) — call sites
should go through the engine layer rather than these directly:

* ``topk_select`` — exact ``lax.top_k`` over |x| (the ``exact`` engine and
  the reference oracles).
* ``sampled_threshold`` — DGC-style sampled threshold estimation (the
  ``sampled`` engine's estimator) for very large tensors, where an exact
  top-k of a 100M-element gradient would dominate step time.  The live
  selection against the estimate is ``engine._threshold_compact_rows``
  (sort- and scatter-free compaction: a search of the passers' running
  count for each candidate slot, then a candidate top-k);
  ``threshold_select`` here is the magnitude-keyed *reference* selector
  for threshold-based selection (full-width keyed top_k, support provably
  identical to exact top-k) kept as the semantics oracle it is tested
  against.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class SparseLeaf(NamedTuple):
    """Fixed-size sparse representation of one flattened tensor."""

    values: jax.Array   # (k,) same dtype as source
    indices: jax.Array  # (k,) int32 into the flattened tensor
    size: int           # static: number of elements in the dense tensor

    @property
    def k(self) -> int:
        return self.values.shape[-1]


def density_to_k(size: int, density: float) -> int:
    """Static number of kept elements for a tensor of ``size`` elements."""
    if not (0.0 < density <= 1.0):
        raise ValueError(f"density must be in (0, 1], got {density}")
    return max(1, min(size, int(round(size * density))))


def topk_select(x: jax.Array, k: int) -> SparseLeaf:
    """Exact top-k by magnitude over the flattened tensor."""
    flat = x.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    return SparseLeaf(values=flat[idx], indices=idx, size=flat.shape[0])


def topk_threshold(x: jax.Array, k: int) -> jax.Array:
    """The k-th largest |x| (elements with |x| >= thr are the top-k)."""
    vals = jax.lax.top_k(jnp.abs(x.reshape(-1)), k)[0]
    return vals[-1]


def topk_mask(x: jax.Array, k: int) -> jax.Array:
    """Boolean mask selecting exactly the top-k |x| positions (ties broken by
    index order, matching ``lax.top_k``)."""
    flat = x.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    mask = jnp.zeros(flat.shape, dtype=bool).at[idx].set(True)
    return mask.reshape(x.shape)


def sparse_to_dense(leaf: SparseLeaf) -> jax.Array:
    """Decode a SparseLeaf back into a flat dense vector (scatter).

    Duplicate indices ACCUMULATE (matching the server's receive path): the
    sampled engine pads underfull messages with zero-valued duplicates of
    an already-shipped index, which must decode as a no-op — a ``.set``
    scatter would nondeterministically overwrite the real value.
    """
    out = jnp.zeros((leaf.size,), dtype=leaf.values.dtype)
    return out.at[leaf.indices].add(leaf.values)


def sparse_accumulate(dense_flat: jax.Array, leaf: SparseLeaf) -> jax.Array:
    """dense += decode(leaf) without materialising the decode."""
    return dense_flat.at[leaf.indices].add(leaf.values)


def sampled_threshold(
    x: jax.Array,
    density: float,
    *,
    sample_size: int = 65536,
    key: jax.Array | None = None,
) -> jax.Array:
    """Estimate the top-``density`` magnitude threshold from a subsample.

    Deep Gradient Compression (Lin et al. 2017) samples 0.1–1% of the tensor,
    takes the top-k of the sample, and uses that as the threshold for the full
    tensor.  We use a strided deterministic sample by default (reproducible
    under jit without threading PRNG keys through the optimizer), or a uniform
    random sample when ``key`` is given.
    """
    flat = jnp.abs(x.reshape(-1))
    n = flat.shape[0]
    s = min(sample_size, n)
    if key is None:
        # ceil stride: the sample spans the WHOLE tensor (a floor stride
        # truncates coverage to the first s*stride elements whenever n/s is
        # fractional), at the cost of ceil(n/stride) <= s actual samples
        stride = -(-n // s)
        sample = flat[::stride]
    else:
        idx = jax.random.randint(key, (s,), 0, n)
        sample = flat[idx]
    ks = max(1, int(round(sample.shape[0] * density)))
    return jax.lax.top_k(sample, ks)[0][-1]


def threshold_select(x: jax.Array, thr: jax.Array, k: int) -> SparseLeaf:
    """Select up to k elements with |x| >= thr, padded/truncated to exactly k.

    Selection is done with a single ``top_k`` over a *keyed* magnitude so that
    above-threshold elements always beat below-threshold ones; the result is
    exactly the top-k by magnitude whenever >= k elements pass the threshold,
    and otherwise the passing elements padded with the next-largest ones.
    (Identical support to exact top-k; the threshold only exists so callers
    can skip the full-tensor sort on TPU — see kernels/block_topk.py.)
    """
    flat = x.reshape(-1)
    mag = jnp.abs(flat)
    keyed = jnp.where(mag >= thr, mag + 1.0, mag)  # lift passing elems
    _, idx = jax.lax.top_k(keyed, k)
    idx = idx.astype(jnp.int32)
    return SparseLeaf(values=flat[idx], indices=idx, size=flat.shape[0])


# ---------------------------------------------------------------------------
# Pytree helpers: the paper loops "for j = 0..J" over parameter tensors.
# ---------------------------------------------------------------------------

def tree_ks(tree, density: float) -> list[int]:
    """Static per-leaf k for a pytree (order = jax.tree.leaves order)."""
    return [density_to_k(int(l.size), density) for l in jax.tree.leaves(tree)]


def tree_sparsify(tree, density: float):
    """Per-leaf exact top-k sparsification.

    Returns (messages, residual_tree): messages is a list of SparseLeaf (one
    per leaf, leaves order), residual_tree keeps the unsent mass (Algorithm 1
    lines 10-11).
    """
    leaves, treedef = jax.tree.flatten(tree)
    msgs, residuals = [], []
    for leaf in leaves:
        k = density_to_k(int(leaf.size), density)
        flat = leaf.reshape(-1)
        msg = topk_select(flat, k)
        resid = flat.at[msg.indices].set(0.0).reshape(leaf.shape)
        msgs.append(msg)
        residuals.append(resid)
    return msgs, jax.tree.unflatten(treedef, residuals)


def tree_desparsify(msgs, tree_like):
    """Decode a list of SparseLeaf back into a dense pytree shaped like
    ``tree_like``."""
    leaves, treedef = jax.tree.flatten(tree_like)
    dense = [
        sparse_to_dense(m).reshape(l.shape).astype(l.dtype)
        for m, l in zip(msgs, leaves)
    ]
    return jax.tree.unflatten(treedef, dense)


def message_bytes(msgs, *, index_bytes: int = 4) -> int:
    """Nominal wire size of a sparse message (values + indices).

    Accepts one arena SparseLeaf or a list of per-leaf messages.  This is
    the analytic f32+int accounting used by microbenches; the cluster
    codec's measured framing lives in ``repro.cluster.wire``.
    """
    if isinstance(msgs, SparseLeaf):
        msgs = [msgs]
    total = 0
    for m in msgs:
        total += m.values.size * m.values.dtype.itemsize
        total += m.indices.size * index_bytes
    return total


def dense_bytes(tree) -> int:
    return sum(int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Wire quantization of sparse values — the paper's stated future work
# ("the combination of DGS and other compression approaches (e.g. TernGrad)
# can be considered", §Conclusion).  Quantization composes with DGS because
# the unsent mass still lives in the SAMomentum velocity: quantization error
# on sent values is NOT fed back (matching TernGrad's unbiased design), but
# the selection itself is error-compensated by construction.
# ---------------------------------------------------------------------------

QUANTIZE_BITS = {"none": 32, "bf16": 16, "int8": 8, "tern": 2}


@partial(jax.jit, static_argnames=("mode",))
def quantize_parts(values: jax.Array, mode: str):
    """(codes, scale, dequantized) — THE quantization arithmetic.

    The single implementation behind both :func:`quantize_dequantize`
    (every engine/strategy path) and the cluster wire codec's encoder
    (``cluster/wire.py`` ships ``codes``+``scale``, the receiver decodes to
    exactly ``dequantized``).  One jitted program means the simulator and a
    real cluster run quantize bit-identically.

    modes:
      none  — float32 passthrough (32 bits); codes == values
      bf16  — bfloat16 wire (16); codes are the bf16 values
      int8  — symmetric per-message int8 (8 + one f32 scale per message)
      tern  — TernGrad-style {-1, 0, +1} * mean|v| (2 bits + one scale);
              with top-k inputs the 0 level is unused, so this is
              effectively 1-bit sign + shared magnitude.
    """
    values = values.astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    if mode == "none":
        return values, zero, values
    if mode == "bf16":
        b = values.astype(jnp.bfloat16)
        return b, zero, b.astype(jnp.float32)
    if mode == "int8":
        scale = jnp.max(jnp.abs(values)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(values / scale), -127, 127)
        return q.astype(jnp.int8), scale.astype(jnp.float32), \
            (q * scale).astype(jnp.float32)
    if mode == "tern":
        # scale over NONZERO entries only: exact zeros are either genuine
        # (nothing to ship) or the sampled engine's decode-neutral padding,
        # and averaging them in would dilute the shared magnitude of every
        # real value with no error compensation; sign(0) keeps them 0
        nnz = jnp.maximum(jnp.sum(values != 0.0), 1)
        scale = jnp.sum(jnp.abs(values)) / nnz
        s = jnp.sign(values)
        return s.astype(jnp.int8), scale.astype(jnp.float32), \
            (s * scale).astype(jnp.float32)
    raise ValueError(f"unknown quantization mode {mode!r}")


def quantize_dequantize(values: jax.Array, mode: str):
    """Quantize sparse message values for the wire; returns (dequantized
    values, bits per value).  See :func:`quantize_parts` for the modes."""
    return quantize_parts(values, mode)[2], QUANTIZE_BITS[mode]


def quantize_segments(values: jax.Array, mode: str, seg) -> jax.Array:
    """Segment-wise wire quantization of a concatenated value vector.

    ``seg`` is the static per-segment length tuple (one segment per original
    parameter tensor of an arena message).  Each segment is quantized
    INDEPENDENTLY through the same jitted :func:`quantize_parts` program the
    codec's encoder uses — one scale per tensor, exactly like the per-leaf
    message path, so arena messages are bit-equal to per-leaf ones.
    """
    if mode == "none":
        return values
    if len(seg) == 1:
        return quantize_parts(values, mode)[2]
    parts, off = [], 0
    for s in seg:
        parts.append(quantize_parts(
            jax.lax.slice_in_dim(values, off, off + s), mode)[2])
        off += s
    return jnp.concatenate(parts)
