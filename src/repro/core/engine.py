"""The compression engine: ONE pluggable top-k selector behind every DGS path.

Every sparsified exchange in this repo — the async-sim strategies
(baselines.py), the parameter server's secondary compression (server.py),
and the mesh collectives (distributed.py) — reduces to the same operator:

    select the top-k |x| support of a tensor (or of each row of a 2-D
    row view), optionally after a SAMomentum velocity accumulate, and
    rescale the unsent remainder so its mass telescopes into the velocity.

This module is the single implementation of that operator (DESIGN.md
§10 Compression-engine).  Three engines share the semantics contract written
down in ``kernels/ref.py``:

* ``exact``     — ``lax.top_k`` over |x|.  The oracle: every other engine
                  is tested against it.  Right answer below ~1M elements.
* ``sampled``   — DGC-style sampled-threshold estimation
                  (``sparsify.sampled_threshold`` + a sort- and
                  scatter-free compaction): estimate the k-th magnitude
                  from a strided subsample, take the passers' running
                  count, search it for the first <= 4k passers in index
                  order (the candidate slots), exact top-k over only
                  those candidates.  No full-width sort ever runs;
                  exact while <= 4k coordinates pass the estimate.
* ``blockwise`` — the Pallas hot path: ``kernels.ops.hierarchical_topk``
                  (per-VMEM-block top-r candidates, no sort, one HBM pass)
                  for selection, ``samomentum_fused`` for the fused
                  accumulate/threshold/rescale pass, ``scatter_apply`` for
                  the support repair.  Exact whenever ``block_r >= k``;
                  with ``block_r < k`` it is the production oversampled
                  approximation.  ``interpret=None`` runs the compiled
                  kernels on a TPU and Pallas interpret mode elsewhere.

``engine="auto"`` dispatches by tensor size: exact below
``sampled_threshold_above`` elements, sampled at or above it — the knob
``ExchangeConfig.sampled_threshold_above`` threads straight into this.

Exactly one SAMomentum rescale implementation exists in the repo and it is
``samomentum_rescale`` below (the Pallas kernel + its ref.py oracle are the
fused-kernel semantics contract, validated against it in tests).

Wire quantization (TernGrad-style, ``sparsify.quantize_dequantize``)
composes uniformly here: the *outgoing* message values are quantized, the
velocity rescale never sees the quantization error (unbiased-wire design —
the selection itself is error-compensated, the quantizer must not be).
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.telemetry.trace import LAYER

from .sparsify import (
    SparseLeaf,
    quantize_dequantize,
    quantize_segments,
    sampled_threshold,
    topk_select,
)


# ---------------------------------------------------------------------------
# spec + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Everything a call site needs to say about how to compress.

    engine:  "exact" | "sampled" | "blockwise" | "auto"
    quantize: wire quantization mode for message VALUES
              ("none" | "bf16" | "int8" | "tern", see sparsify)
    sampled_threshold_above: auto-dispatch size cutoff — tensors with at
              least this many elements use the sampled engine
    sample_size: subsample size for the sampled threshold estimate
    block_r: per-block candidate count for blockwise (None = k, i.e. exact)
    interpret: run Pallas kernels in interpret mode; None = auto
              (True off-TPU)
    """

    engine: str = "auto"
    quantize: str = "none"
    sampled_threshold_above: int = 1 << 20
    sample_size: int = 65536
    block_r: int | None = None
    interpret: bool | None = None

    @property
    def value_bits(self) -> int:
        return {"none": 32, "bf16": 16, "int8": 8, "tern": 2}[self.quantize]


DEFAULT_SPEC = CompressionSpec()
EXACT_SPEC = CompressionSpec(engine="exact")


@runtime_checkable
class SelectionEngine(Protocol):
    """One way of computing a top-k support.

    select(x, k)        flat (n,) -> SparseLeaf of exactly k entries
    select_rows(x2d, k) (S, n)    -> (vals (S, k), idx (S, k) int32, local
                                      per-row indices)
    """

    name: str

    def select(self, x: jax.Array, k: int) -> SparseLeaf: ...

    def select_rows(self, x2d: jax.Array, k: int): ...


ENGINES: dict[str, type] = {}


def register_engine(cls):
    ENGINES[cls.name] = cls
    return cls


def get_engine(name: str, spec: CompressionSpec = DEFAULT_SPEC
               ) -> SelectionEngine:
    """Instantiate a registered engine, configured from ``spec``."""
    try:
        cls = ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; have {sorted(ENGINES)} + 'auto'")
    return cls.from_spec(spec)


def resolve_engine(spec: CompressionSpec, size: int) -> SelectionEngine:
    """Engine instance for a ``size``-element tensor: auto-dispatch.

    This is where ``sampled_threshold_above`` is honoured: under "auto", a
    tensor with >= that many elements routes to the sampled engine (the
    exact sort would dominate step time), everything smaller stays exact.
    """
    name = spec.engine
    if name == "auto":
        name = "sampled" if size >= spec.sampled_threshold_above else "exact"
    return get_engine(name, spec)


# ---------------------------------------------------------------------------
# the three engines
# ---------------------------------------------------------------------------

@register_engine
@dataclasses.dataclass(frozen=True)
class ExactEngine:
    """``lax.top_k`` over |x| — the semantics oracle."""

    name = "exact"

    @classmethod
    def from_spec(cls, spec: CompressionSpec):
        return cls()

    def select(self, x, k):
        return topk_select(x, k)

    def select_rows(self, x2d, k):
        _, idx = jax.lax.top_k(jnp.abs(x2d), k)
        idx = idx.astype(jnp.int32)
        return jnp.take_along_axis(x2d, idx, axis=1), idx


def _first_reaching(csum, cap: int):
    """Column where each row's running count ``csum`` (S, n) first reaches
    1, 2, ..., cap; -1 past the row's total.

    A three-level search, no scatter and no per-element gather: the count
    is cut into rows of 128 values, grouped 128 rows to a block.  Per rank:
    the block is the number of block ends below it (a compare over the
    n / 16384 block ends), the row the number of that block's row ends
    below it, the column the number of that row's values below it (one
    gathered 128-value row each).  A binary search over the count
    (``jnp.searchsorted``) gathers ~log2(n) single values per rank instead:
    3.9 ms against 1.1 ms for one 4.7M-value row on a TPU v5e.
    """
    S, n = csum.shape
    L = 128                                       # one lane row of a TPU
    pad = -n % (L * L)
    c3 = jnp.pad(csum, ((0, 0), (0, pad)), mode="edge").reshape(S, -1, L)
    c2 = c3[:, :, L - 1].reshape(S, -1, L)        # count at each row's end
    ranks = jnp.arange(1, cap + 1, dtype=jnp.int32)
    r = ranks[:, None]
    rows = jax.vmap(lambda c, i: c[i])            # (S, m, L)[(S, cap)]
    # a rank past the row's total overruns the last block; its slot is
    # masked below, whatever the clamped gathers read
    blk = jnp.sum(c2[:, None, :, L - 1] < r, axis=2, dtype=jnp.int32)
    row = blk * L + jnp.sum(rows(c2, blk) < r, axis=2, dtype=jnp.int32)
    col = row * L + jnp.sum(rows(c3, row) < r, axis=2, dtype=jnp.int32)
    return jnp.where(ranks <= csum[:, -1:], col, -1)


def _threshold_compact_rows(x2d, thr, k: int, *, cap_factor: int = 4):
    """Exactly-k selection of threshold passers without a full-width sort.

    This is the point of the sampled threshold: the O(n) work is one
    streaming pass (the passers' running count) that compacts the passers
    into at most ``cap = cap_factor * k`` candidate slots in index order;
    an exact ``top_k`` then runs over only those candidates (k << n sort).
    Slot j holds the column where the running count first reaches j + 1,
    found by searching the count for each slot rank (``_first_reaching``);
    a per-element scatter into the slots costs n colliding updates and
    runs far below the bandwidth.
    The selection is exact whenever at most ``cap`` coordinates pass the
    threshold — the estimator targets ~k passers, so the factor-4 cap
    absorbs estimation error; beyond that, surplus passers are dropped in
    index order (the DGC trade — the dropped mass stays error-compensated
    in the caller's velocity/residual).  Exact zeros never pass (guards
    the degenerate thr == 0 case: a subsample that misses every nonzero
    must not ship zeros while starving the real mass).  If fewer than k
    coordinates pass, the spare slots duplicate the strongest candidate
    with value 0: decode-neutral padding that never fabricates support.

    x2d: (S, n); thr: (S, 1).  Returns (vals (S, k), idx (S, k) int32).
    """
    n = x2d.shape[1]
    mag = jnp.abs(x2d)
    cap = int(min(n, cap_factor * k))
    mask = (mag >= thr) & (mag > 0.0)
    csum = jnp.cumsum(mask, axis=1, dtype=jnp.int32)     # passers in [0, i]
    cidx = _first_reaching(csum, cap)                     # -1: empty slot
    valid_c = cidx >= 0
    cvals = jnp.where(
        valid_c,
        jnp.take_along_axis(x2d, jnp.maximum(cidx, 0), axis=1), 0.0)
    # exact top-k over the <= cap candidates (padding ranks below any real
    # candidate); k <= cap always since k <= n
    _, sel = jax.lax.top_k(jnp.where(valid_c, jnp.abs(cvals), -1.0), k)
    idx = jnp.take_along_axis(cidx, sel, axis=1)
    vals = jnp.take_along_axis(cvals, sel, axis=1)
    invalid = idx < 0
    idx = jnp.where(invalid, jnp.maximum(idx[:, :1], 0), idx)
    vals = jnp.where(invalid, 0.0, vals)
    return vals.astype(x2d.dtype), idx.astype(jnp.int32)


@register_engine
@dataclasses.dataclass(frozen=True)
class SampledEngine:
    """DGC sampled-threshold estimation (Lin et al. 2017).

    The k-th |x| is estimated from a ``sample_size`` strided subsample
    (``sparsify.sampled_threshold``), then the passers are compacted to a
    small candidate set and top-k'd WITHOUT a full-tensor sort or a
    per-element scatter (``_threshold_compact_rows``: a running count of
    the passers, searched for each candidate slot) — exact while at most
    ``4k`` coordinates pass the estimate, index-order truncated beyond
    that; shapes stay static and the per-element work is one streaming
    pass.
    """

    name = "sampled"
    sample_size: int = 65536

    @classmethod
    def from_spec(cls, spec: CompressionSpec):
        return cls(sample_size=spec.sample_size)

    def select(self, x, k):
        flat = x.reshape(-1)
        thr = sampled_threshold(flat, k / flat.shape[0],
                                sample_size=self.sample_size)
        vals, idx = _threshold_compact_rows(flat[None], thr.reshape(1, 1), k)
        return SparseLeaf(values=vals[0], indices=idx[0],
                          size=flat.shape[0])

    def select_rows(self, x2d, k):
        n = x2d.shape[1]
        # one estimator implementation (sparsify.sampled_threshold), vmapped
        # per row so flat and row-wise selections can never drift apart
        thr = jax.vmap(lambda row: sampled_threshold(
            row, k / n, sample_size=self.sample_size))(x2d)
        return _threshold_compact_rows(x2d, thr[:, None], k)


@register_engine
@dataclasses.dataclass(frozen=True)
class BlockwiseEngine:
    """Hierarchical Pallas block selection (kernels/block_topk.py).

    Each 1024-element VMEM block emits its local top-``r`` candidates; a
    cheap top-k over the nb*r candidates finishes the selection.  Exact
    whenever r >= k; ``block_r < k`` is the oversampled production
    approximation (same spirit as the sampled threshold — unsent mass
    stays in the SAMomentum velocity either way).
    """

    name = "blockwise"
    block_r: int | None = None
    interpret: bool | None = None   # None: interpret mode only off-TPU

    @classmethod
    def from_spec(cls, spec: CompressionSpec):
        return cls(block_r=spec.block_r, interpret=spec.interpret)

    def _plan(self, n: int, k: int) -> int | None:
        """Per-block candidate count ``r`` guaranteeing >= k REAL
        candidates, or None when the hierarchy cannot cover k (k close to
        n — degrade to exact; small-tensor selection is cheap anyway)."""
        from repro.kernels.block_topk import BLOCK

        nb_real = -(-n // BLOCK)           # blocks holding real data
        n_last = n - (nb_real - 1) * BLOCK  # real elems in the last block
        r = min(BLOCK, max(1, k if self.block_r is None else self.block_r,
                           -(-k // nb_real)))
        while r < BLOCK and (nb_real - 1) * r + min(r, n_last) < k:
            r = min(BLOCK, r * 2)
        if (nb_real - 1) * r + min(r, n_last) < k:
            return None
        return r

    def select(self, x, k):
        from repro.kernels import ops

        flat = x.reshape(-1)
        n = flat.shape[0]
        r = self._plan(n, k)
        if r is None:
            return topk_select(flat, k)
        vals, idx = ops.hierarchical_topk(
            flat, k=k, r=r, interpret=self.interpret)
        # _plan guarantees >= k real candidates and hierarchical_topk ranks
        # padding strictly below real ones, so idx < n always holds here;
        # the clamp is belt-and-braces for decode safety
        idx = jnp.minimum(idx, n - 1)
        return SparseLeaf(values=vals, indices=idx.astype(jnp.int32), size=n)

    def select_rows(self, x2d, k):
        from repro.kernels import ops
        import functools

        n = x2d.shape[1]
        r = self._plan(n, k)
        if r is None:
            return ExactEngine().select_rows(x2d, k)
        f = functools.partial(ops.hierarchical_topk, k=k, r=r,
                              interpret=self.interpret)
        vals, idx = jax.vmap(f)(x2d)
        return vals, jnp.minimum(idx, n - 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# SAMomentum on top of a selection — THE single rescale implementation
# ---------------------------------------------------------------------------

def velocity_accumulate(u, g, *, momentum: float, lr: float):
    """Paper Eq. (11): u <- m * u + eta * g (dtype follows the velocity)."""
    return momentum * u + lr * g


def samomentum_rescale(uacc, sent_mask, momentum: float):
    """Paper Alg. 3 line 11 — the ONLY SAMomentum rescale in the repo.

    Sent coordinates keep their velocity; unsent are pre-divided by m so
    next step's ``m * u`` decay cancels and the unsent mass telescopes
    (Eq. 13).  ``sent_mask`` must be the support that is ACTUALLY shipped
    (after any bucket overflow), or mass leaks.
    """
    return jnp.where(sent_mask, uacc, uacc / momentum)


def support_mask(indices, size: int):
    """Boolean (size,) mask from a flat index set."""
    return jnp.zeros((size,), bool).at[indices].set(True)


def rows_support_mask(idx, n: int):
    """Boolean (S, n) mask from per-row index sets (S, k)."""
    S = idx.shape[0]
    rows = jnp.arange(S, dtype=jnp.int32)[:, None]
    return jnp.zeros((S, n), bool).at[rows, idx].set(True)


def quantize_leaf(leaf: SparseLeaf, mode: str) -> SparseLeaf:
    """Wire-quantize one message leaf's values (indices untouched)."""
    if mode == "none":
        return leaf
    with jax.named_scope(LAYER.quantize):
        vq, _ = quantize_dequantize(leaf.values, mode)
    return SparseLeaf(values=vq.astype(leaf.values.dtype),
                      indices=leaf.indices, size=leaf.size)


def _maybe_quantize_rows(vals, mode: str):
    if mode == "none":
        return vals
    with jax.named_scope(LAYER.quantize):
        vq, _ = quantize_dequantize(vals, mode)
    return vq.astype(vals.dtype)


def select(x, k: int, spec: CompressionSpec = DEFAULT_SPEC) -> SparseLeaf:
    """Top-k of a flat tensor through the dispatched engine (+ wire
    quantization)."""
    flat = x.reshape(-1)
    eng = resolve_engine(spec, int(flat.shape[0]))
    with jax.named_scope(LAYER.select):
        leaf = eng.select(flat, k)
    return quantize_leaf(leaf, spec.quantize)


def select_rows(x2d, k: int, spec: CompressionSpec = DEFAULT_SPEC):
    """Per-row top-k through the dispatched engine (+ wire quantization).

    Returns (vals (S, k), idx (S, k) int32 local per-row)."""
    eng = resolve_engine(spec, int(x2d.shape[1]))
    with jax.named_scope(LAYER.select):
        vals, idx = eng.select_rows(x2d, k)
    return _maybe_quantize_rows(vals, spec.quantize), idx


def samomentum_step(u, g, *, momentum: float, lr: float, k: int,
                    spec: CompressionSpec = DEFAULT_SPEC):
    """One SAMomentum step on one tensor: accumulate -> select -> rescale.

    Returns (msg: SparseLeaf over the flattened tensor, u_new shaped like
    ``u``).  The message holds the UNquantized support selection of the
    chosen engine with ``spec.quantize`` applied to its values; ``u_new``
    never sees quantization error.
    """
    eng = resolve_engine(spec, int(u.size))
    if isinstance(eng, BlockwiseEngine):
        msg, u_new = _samomentum_step_blockwise(
            u, g, eng, momentum=momentum, lr=lr, k=k)
    else:
        uacc = velocity_accumulate(u, g, momentum=momentum, lr=lr)
        flat = uacc.reshape(-1)
        with jax.named_scope(LAYER.select):
            msg = eng.select(flat, k)
        mask = support_mask(msg.indices, flat.shape[0])
        u_new = samomentum_rescale(flat, mask, momentum).reshape(u.shape)
    return quantize_leaf(msg, spec.quantize), u_new


def _samomentum_step_blockwise(u, g, eng: BlockwiseEngine, *, momentum, lr,
                               k):
    """The Pallas hot path: all three kernels in one step.

    1. ``hierarchical_topk`` picks the support of the accumulated velocity
       (one HBM pass, no sort),
    2. ``samomentum_fused`` re-walks (u, g) once against the k-th candidate
       magnitude, producing the thresholded dense output and the rescaled
       velocity in a single fused pass,
    3. ``scatter_apply`` repairs the (tie / r<k oversampling) coordinates
       that pass the threshold but are not in the shipped support — they
       must be rescaled like any unsent coordinate or their mass is lost.
    """
    from repro.kernels import ops

    uacc = velocity_accumulate(u, g, momentum=momentum, lr=lr)
    with jax.named_scope(LAYER.select):
        msg = eng.select(uacc.reshape(-1), k)
    thr = jnp.min(jnp.abs(msg.values))
    # uacc is already materialized for the selection above, so feed it back
    # through the fused kernel as both operands with (m, 1 - m):
    # m*uacc + (1-m)*uacc == uacc — the kernel skips the redundant
    # re-accumulate of (u, g) and only thresholds + rescales (by the real
    # momentum) in its single pass
    sent_dense, u_new = ops.samomentum_fused(
        uacc, uacc, thr, momentum=momentum, lr=1.0 - momentum,
        interpret=eng.interpret)
    # extra = thresholded-but-not-shipped coordinates (0 on the support)
    extra = ops.scatter_apply(sent_dense.reshape(-1), msg.indices,
                              -msg.values, interpret=eng.interpret)
    u_new = u_new.reshape(-1) + extra * (1.0 / momentum - 1.0)
    return msg, u_new.reshape(u.shape)


def quantize_arena(msg: SparseLeaf, mode: str, seg) -> SparseLeaf:
    """Wire-quantize a global-index arena message SEGMENT-WISE.

    ``seg`` is the per-tensor entry count (``ParamSpace.ks(density)``): each
    original tensor's slice of the concatenated value vector gets its own
    scale, exactly like the old per-leaf messages — so arena and per-leaf
    paths are bit-equal under every quantize mode.
    """
    if mode == "none":
        return msg
    with jax.named_scope(LAYER.quantize):
        values = quantize_segments(msg.values, mode, seg)
    return SparseLeaf(values=values, indices=msg.indices, size=msg.size)


def samomentum_step_arena(u, g, space, *, momentum: float, lr: float,
                          ks, spec: CompressionSpec = DEFAULT_SPEC):
    """SAMomentum over a packed arena: per-tensor steps, one global message.

    ``u``/``g`` are ``(space.total,)`` arenas.  Each leaf view runs the
    SAME :func:`samomentum_step` as the per-leaf path (bit-equal across
    every engine, including the fused blockwise Pallas path); per-leaf
    message indices are rebased by the leaf offset and concatenated into
    one global-index SparseLeaf, and the rescaled velocity views
    concatenate back into one arena.
    """
    vals, idxs, new_u = [], [], []
    for off, k, u_view, g_view in zip(
            space.offsets, ks, space.views(u), space.views(g)):
        msg, u_new = samomentum_step(u_view, g_view, momentum=momentum,
                                     lr=lr, k=k, spec=spec)
        vals.append(msg.values)
        idxs.append(msg.indices + jnp.int32(off))
        new_u.append(u_new.reshape(-1))
    return (SparseLeaf(values=jnp.concatenate(vals),
                       indices=jnp.concatenate(idxs), size=space.total),
            jnp.concatenate(new_u))


def samomentum_step_rows(u2d, g2d, *, momentum: float, lr: float, k: int,
                         spec: CompressionSpec = DEFAULT_SPEC):
    """Row-wise SAMomentum step (the mesh hot path's (S, rest) view).

    Returns (vals (S, k), idx (S, k) int32, u_new (S, rest)).  Callers that
    drop entries after selection (bucket overflow) must rescale with their
    own shipped mask instead — see distributed.py's sharded-PS path.
    """
    uacc = velocity_accumulate(u2d, g2d, momentum=momentum, lr=lr)
    vals, idx = select_rows(uacc, k, spec)
    mask = rows_support_mask(idx, uacc.shape[1])
    return vals, idx, samomentum_rescale(uacc, mask, momentum)
