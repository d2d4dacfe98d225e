"""Fused wire quantize+pack — the codec's value pipeline as one program.

The legacy ARENA encoder (cluster/wire.py) looped python-side over the
message's per-tensor segments: one jitted ``quantize_parts`` call per
segment plus one host transfer per segment for codes and one for the
scale.  This module fuses the whole value pipeline — per-segment scales,
wire codes, the bit-packed value block, the dequantized ("shipped")
values, and the size-narrowed index block — into ONE jitted program per
``(mode, seg, size)`` specialization, so ``wire.pack_from_arena`` makes a
constant ~3 host transfers per message regardless of how many tensors the
arena message spans.  The message values can be (and in the batched
runtime are) views into the flat parameter arena: nothing here copies
them before the program runs.

Scale arithmetic, codes and dequantized values are
``sparsify.quantize_parts`` VERBATIM (the same jitted sub-program per
segment), which is what makes the packed frames bit-equal to the legacy
per-segment encoder, on every backend.  The one Pallas kernel packs tern
sign codes, four 2-bit two's-complement codes per byte, little-end first
(the codec's ``_pack_tern`` order): the wrapper splits the codes into four
``(m, LANE)`` int32 planes and the kernel ORs them into ``(m, LANE)``
bytes on whole tiles.  bf16 and int8 need no kernel: their codes are one
XLA convert or round/clip away from ``quantize_parts``.

Off-TPU the tern packer is the identical-arithmetic XLA op (interpret-mode
Pallas would serialize the grid loop in Python — the repo-wide pitfall);
the Pallas path compiles on TPU and is exercised in tests via
``interpret=True``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sparsify import quantize_parts

LANE = 128
ROWS = 8       # f32 tile rows per grid step


# ---------------------------------------------------------------------------
# Pallas tern packer (TPU fast path; interpret=True in tests)
# ---------------------------------------------------------------------------

def _tern_pack_kernel(c_ref, o_ref):
    # (4, ROWS, LANE) sign-code planes -> (ROWS, LANE) bytes (as int32):
    # plane q holds code 4t+q of byte t, packed as the q-th little-end
    # 2-bit two's-complement field
    u = c_ref[...] & 3
    o_ref[...] = u[0] | (u[1] << 2) | (u[2] << 4) | (u[3] << 6)


def _pack_tern_pallas(codes, interpret: bool):
    """int32 sign codes (k,) -> (ceil(k/4),) packed bytes via the kernel.

    The wrapper splits the codes into four planes (plane q = codes
    q, q+4, q+8, ...), so the kernel packs with elementwise ops on whole
    (ROWS, LANE) tiles and never reshapes inside."""
    k = codes.shape[0]
    pad = (-k) % (4 * ROWS * LANE)
    if pad:
        codes = jnp.pad(codes, (0, pad))
    planes = codes.reshape(-1, 4).T.reshape(4, -1, LANE)
    m = planes.shape[1]
    packed = pl.pallas_call(
        _tern_pack_kernel, grid=(m // ROWS,),
        in_specs=[pl.BlockSpec((4, ROWS, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANE), jnp.int32),
        interpret=interpret)(planes)
    return packed.reshape(-1)[: (k + 3) // 4].astype(jnp.uint8)


# ---------------------------------------------------------------------------
# XLA fallback (bit-identical arithmetic; the off-TPU default)
# ---------------------------------------------------------------------------

def _pack_tern_xla(codes):
    k = codes.shape[0]
    u = (codes.astype(jnp.int32) & 3).astype(jnp.uint8)
    pad = (-k) % 4
    if pad:
        u = jnp.pad(u, (0, pad))
    u4 = u.reshape(-1, 4)
    return (u4[:, 0] | (u4[:, 1] << 2) | (u4[:, 2] << 4)
            | (u4[:, 3] << 6)).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mode", "seg", "pallas", "interpret"))
def quantize_pack(values, *, mode: str, seg: tuple,
                  pallas: bool | None = None, interpret: bool = False):
    """One fused program: ``(wire_codes, scales, shipped)`` for a message.

    ``values`` is the concatenated (k,) value vector of an arena message,
    ``seg`` its static per-tensor segmentation (sum == k; each segment
    quantizes with its OWN scale, matching the ARENA frame contract).

    Returns:
      * ``wire_codes`` — the value block exactly as serialized: f32 (none),
        uint16 bf16 bit patterns, int8 codes, or uint8 2-bit-packed tern
        bytes (``ceil(k/4)``, codec ``_pack_tern`` order).
      * ``scales``     — (n_seg,) f32 per-tensor scales (zeros for
        none/bf16, which ship no scales).
      * ``shipped``    — (k,) f32 dequantized values: bit-for-bit what the
        decoder on the far side reconstructs (== ``quantize_segments``).

    ``pallas=None`` routes the tern packer by backend (the Pallas kernel on
    TPU, plain XLA elsewhere — same convention as ``ops.scatter_add``);
    tests force the kernel with ``pallas=True, interpret=True``.  bf16 and
    int8 take the XLA ops everywhere.
    """
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    values = values.astype(jnp.float32)
    k = values.shape[0]
    assert sum(seg) == k, (seg, k)
    if mode == "none":
        return values, jnp.zeros((len(seg),), jnp.float32), values

    # quantize_parts verbatim, segment by segment (the reduction order
    # must match the legacy encoder exactly)
    parts, off = [], 0
    for s in seg:
        parts.append(quantize_parts(
            jax.lax.slice_in_dim(values, off, off + s), mode))
        off += s
    scales = jnp.stack([p[1] for p in parts])

    codes = (parts[0][0] if len(parts) == 1
             else jnp.concatenate([p[0] for p in parts]))
    dq = (parts[0][2] if len(parts) == 1
          else jnp.concatenate([p[2] for p in parts]))
    if mode == "bf16":
        codes = jax.lax.bitcast_convert_type(codes, jnp.uint16)
    elif mode == "tern":
        codes = (_pack_tern_pallas(codes.astype(jnp.int32),
                                   interpret=interpret)
                 if pallas else _pack_tern_xla(codes))
    return codes, scales, dq


@partial(jax.jit, static_argnames=("size",))
def narrow_indices(indices, *, size: int):
    """Size-derived index narrowing, on device (u8 / u16 / u32 — the same
    rule as ``wire.index_dtype``, so the bytes match ``np.astype``)."""
    if size <= 1 << 8:
        return indices.astype(jnp.uint8)
    if size <= 1 << 16:
        return indices.astype(jnp.uint16)
    return indices.astype(jnp.uint32)
