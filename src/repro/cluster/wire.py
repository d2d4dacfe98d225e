"""Packed binary wire codec for the federated cluster runtime.

Every message between a client and the coordinator is one *envelope*
followed by zero or more length-prefixed *frames*:

    envelope:   u8  type      HELLO/WELCOME/UP/DOWN/SKIP/BYE
                u32 sender    client id (coordinator = 0xFFFFFFFF)
                u32 seq       per-sender sequence number (control types
                              reuse this field: HELLO = proposed id,
                              WELCOME = assigned worker slot)
                f32 aux       UP: the worker's scalar loss; else 0
                u32 n_leaves

    frame:      u32 frame_len (bytes after this field)
                u16 leaf_id   (ARENA frames reuse this field as n_seg)
                u8  mode      value packing: 0 none / 1 bf16 / 2 int8 / 3 tern
                u8  kind      0 sparse COO / 1 dense f32 / 2 dense-as-COO /
                              3 ARENA (global-index COO over the packed
                              parameter arena, segmented per tensor)
                u32 k         number of entries carried
                u32 size      dense length of the leaf / arena
                [f32 scale]   kind 0, int8/tern only: the per-message scale
                uN * k        indices (kinds 0, 2, 3); N derived from
                              ``size`` — u8 when size <= 256, u16 when
                              size <= 65536, u32 beyond — so the decoder
                              needs no extra field
                values        none: f32*k | bf16: u16*k | int8: i8*k
                              tern: 2-bit codes, 4 per byte
                              dense f32 (kind 1): f32*size, no indices

    ARENA body (kind 3) carries, between the header and the index block:
                u32 * n_seg   per-tensor entry counts (the segmentation)
                f32 * n_seg   int8/tern only: one scale PER TENSOR

The arena frame is how the flat-parameter-arena runtime (DESIGN.md §8)
ships a whole model's sparse update as ONE frame: one header, one index
block whose width derives from the arena ``size``, one value block.
Quantization is segment-wise — one scale per original tensor, exactly like
the old per-leaf frames — so arena messages are bit-equal to per-leaf ones
and the decoder never needs the model structure (it reads the seg table).

All integers little-endian.  Dense leaves always travel as f32 (quantizing
the model-difference would break the server's ``v_k == M`` invariant, Eq. 4);
the codec picks whichever of kind 1/2 is smaller for the actual nnz.

Quantization semantics are *exactly* ``sparsify.quantize_dequantize``:
``decode(encode(values, mode))`` reproduces ``quantize_dequantize(values,
mode)[0]`` bit-for-bit (tests/test_wire.py).  The same jitted quantizer is
exposed as :func:`quantize_message` and used by ``core.async_sim`` so the
simulator's arithmetic — and therefore its losses — is bit-identical to a
cluster run over this codec.

:func:`frame_bytes` computes the serialized size of a message from its
structure alone; it is definitionally equal to ``len(encode_message(...))``
and replaces the old analytic byte accounting everywhere.
"""
from __future__ import annotations

import struct
from typing import NamedTuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro.core.sparsify import (SparseLeaf, quantize_parts as
                                 _quantize_parts, quantize_segments)
from repro.telemetry.trace import LAYER

# message types
HELLO, WELCOME, UP, DOWN, SKIP, BYE = range(6)
# subscriber leg (DESIGN.md §13): an inference replica SUBscribes to the
# coordinator, PULLs one coalesced re-sparsified model-diff per decode
# boundary, and SYNCs (full accumulated update, dense) for the bit-exact
# final handshake.  Every subscriber-bound reply is a DIFF frame whose
# ``seq`` field carries the server version (committed event count) the
# diff brings the replica to, and whose ``aux`` field is 1.0 once
# training has quiesced (the replica's cue to SYNC and leave).
SUB, PULL, SYNC, DIFF = 6, 7, 8, 9
TYPE_NAMES = {HELLO: "HELLO", WELCOME: "WELCOME", UP: "UP", DOWN: "DOWN",
              SKIP: "SKIP", BYE: "BYE", SUB: "SUB", PULL: "PULL",
              SYNC: "SYNC", DIFF: "DIFF"}
COORDINATOR_ID = 0xFFFFFFFF

# inference replicas address themselves from a reserved id range so the
# coordinator can recognize (and selectively drain) subscriber traffic
# without disturbing the schedule-driven selective receive of training
# clients — client ids are small ints, shard coordinators sit just under
# COORDINATOR_ID, and 2**30 collides with neither.
SUBSCRIBER_BASE = 1 << 30


def is_subscriber(addr: int) -> bool:
    """True when ``addr`` is in the reserved inference-replica id range."""
    return SUBSCRIBER_BASE <= addr < COORDINATOR_ID - (1 << 16)

# value packing modes (wire codes)
MODES = {"none": 0, "bf16": 1, "int8": 2, "tern": 3}
MODE_NAMES = {v: k for k, v in MODES.items()}

# leaf kinds
SPARSE, DENSE, DENSE_COO, ARENA = 0, 1, 2, 3

_ENVELOPE = struct.Struct("<BIIfI")     # 17 bytes
_LEN = struct.Struct("<I")              # 4-byte leaf frame length prefix
_HEADER = struct.Struct("<HBBII")       # 12-byte leaf header
_SCALE = struct.Struct("<f")

ENVELOPE_BYTES = _ENVELOPE.size


class Message(NamedTuple):
    type: int
    sender: int
    seq: int
    aux: float
    leaves: list  # [SparseLeaf | flat f32 jax array], leaf_id order


# ---------------------------------------------------------------------------
# quantization — sparsify.quantize_parts is the single implementation; the
# codec ships its (codes, scale) and async_sim applies its dequantized
# values, so both sides of the parity contract share one XLA program
# ---------------------------------------------------------------------------

def quantize_message(msg, mode: str, seg=None):
    """Apply wire quantization to a message — what the decoder on the far
    side will reconstruct; async_sim and the scan runner call it in place
    of a real encode/decode round trip (it is pure jax, so it also runs
    in-graph inside ``lax.scan``).

    ``msg`` is one arena leaf (global-index SparseLeaf or dense flat
    array); ``seg`` gives the per-tensor segmentation of a sparse arena
    message — each segment quantizes with its own scale, matching the
    ARENA frame encoder bit-for-bit (defaults to one segment).  Dense
    leaves pass through untouched (they travel f32, see module doc).
    A legacy list of per-leaf messages quantizes leaf-wise.
    """
    if isinstance(msg, (list, tuple)) and not isinstance(msg, SparseLeaf):
        return [quantize_message(m, mode) for m in msg]
    if mode == "none" or not isinstance(msg, SparseLeaf):
        return msg
    if seg is None:
        seg = (msg.k,)
    with jax.named_scope(LAYER.quantize):
        values = quantize_segments(msg.values, mode, seg)
    return SparseLeaf(values=values, indices=msg.indices, size=msg.size)


# ---------------------------------------------------------------------------
# size accounting — matches serialization by construction
# ---------------------------------------------------------------------------

def _value_nbytes(k: int, mode: str) -> int:
    return {"none": 4 * k, "bf16": 2 * k, "int8": k,
            "tern": (k + 3) // 4}[mode]


def index_dtype(size: int):
    """Narrowest unsigned index type for a ``size``-element leaf — derived
    from the header's ``size`` field, so it costs no wire bytes."""
    if size <= 1 << 8:
        return np.uint8
    if size <= 1 << 16:
        return np.uint16
    return np.uint32


def _index_nbytes(size: int) -> int:
    return np.dtype(index_dtype(size)).itemsize


def leaf_frame_bytes(k: int, size: int, mode: str, kind: int = SPARSE) -> int:
    """Serialized bytes of one leaf frame, length prefix included."""
    n = _LEN.size + _HEADER.size
    if kind == DENSE:
        return n + 4 * size
    if kind == DENSE_COO:
        return n + (4 + _index_nbytes(size)) * k
    if mode in ("int8", "tern"):
        n += _SCALE.size
    return n + _index_nbytes(size) * k + _value_nbytes(k, mode)


def arena_frame_bytes(seg, size: int, mode: str = "none") -> int:
    """Serialized bytes of one ARENA frame (length prefix included) — a
    pure function of the static ``(seg, size, mode)`` triple."""
    k = sum(seg)
    n = _LEN.size + _HEADER.size + 4 * len(seg)     # header + seg table
    if mode in ("int8", "tern"):
        n += 4 * len(seg)                           # one scale per tensor
    return n + _index_nbytes(size) * k + _value_nbytes(k, mode)


def frame_bytes_static(seg, size: int, mode: str = "none") -> int:
    """Per-event wire bytes of a sparse arena message (envelope included).

    Static per ``(mode, seg, size)`` — memoize once per run instead of
    re-deriving frame sizes from on-device message structure every event.
    """
    return _ENVELOPE.size + arena_frame_bytes(seg, size, mode)


def shard_frame_bytes_static(shard_spec, seg, mode: str = "none"):
    """Per-shard static wire bytes of one sharded sparse arena message.

    Shard ``s`` ships its own ARENA frame over the ``sizes[s]``-element
    sub-arena: its slice of the seg table, indices rebased shard-local
    (and therefore possibly NARROWER — ``index_dtype`` derives from the
    shard size, not the global arena size), its tensors' scales.  The
    tuple is a pure function of ``(shard_spec, seg, mode)``; its sum is
    the sharded run's exact per-event up/down byte cost (each shard pays
    its own envelope + header — the only bytes an S-shard run adds over
    the single-server frame).
    """
    return tuple(
        frame_bytes_static(shard_spec.shard_seg(seg, s), size, mode)
        for s, size in enumerate(shard_spec.sizes))


def encode_sharded_message(msg_type: int, sender: int, seq: int, msg, *,
                           shard_spec, mode: str = "none", seg=None,
                           aux: float = 0.0):
    """Route one arena message as ``S`` shard-local frames (DESIGN.md §12).

    The message splits by index range (``ShardSpec.split_by_shard`` —
    indices rebased ``global - bounds[s]``, seg table sliced per shard)
    and each piece encodes as its own complete message so coordinator
    shard ``s`` decodes ONLY its range, with per-tensor quantization
    scales identical to the unsharded frame (leaf-aligned shards keep
    whole tensors, so each segment's scale is computed over the same
    values).  Returns ``[(payload, shipped_piece), ...]`` in shard order;
    ``ShardSpec.merge`` of the shipped pieces is bit-equal to the
    single-frame ``encode_message`` shipped leaf.
    """
    out = []
    for piece, sub_seg in shard_spec.split_by_shard(msg, seg):
        out.append(encode_message(msg_type, sender, seq, [piece],
                                  mode=mode, seg=sub_seg, aux=aux))
    return out


def dense_frame_bytes(nnz, size: int):
    """Frame bytes of a dense f32 leaf with ``nnz`` nonzeros — the codec
    picks the cheaper of DENSE / DENSE_COO.  Works elementwise on numpy
    arrays of nnz (the scan runner's vectorized accounting)."""
    coo = (4 + _index_nbytes(size)) * nnz
    body = np.where(coo < 4 * size, coo, 4 * size)
    return _LEN.size + _HEADER.size + body


def _dense_kind(nnz: int, size: int) -> int:
    """COO when (idx, value) pairs beat the dense f32 vector."""
    return (DENSE_COO
            if (4 + _index_nbytes(size)) * nnz < 4 * size else DENSE)


def frame_bytes(msgs, *, mode: str = "none", seg=None,
                envelope: bool = True) -> int:
    """Wire size of a message — equal to ``len(encode_message(...))``.

    Accepts one arena leaf or a legacy list of per-leaf messages.  ``seg``
    marks a SparseLeaf as an ARENA frame with that segmentation; without
    it the legacy per-leaf SPARSE framing is counted.  Headers, per-tensor
    scales, and the bit-packed value widths are all counted exactly as
    serialized.
    """
    if isinstance(msgs, SparseLeaf) or not isinstance(msgs, (list, tuple)):
        msgs = [msgs]
    total = _ENVELOPE.size if envelope else 0
    for m in msgs:
        if isinstance(m, SparseLeaf):
            if seg is not None:
                total += arena_frame_bytes(seg, int(m.size), mode)
            else:
                total += leaf_frame_bytes(m.k, m.size, mode, SPARSE)
        else:
            # count on-device: only the scalar nnz crosses to the host
            total += int(dense_frame_bytes(int(jnp.count_nonzero(m)),
                                           int(m.size)))
    return total


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------

def _pack_tern(codes: np.ndarray) -> bytes:
    """{-1, 0, +1} int8 -> 2-bit codes (two's complement), 4 per byte."""
    u = (codes.astype(np.int8) & 3).astype(np.uint8)
    pad = (-len(u)) % 4
    if pad:
        u = np.concatenate([u, np.zeros(pad, np.uint8)])
    u = u.reshape(-1, 4)
    return (u[:, 0] | (u[:, 1] << 2) | (u[:, 2] << 4)
            | (u[:, 3] << 6)).astype(np.uint8).tobytes()


def _unpack_tern(buf: bytes, k: int) -> np.ndarray:
    b = np.frombuffer(buf, np.uint8)
    u = np.empty((len(b), 4), np.uint8)
    for j in range(4):
        u[:, j] = (b >> (2 * j)) & 3
    codes = u.reshape(-1)[:k].astype(np.int8)
    codes[codes == 3] = -1
    return codes


def _pack_values(codes, mode: str) -> bytes:
    if mode == "none":
        return np.asarray(codes, np.float32).tobytes()
    if mode == "bf16":
        return np.asarray(codes).view(np.uint16).tobytes()
    if mode == "int8":
        return np.asarray(codes).tobytes()
    return _pack_tern(np.asarray(codes))  # tern


def encode_arena_leaf_segments(leaf: SparseLeaf, mode: str, seg):
    """Reference ARENA encoder: the original python-side segment loop.

    One jitted ``quantize_parts`` dispatch plus two host transfers PER
    SEGMENT — kept as the semantics oracle :func:`pack_from_arena` is
    tested bit-equal against (tests/test_wire.py), and as the simplest
    statement of the frame layout.  Returns ``(frame_bytes, shipped)``.
    """
    seg = tuple(int(s) for s in seg)
    k, size = int(leaf.k), int(leaf.size)
    assert sum(seg) == k, (seg, k)
    if not seg:   # an empty shard's frame: header only (k == 0)
        body = _HEADER.pack(0, MODES[mode], ARENA, 0, size)
        return _LEN.pack(len(body)) + body, leaf
    idx = np.asarray(leaf.indices).astype(index_dtype(size))
    codes, scales, dq = [], [], []
    off = 0
    for s in seg:
        c, sc, d = _quantize_parts(leaf.values[off:off + s], mode)
        codes.append(np.asarray(c))
        scales.append(float(sc))
        dq.append(d)
        off += s
    body = _HEADER.pack(len(seg), MODES[mode], ARENA, k, size)
    body += np.asarray(seg, np.uint32).tobytes()
    if mode in ("int8", "tern"):
        body += np.asarray(scales, np.float32).tobytes()
    body += idx.tobytes() + _pack_values(np.concatenate(codes), mode)
    # the dequantized segments ARE quantize_segments(values, mode, seg) —
    # same jitted program per slice — so `shipped` costs no second pass
    shipped = SparseLeaf(
        values=dq[0] if len(dq) == 1 else jnp.concatenate(dq),
        indices=leaf.indices, size=size)
    return _LEN.pack(len(body)) + body, shipped


def pack_from_arena(leaf: SparseLeaf, mode: str, seg):
    """Fused zero-copy ARENA encode (kernels/wire_pack.py).

    ONE jitted program quantizes every segment with its own scale and
    emits the bit-packed wire value block, the per-tensor scale vector,
    and the dequantized shipped values; a second tiny program narrows the
    indices on device.  The leaf's values/indices can be views straight
    off the flat parameter arena (nothing copies before the program
    runs), and exactly three buffers cross to the host per message —
    codes, scales, indices — instead of two per segment.  Bit-equal to
    :func:`encode_arena_leaf_segments`, byte for byte.  On TPU the value
    packing runs as Pallas kernels; elsewhere as the identical XLA ops.
    Returns ``(frame_bytes, shipped_leaf)``.
    """
    from repro.kernels import wire_pack
    seg = tuple(int(s) for s in seg)
    k, size = int(leaf.k), int(leaf.size)
    assert sum(seg) == k, (seg, k)
    if not seg:   # an empty shard's frame: header only (k == 0)
        body = _HEADER.pack(0, MODES[mode], ARENA, 0, size)
        return _LEN.pack(len(body)) + body, leaf
    codes, scales, dq = wire_pack.quantize_pack(
        leaf.values, mode=mode, seg=seg)
    idx = wire_pack.narrow_indices(leaf.indices, size=size)
    body = _HEADER.pack(len(seg), MODES[mode], ARENA, k, size)
    body += np.asarray(seg, np.uint32).tobytes()
    if mode in ("int8", "tern"):
        body += np.asarray(scales).tobytes()
    body += np.asarray(idx).tobytes() + np.asarray(codes).tobytes()
    shipped = SparseLeaf(values=dq, indices=leaf.indices, size=size)
    return _LEN.pack(len(body)) + body, shipped


def encode_arena_leaf(leaf: SparseLeaf, mode: str, seg):
    """Serialize one global-index arena message as an ARENA frame.

    ``seg`` is the static per-tensor entry count tuple (sum == leaf.k).
    Each segment's values quantize with their OWN scale through the same
    quantization arithmetic as ``quantize_message`` — so ``shipped``
    (what the decoder reconstructs) is bit-equal to the in-process
    stand-in.  Routed through the fused :func:`pack_from_arena` path.
    Returns ``(frame_bytes, shipped_leaf)``.
    """
    return pack_from_arena(leaf, mode, seg)


def encode_leaf(leaf_id: int, leaf, mode: str = "none", seg=None):
    """Serialize one leaf; returns ``(frame_bytes, shipped_leaf)``.

    ``shipped_leaf`` is exactly what :func:`decode_leaf` on the far side
    reconstructs (the dequantized SparseLeaf, or the dense array verbatim)
    — callers use it to keep local state consistent with the receiver.
    A SparseLeaf with ``seg`` travels as a segmented ARENA frame; without
    it, as a legacy per-leaf SPARSE frame.
    """
    if isinstance(leaf, SparseLeaf) and seg is not None:
        return encode_arena_leaf(leaf, mode, seg)
    if isinstance(leaf, SparseLeaf):
        codes, scale, dq = _quantize_parts(leaf.values, mode)
        k, size = leaf.k, leaf.size
        idx = np.asarray(leaf.indices).astype(index_dtype(size))
        if mode == "none":
            vals = np.asarray(codes, np.float32).tobytes()
        elif mode == "bf16":
            vals = np.asarray(codes).view(np.uint16).tobytes()
        elif mode == "int8":
            vals = np.asarray(codes).tobytes()
        else:  # tern
            vals = _pack_tern(np.asarray(codes))
        body = _HEADER.pack(leaf_id, MODES[mode], SPARSE, k, size)
        if mode in ("int8", "tern"):
            body += _SCALE.pack(float(scale))
        body += idx.tobytes() + vals
        shipped = SparseLeaf(values=dq, indices=leaf.indices, size=size)
        return _LEN.pack(len(body)) + body, shipped

    flat = np.asarray(leaf, np.float32).reshape(-1)
    nz = np.flatnonzero(flat)
    kind = _dense_kind(len(nz), flat.size)
    if kind == DENSE:
        body = _HEADER.pack(leaf_id, MODES["none"], DENSE,
                            flat.size, flat.size) + flat.tobytes()
    else:
        body = (_HEADER.pack(leaf_id, MODES["none"], DENSE_COO,
                             len(nz), flat.size)
                + nz.astype(index_dtype(flat.size)).tobytes()
                + flat[nz].tobytes())
    return _LEN.pack(len(body)) + body, leaf


def encode_message(msg_type: int, sender: int, seq: int, msgs=(),
                   *, mode: str = "none", seg=None, aux: float = 0.0):
    """Serialize a full message; returns ``(payload, shipped_msgs)``.

    ``msgs`` is the leaf list (the arena runtime ships exactly one leaf:
    the global-index arena message); ``seg`` routes SparseLeaf leaves
    through the segmented ARENA framing.
    """
    if isinstance(msgs, SparseLeaf) or not isinstance(msgs, (list, tuple)):
        msgs = [msgs]
    if seg is not None and sum(isinstance(m, SparseLeaf) for m in msgs) > 1:
        # the ARENA header reuses the leaf_id field as n_seg, so an arena
        # frame cannot carry a leaf id — a message holds at most ONE
        # (decode would collapse several onto leaves[0])
        raise ValueError("arena (seg=) messages carry exactly one "
                         f"SparseLeaf; got {len(msgs)} leaves")
    frames, shipped = [], []
    for i, m in enumerate(msgs):
        frame, s = encode_leaf(i, m, mode, seg)
        frames.append(frame)
        shipped.append(s)
    payload = _ENVELOPE.pack(msg_type, sender, seq, aux, len(frames))
    return payload + b"".join(frames), shipped


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_leaf(buf, offset: int = 0):
    """Decode one leaf frame; returns ``(leaf_id, leaf, next_offset)``."""
    (blen,) = _LEN.unpack_from(buf, offset)
    offset += _LEN.size
    end = offset + blen
    leaf_id, mode_c, kind, k, size = _HEADER.unpack_from(buf, offset)
    offset += _HEADER.size
    mode = MODE_NAMES[mode_c]

    idt = index_dtype(size)
    if kind == ARENA:
        n_seg = leaf_id  # ARENA frames reuse the leaf_id field as n_seg
        seg = np.frombuffer(buf, np.uint32, n_seg, offset)
        offset += seg.nbytes
        scales = None
        if mode in ("int8", "tern"):
            scales = np.frombuffer(buf, np.float32, n_seg, offset)
            offset += scales.nbytes
        idx = np.frombuffer(buf, idt, k, offset).astype(np.int32)
        offset += k * np.dtype(idt).itemsize
        if mode == "none":
            vals = np.frombuffer(buf, np.float32, k, offset).copy()
        elif mode == "bf16":
            vals = np.frombuffer(buf, np.uint16, k, offset) \
                .view(ml_dtypes.bfloat16).astype(np.float32)
        else:
            if mode == "int8":
                codes = np.frombuffer(buf, np.int8, k, offset)
            else:  # tern
                codes = _unpack_tern(bytes(buf[offset:end]), k)
            vals = np.empty(k, np.float32)
            off = 0
            for s, sc in zip(seg, scales):
                # same IEEE op per segment as the jitted `codes * scale`
                vals[off:off + s] = codes[off:off + s].astype(np.float32) \
                    * sc
                off += s
        return 0, SparseLeaf(values=jnp.asarray(vals),
                             indices=jnp.asarray(idx), size=size), end
    if kind == DENSE:
        flat = np.frombuffer(buf, np.float32, size, offset).copy()
        return leaf_id, jnp.asarray(flat), end
    if kind == DENSE_COO:
        idx = np.frombuffer(buf, idt, k, offset)
        offset += idx.nbytes
        vals = np.frombuffer(buf, np.float32, k, offset)
        flat = np.zeros(size, np.float32)
        flat[idx] = vals
        return leaf_id, jnp.asarray(flat), end

    scale = np.float32(0.0)
    if mode in ("int8", "tern"):
        (scale,) = _SCALE.unpack_from(buf, offset)
        scale = np.float32(scale)
        offset += _SCALE.size
    idx = np.frombuffer(buf, idt, k, offset).astype(np.int32)
    offset += k * np.dtype(idt).itemsize
    if mode == "none":
        vals = np.frombuffer(buf, np.float32, k, offset).copy()
    elif mode == "bf16":
        vals = np.frombuffer(buf, np.uint16, k, offset) \
            .view(ml_dtypes.bfloat16).astype(np.float32)
    elif mode == "int8":
        vals = np.frombuffer(buf, np.int8, k, offset).astype(np.float32) \
            * scale
    else:  # tern
        codes = _unpack_tern(bytes(buf[offset:end]), k)
        vals = codes.astype(np.float32) * scale
    return leaf_id, SparseLeaf(values=jnp.asarray(vals),
                               indices=jnp.asarray(idx), size=size), end


def decode_message(payload) -> Message:
    buf = memoryview(payload)
    msg_type, sender, seq, aux, n_leaves = _ENVELOPE.unpack_from(buf, 0)
    offset = _ENVELOPE.size
    leaves = [None] * n_leaves
    for _ in range(n_leaves):
        leaf_id, leaf, offset = decode_leaf(buf, offset)
        leaves[leaf_id] = leaf
    return Message(type=msg_type, sender=sender, seq=seq, aux=aux,
                   leaves=leaves)
