"""Wire codec: round-trip exactness, quantize semantics, size accounting."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.cluster import wire
from repro.core.sparsify import SparseLeaf, quantize_dequantize, topk_select

MODES = ("none", "bf16", "int8", "tern")

# the repo always executes quantize_dequantize under jit (inside engine /
# strategy jits, and as the codec's _quantize_parts); XLA's fused
# evaluation can differ from eager by 1 ulp (e.g. FMA-contracted
# `max/127 + 1e-12`), so bitwise equivalence is pinned to the jitted form
# and eager gets an allclose-at-1-ulp check
_qd_jit = {m: jax.jit(partial(quantize_dequantize, mode=m)) for m in MODES}


def _assert_matches_quantize_dequantize(dec_values, raw_values, mode):
    ref_jit = _qd_jit[mode](raw_values)[0]
    np.testing.assert_array_equal(np.asarray(dec_values),
                                  np.asarray(ref_jit))
    ref_eager = quantize_dequantize(raw_values, mode)[0]
    np.testing.assert_allclose(np.asarray(dec_values),
                               np.asarray(ref_eager), rtol=3e-7, atol=0)


def _leaf(n, k, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    return topk_select(x, k)


class TestLeafRoundTrip:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("n,k", [(8, 1), (300, 7), (70000, 33),
                                     (50, 50)])
    def test_sparse_roundtrip_exact_vs_quantize_dequantize(self, mode, n, k):
        """decode(encode(v, mode)) == quantize_dequantize(v, mode) bitwise,
        and equals the `shipped` leaf the encoder hands back."""
        leaf = _leaf(n, k, seed=n + k)
        frame, shipped = wire.encode_leaf(5, leaf, mode)
        leaf_id, dec, end = wire.decode_leaf(frame)
        assert leaf_id == 5 and end == len(frame)
        _assert_matches_quantize_dequantize(dec.values, leaf.values, mode)
        np.testing.assert_array_equal(np.asarray(dec.values),
                                      np.asarray(shipped.values))
        np.testing.assert_array_equal(np.asarray(dec.indices),
                                      np.asarray(leaf.indices))
        assert dec.size == n

    @pytest.mark.parametrize("mode", MODES)
    def test_frame_size_matches_accounting(self, mode):
        for n, k in [(8, 3), (300, 7), (70000, 128)]:
            leaf = _leaf(n, k, seed=1)
            frame, _ = wire.encode_leaf(0, leaf, mode)
            assert len(frame) == wire.leaf_frame_bytes(k, n, mode)

    def test_index_width_narrows_with_size(self):
        # u8 for <=256, u16 for <=65536, u32 beyond — derived from `size`
        assert wire.index_dtype(256) == np.uint8
        assert wire.index_dtype(257) == np.uint16
        assert wire.index_dtype(1 << 16) == np.uint16
        assert wire.index_dtype((1 << 16) + 1) == np.uint32
        small = wire.leaf_frame_bytes(10, 200, "none")
        big = wire.leaf_frame_bytes(10, 1 << 20, "none")
        assert big - small == 10 * 3  # 3 extra index bytes per entry

    @pytest.mark.parametrize("nnz_frac", [0.0, 0.05, 0.5, 1.0])
    def test_dense_roundtrip_exact(self, nnz_frac):
        rng = np.random.default_rng(3)
        d = np.where(rng.random(400) < nnz_frac,
                     rng.normal(size=400), 0.0).astype(np.float32)
        frame, shipped = wire.encode_leaf(1, jnp.asarray(d), "none")
        _, dec, end = wire.decode_leaf(frame)
        assert end == len(frame)
        np.testing.assert_array_equal(np.asarray(dec), d)
        np.testing.assert_array_equal(np.asarray(shipped), d)
        # codec picked the cheaper dense encoding, and accounted it exactly
        nnz = int(np.count_nonzero(d))
        assert len(frame) == wire.leaf_frame_bytes(
            nnz, 400, "none", wire._dense_kind(nnz, 400))

    def test_tern_packs_four_codes_per_byte(self):
        leaf = _leaf(1000, 100, seed=2)
        f_tern, _ = wire.encode_leaf(0, leaf, "tern")
        f_none, _ = wire.encode_leaf(0, leaf, "none")
        # 100 f32 values (400B) become 25 code bytes + 4B scale
        assert len(f_none) - len(f_tern) == 400 - 25 - 4


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 2000), st.integers(1, 64), st.integers(0, 2 ** 31))
def test_property_roundtrip_all_modes(n, k, seed):
    k = min(k, n)
    leaf = _leaf(n, k, seed)
    for mode in MODES:
        frame, shipped = wire.encode_leaf(0, leaf, mode)
        assert len(frame) == wire.leaf_frame_bytes(k, n, mode)
        _, dec, _ = wire.decode_leaf(frame)
        _assert_matches_quantize_dequantize(dec.values, leaf.values, mode)
        np.testing.assert_array_equal(np.asarray(dec.values),
                                      np.asarray(shipped.values))
        np.testing.assert_array_equal(np.asarray(dec.indices),
                                      np.asarray(leaf.indices))


def _arena_leaf(sizes, density, seed):
    """A segmented global-index arena message like the runtime ships."""
    rng = np.random.default_rng(seed)
    offs = np.cumsum([0] + list(sizes[:-1]))
    total = int(sum(sizes))
    vals, idxs, seg = [], [], []
    for off, size in zip(offs, sizes):
        k = max(1, int(round(size * density)))
        idxs.append(rng.choice(size, k, replace=False).astype(np.int32)
                    + off)
        vals.append(rng.normal(size=k).astype(np.float32))
        seg.append(k)
    leaf = SparseLeaf(values=jnp.asarray(np.concatenate(vals)),
                      indices=jnp.asarray(np.concatenate(idxs)),
                      size=total)
    return leaf, tuple(seg)


class TestArenaFrame:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("sizes", [(40,), (200, 31, 4000),
                                       (70000, 9, 300)])
    def test_arena_roundtrip_segmentwise_quantize(self, mode, sizes):
        """decode(encode_arena) reproduces the SEGMENT-wise jitted
        quantizer bitwise (one scale per tensor) and the ``shipped`` leaf,
        with one header + one index block + one value block."""
        leaf, seg = _arena_leaf(sizes, 0.1, seed=sum(sizes))
        frame, shipped = wire.encode_arena_leaf(leaf, mode, seg)
        assert len(frame) == wire.arena_frame_bytes(seg, leaf.size, mode)
        leaf_id, dec, end = wire.decode_leaf(frame)
        assert end == len(frame)
        # per-segment bit-equality against the jitted quantizer
        off = 0
        for s in seg:
            _assert_matches_quantize_dequantize(
                dec.values[off:off + s], leaf.values[off:off + s], mode)
            off += s
        np.testing.assert_array_equal(np.asarray(dec.values),
                                      np.asarray(shipped.values))
        np.testing.assert_array_equal(np.asarray(dec.indices),
                                      np.asarray(leaf.indices))
        assert dec.size == leaf.size

    @pytest.mark.parametrize("mode", MODES)
    def test_arena_matches_quantize_message(self, mode):
        """The in-process stand-in (async_sim / scan runner path) ==
        what the codec ships over the wire."""
        leaf, seg = _arena_leaf((128, 40), 0.2, seed=3)
        _, shipped = wire.encode_arena_leaf(leaf, mode, seg)
        local = wire.quantize_message(leaf, mode, seg=seg)
        np.testing.assert_array_equal(np.asarray(shipped.values),
                                      np.asarray(local.values))

    def test_arena_beats_perleaf_framing(self):
        """One arena frame costs less than the per-leaf frames it fuses:
        a 4-byte seg entry replaces each 16-byte leaf header (the arena's
        global indices can cost one extra byte per entry on tiny leaves,
        but header savings dominate at matched index widths)."""
        sizes = (500, 300, 290, 450, 310)   # all u16, total still u16
        leaf, seg = _arena_leaf(sizes, 0.1, seed=5)
        arena = wire.arena_frame_bytes(seg, leaf.size, "none")
        perleaf = sum(wire.leaf_frame_bytes(k, size, "none")
                      for k, size in zip(seg, sizes))
        assert arena < perleaf

    def test_message_roundtrip_with_arena_seg(self):
        leaf, seg = _arena_leaf((64, 1000), 0.1, seed=9)
        payload, shipped = wire.encode_message(
            wire.UP, 2, 5, [leaf], mode="int8", seg=seg, aux=1.5)
        assert len(payload) == wire.frame_bytes(leaf, mode="int8", seg=seg)
        m = wire.decode_message(payload)
        assert (m.type, m.sender, m.seq, m.aux) == (wire.UP, 2, 5, 1.5)
        assert len(m.leaves) == 1
        np.testing.assert_array_equal(np.asarray(m.leaves[0].values),
                                      np.asarray(shipped[0].values))
        np.testing.assert_array_equal(np.asarray(m.leaves[0].indices),
                                      np.asarray(leaf.indices))


class TestMessage:
    def test_envelope_and_multi_leaf(self):
        msgs = [_leaf(100, 5, 0), jnp.zeros(64),
                _leaf(300, 2, 1)]
        payload, shipped = wire.encode_message(
            wire.UP, 3, 17, msgs, mode="int8", aux=2.5)
        assert len(payload) == wire.frame_bytes(msgs, mode="int8")
        m = wire.decode_message(payload)
        assert (m.type, m.sender, m.seq, m.aux) == (wire.UP, 3, 17, 2.5)
        assert len(m.leaves) == 3
        for dec, ship in zip(m.leaves, shipped):
            if isinstance(ship, SparseLeaf):
                np.testing.assert_array_equal(np.asarray(dec.values),
                                              np.asarray(ship.values))
            else:
                np.testing.assert_array_equal(np.asarray(dec),
                                              np.asarray(ship))

    def test_control_messages(self):
        for t in (wire.HELLO, wire.WELCOME, wire.SKIP, wire.BYE):
            payload, _ = wire.encode_message(t, 9, 4)
            m = wire.decode_message(payload)
            assert (m.type, m.sender, m.seq, m.leaves) == (t, 9, 4, [])

    def test_quantize_message_matches_encode_shipped(self):
        """async_sim's in-process stand-in == what the codec ships."""
        msgs = [_leaf(128, 9, 7), _leaf(40, 3, 8)]
        for mode in MODES:
            _, shipped = wire.encode_message(wire.UP, 0, 0, msgs, mode=mode)
            local = wire.quantize_message(msgs, mode)
            for a, b in zip(shipped, local):
                np.testing.assert_array_equal(np.asarray(a.values),
                                              np.asarray(b.values))


# ------------------------------------------- fused quantize+pack kernel

class TestPackFromArena:
    """wire.pack_from_arena (the fused kernels/wire_pack.py path) against
    the legacy per-segment encoder it replaced."""

    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("sizes", [(37, 400, 63), (5, 3), (1000,)])
    def test_frames_byte_identical_to_segment_encoder(self, mode, sizes):
        size = 70000
        k = sum(sizes)
        rng = np.random.default_rng(hash((mode, sizes)) % 2 ** 31)
        leaf = SparseLeaf(
            values=jnp.asarray(rng.normal(size=k).astype(np.float32)),
            indices=jnp.asarray(np.sort(rng.choice(size, k, replace=False))
                                .astype(np.int32)),
            size=size)
        legacy, ship_legacy = wire.encode_arena_leaf_segments(
            leaf, mode, sizes)
        fused, ship_fused = wire.pack_from_arena(leaf, mode, sizes)
        assert fused == legacy                      # byte-for-byte frame
        np.testing.assert_array_equal(np.asarray(ship_fused.values),
                                      np.asarray(ship_legacy.values))
        np.testing.assert_array_equal(np.asarray(ship_fused.indices),
                                      np.asarray(ship_legacy.indices))
        # and the frame still decodes to exactly the shipped values
        _, dec, off = wire.decode_leaf(fused)
        assert off == len(fused)
        np.testing.assert_array_equal(np.asarray(dec.values),
                                      np.asarray(ship_fused.values))

    @pytest.mark.parametrize("ties", (False, True))
    @pytest.mark.parametrize("mode", ("bf16", "int8", "tern"))
    def test_quantize_pack_pallas_interpret_matches_xla(self, mode, ties):
        from repro.kernels import wire_pack

        seg = (100, 30, 126)
        k = sum(seg)
        rng = np.random.default_rng(11)
        values = rng.normal(size=k).astype(np.float32)
        if ties:
            # low 16 bits exactly half a bf16 ulp: the round-to-nearest-
            # even cases, with odd and even kept bits, plus the largest
            # finite f32 (rounds up to inf)
            bits = values.view(np.uint32) & np.uint32(0xFFFF0000)
            values = (bits | np.uint32(0x8000)).view(np.float32)
            values[-1] = np.finfo(np.float32).max
        values = jnp.asarray(values)
        codes_x, scales_x, dq_x = wire_pack.quantize_pack(
            values, mode=mode, seg=seg, pallas=False)
        codes_p, scales_p, dq_p = wire_pack.quantize_pack(
            values, mode=mode, seg=seg, pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(codes_x),
                                      np.asarray(codes_p))
        np.testing.assert_array_equal(np.asarray(scales_x),
                                      np.asarray(scales_p))
        np.testing.assert_array_equal(np.asarray(dq_x), np.asarray(dq_p))

    def test_narrow_indices_widths(self):
        from repro.kernels import wire_pack

        idx = jnp.asarray([0, 17, 255], jnp.int32)
        assert wire_pack.narrow_indices(idx, size=256).dtype == jnp.uint8
        assert wire_pack.narrow_indices(idx, size=257).dtype == jnp.uint16
        assert wire_pack.narrow_indices(idx, size=1 << 17).dtype \
            == jnp.uint32
