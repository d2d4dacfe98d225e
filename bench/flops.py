"""Operations and bytes the measured work needs, from its shapes alone.

Counted from the algorithm, not from the compiled program: work that is
recomputed (rematerialized layers) or that a kernel does beyond the
minimum (a whole-arena pass for a sparse update) is not counted, so a
share of a peak computed from these is a lower bound on what the chip
did and cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

# bytes per value of the f32 arenas and per int32 index
F32, IDX = 4, 4


def kcount(size: int, density: float) -> int:
    return max(1, min(size, int(round(size * density))))


def mlp_train_flops(dims, batch: int) -> float:
    """Forward and backward FLOPs of one ``batch``-row step of a dense MLP
    with layer widths ``dims``: 2 per multiply-add forward, the same
    again for the weight gradients, and for the input gradients of every
    layer but the first (the data needs none).  Bias adds, activations
    and the softmax are left out."""
    macs = [a * b for a, b in zip(dims[:-1], dims[1:])]
    return float(batch * (2 * sum(macs) + 2 * sum(macs) +
                          2 * sum(macs[1:])))


def mlp_tensor_sizes(dims) -> list[int]:
    """Element counts of the MLP's tensors, weights and biases."""
    out = []
    for a, b in zip(dims[:-1], dims[1:]):
        out += [a * b, b]
    return out


def commit_apply_bytes(sizes, secondary_density, n_events: int) -> float:
    """HBM bytes the server's commit (``v_k += G``) and the worker's apply
    (``theta_k += G``) need over ``n_events`` events.

    A sparse ``G`` of k entries: each entry reads its index and value and
    reads and writes its target, in both.  A dense ``G``: the commit
    reads ``M`` and writes ``v_k``; the apply reads ``theta_k`` and ``G``
    and writes ``theta_k``."""
    total = sum(sizes)
    if secondary_density is None:
        per_event = F32 * total * (2 + 3)
    else:
        k = sum(kcount(s, secondary_density) for s in sizes)
        per_event = 2 * k * (IDX + F32 + 2 * F32)
    return float(per_event * n_events)


def mamba2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a Mamba2 language model
    (arXiv:2405.21060) at sequence length ``seq``.

    Matrix products: 6 per weight of every projection (in, out) and of
    the tied head, counted once.  The depthwise conv: 6 per tap and
    channel.  The chunked SSD scan per layer, per token, with ``H`` heads
    of width ``P``, state ``N``, chunk ``Q`` (``Q`` = min(chunk, seq)):
    the intra-chunk scores ``C B^T`` (2 Q N per head group) and their
    product with ``x`` (2 Q P per head); the chunk states ``B^T x`` (2 N P
    per head); the state output ``C h`` (2 N P per head).  Backward is
    twice forward.  Norms, gates and the inter-chunk recurrence are left
    out."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    s = cfg["ssm"]
    d_in = s["expand"] * d
    H = d_in // s["head_dim"]
    P, N, G = s["head_dim"], s["d_state"], s["n_groups"]
    Q = min(s["chunk"], seq)
    conv_dim = d_in + 2 * G * N
    d_proj = 2 * d_in + 2 * G * N + H
    proj = d * d_proj + d_in * d
    ssd_fwd = 2 * Q * N * G + 2 * Q * P * H + 2 * N * P * H + 2 * N * P * H
    per_layer = 6 * proj + 6 * s["d_conv"] * conv_dim + 3 * ssd_fwd
    return float(L * per_layer + 6 * d * V)
