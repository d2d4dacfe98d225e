"""Plain reference of the asynchronous parameter server (arXiv:1910.10929).

Written from the paper's algorithms and the wire format's description,
one event at a time, with per-tensor arrays and ``jax.numpy`` alone.  It
imports nothing of the program and takes nothing the program made: the
weights and batches come from the seed through ``bench.gen``.

Per event, worker ``k`` on its own stale model ``theta_k``:

* DGS (Alg. 3): ``u <- m u + lr g`` per tensor; ship the top ``k_j`` of
  ``|u|`` (the selection rule below); unsent coordinates become ``u / m``.
  The shipped values are wire-quantized per tensor (int8: symmetric,
  scale ``max|v| / 127``).  ASGD: ship ``lr g`` dense.
* Server (Alg. 2): ``M <- M - decode(msg)``; ``G = M - v_k``, per-tensor
  top-``k'`` at the secondary density (or dense); ``v_k <- v_k + G``
  (dense: ``v_k <- M``); the worker applies ``theta_k <- theta_k + G``.

Selection, as the configuration's ``auto`` engine states it: a tensor of
fewer than 2**20 values keeps its exact top-k by magnitude (ties to the
lower index).  A larger one estimates the threshold as the k'-th largest
magnitude of a strided subsample (stride ``ceil(n / 65536)``, k' the
density's share of the sample), keeps the first ``4k`` nonzero passers
in index order, and takes the top-k of those; where fewer than k pass,
the message is padded with zero values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.reference.precision import at as precision_at

SAMPLED_ABOVE = 1 << 20
SAMPLE_SIZE = 65536
# envelope, frame length prefix, frame header (wire format, little-endian)
ENVELOPE, FRAME_LEN, HEADER = 17, 4, 12


def kcount(size: int, density: float) -> int:
    return max(1, min(size, int(round(size * density))))


def select(x, k: int):
    """Indices and values of the shipped support of flat ``x``."""
    n = x.shape[0]
    mag = jnp.abs(x)
    if n < SAMPLED_ABOVE:
        _, idx = jax.lax.top_k(mag, k)
        return idx, x[idx]
    stride = -(-n // SAMPLE_SIZE)
    sample = mag[::stride]
    ks = max(1, int(round(sample.shape[0] * (k / n))))
    thr = jnp.sort(sample)[-ks]
    cap = min(n, 4 * k)
    passing = (mag >= thr) & (mag > 0)
    rank = jnp.cumsum(passing) - 1
    keep = passing & (rank < cap)
    cand = jnp.nonzero(keep, size=cap, fill_value=-1)[0]
    cmag = jnp.where(cand >= 0, mag[jnp.maximum(cand, 0)], -1.0)
    _, order = jax.lax.top_k(cmag, k)
    idx = cand[order]
    pad = idx < 0
    idx = jnp.where(pad, jnp.maximum(idx[0], 0), idx)
    return idx, jnp.where(pad, 0.0, x[idx]).astype(x.dtype)


def quantize(v, mode: str):
    if mode == "none":
        return v
    if mode == "int8":
        v32 = v.astype(jnp.float32)
        scale = jnp.max(jnp.abs(v32)) / 127.0 + 1e-12
        return (jnp.clip(jnp.round(v32 / scale), -127, 127) *
                scale).astype(v.dtype)
    raise ValueError(f"no reference for wire mode {mode!r}")


def index_bytes(size: int) -> int:
    return 1 if size <= 1 << 8 else 2 if size <= 1 << 16 else 4


def sparse_frame_bytes(ks, total: int, mode: str) -> int:
    """One message of per-tensor counts ``ks`` over a ``total``-value
    arena: envelope, header, count table, one scale per tensor where the
    mode has scales, indices, packed values."""
    k = sum(ks)
    value = {"none": 4, "int8": 1}[mode]
    scales = 4 * len(ks) if mode == "int8" else 0
    return (ENVELOPE + FRAME_LEN + HEADER + 4 * len(ks) + scales +
            index_bytes(total) * k + value * k)


def dense_frame_bytes(nnz: int, total: int) -> int:
    """A dense f32 vector travels as (index, value) pairs or whole,
    whichever is smaller."""
    return (ENVELOPE + FRAME_LEN + HEADER +
            min((4 + index_bytes(total)) * nnz, 4 * total))


def mlp_loss(theta, x, y, act):
    """Mean cross entropy of the ReLU MLP ``w0, b0, w1, ...``, its matmul
    inputs through ``act``."""
    n = len([k for k in theta if k.startswith("w")])
    h = x
    for i in range(n):
        h = act(h) @ act(theta[f"w{i}"]) + theta[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    lp = jax.nn.log_softmax(h)
    return -jnp.mean(lp[jnp.arange(x.shape[0]), y])


def _event_fn(names, sizes, cfg, mix, act, fault):
    lr, m = cfg["lr"], mix.get("momentum", 0.0)
    sparse = mix["strategy"] == "dgs"
    dens2 = mix.get("secondary_density")
    half = cfg["batch_per_worker"] // 2

    def loss_fn(theta, x, y):
        if fault == "half_batch":
            x, y = x[:half], y[:half]
        return mlp_loss(theta, x, y, act)

    @jax.jit
    def event(theta, u, v, M, x, y):
        loss, g = jax.value_and_grad(loss_fn)(theta, x, y)
        u2, dM = {}, {}
        for n in names:
            gf = g[n].reshape(-1)
            if sparse:
                uacc = m * u[n] + lr * gf
                idx, vals = select(uacc, kcount(sizes[n], mix["density"]))
                sent = jnp.zeros(uacc.shape, bool).at[idx].set(True)
                u2[n] = jnp.where(sent, uacc, uacc / m)
                vals = quantize(vals, mix["quantize"])
                dM[n] = jnp.zeros_like(uacc).at[idx].add(vals)
            else:
                dM[n] = lr * gf
        if fault == "state_unchanged":
            dM = {n: jnp.zeros_like(dM[n]) for n in names}
        M2, v2, theta2 = {}, {}, {}
        nnz_up = sum(jnp.sum(dM[n] != 0) for n in names)
        nnz_dn = 0
        for n in names:
            M2[n] = M[n] - dM[n]
            diff = M2[n] - v[n]
            if dens2 is None:
                G = diff
                v2[n] = M2[n]
                nnz_dn = nnz_dn + jnp.sum(G != 0)
            else:
                idx, vals = select(diff, kcount(sizes[n], dens2))
                G = jnp.zeros_like(diff).at[idx].add(vals)
                v2[n] = v[n] + G
            theta2[n] = (theta[n].reshape(-1) + G).reshape(theta[n].shape)
        return theta2, (u2 if sparse else u), v2, M2, loss, nnz_up, nnz_dn

    return event


def run(cfg: dict, mix: dict, seed: int, events, n1: int, *,
        act_dtype: str = "float32", fault: str | None = None) -> dict:
    """Follow ``events`` (worker ids) from the seed's weights, the matmul
    inputs held in ``act_dtype`` (``bench.reference.precision``).
    Returns the per-event losses, ``M`` after the first ``n1`` events,
    the change of the global model ``theta0 + M`` after all of them, as
    flat arenas in the tensors' sorted-name order, and the wire bytes of
    those events."""
    act, precision = precision_at(act_dtype)
    params0 = gen.mlp_params(seed, cfg)
    names = sorted(params0)
    sizes = {n: int(np.prod(params0[n].shape)) for n in names}
    total = sum(sizes.values())
    theta0 = {n: params0[n] for n in names}
    zero = {n: jnp.zeros((sizes[n],), jnp.float32) for n in names}
    event = _event_fn(names, sizes, cfg, mix, act, fault)
    data = gen.blob_events(seed, cfg, len(events))
    workers: dict[int, list] = {}
    M = dict(zero)
    losses, nbytes, M1 = [], 0, None
    up_ks = [kcount(sizes[n], mix["density"]) for n in names] \
        if mix["strategy"] == "dgs" else None
    dn_ks = ([kcount(sizes[n], mix["secondary_density"]) for n in names]
             if mix.get("secondary_density") is not None else None)
    counts = []
    with jax.default_matmul_precision(precision):
        for e, k in enumerate(np.asarray(events)):
            k = int(k)
            theta, u, v = workers.get(k) or (dict(theta0), dict(zero),
                                             dict(zero))
            theta, u, v, M, loss, nnz_up, nnz_dn = event(
                theta, u, v, M, *data[e])
            workers[k] = [theta, u, v]
            losses.append(loss)
            counts.append((nnz_up, nnz_dn))
            if e + 1 == n1:
                M1 = _arena(M, names)
    for nnz_up, nnz_dn in counts:
        nbytes += (sparse_frame_bytes(up_ks, total, mix["quantize"])
                   if up_ks else dense_frame_bytes(int(nnz_up), total))
        nbytes += (sparse_frame_bytes(dn_ks, total, "none")
                   if dn_ks else dense_frame_bytes(int(nnz_dn), total))
    change = {n: theta0[n].reshape(-1) + M[n] - theta0[n].reshape(-1)
              for n in names}
    return {"losses": np.asarray(jnp.stack(losses), np.float64), "M1": M1,
            "n1": n1,
            "change": _arena(change, names), "bytes": nbytes,
            "sizes": [sizes[n] for n in names]}


def _arena(tree, names) -> np.ndarray:
    return np.concatenate([np.asarray(tree[n], np.float32).reshape(-1)
                           for n in names])


def leaf_norms(arena: np.ndarray, sizes) -> np.ndarray:
    out, off = [], 0
    for s in sizes:
        out.append(float(np.linalg.norm(arena[off:off + s].astype(np.float64))))
        off += s
    return np.asarray(out)


def norm_gaps(prog: np.ndarray, ref: np.ndarray, sizes, moving):
    """Each leaf's gap between the program's and the reference's norm,
    over the larger of that leaf's reference norm and the median leaf's;
    0 for leaves not in ``moving``."""
    p, r = leaf_norms(prog, sizes), leaf_norms(ref, sizes)
    base = np.maximum(r, np.median(r))
    return np.where(moving, np.abs(p - r) / base, 0.0)


def supports(prog: np.ndarray, ref: np.ndarray, sizes):
    """Per leaf: the share of the reference's nonzero positions that the
    program's are nonzero at too, and the median ratio of the program's
    value to the reference's there (a wire scale that differs moves it
    off 1)."""
    overlap, ratio, off = [], [], 0
    for s in sizes:
        p, r = prog[off:off + s], ref[off:off + s]
        both = (p != 0) & (r != 0)
        overlap.append(float(both.sum() / max(1, (r != 0).sum())))
        ratio.append(float(np.median(p[both] / r[both])) if both.any()
                     else 0.0)
        off += s
    return overlap, ratio


def compare(prog: dict, ref: dict, detail: bool = False) -> dict:
    """The numbers ``correct`` is decided by (each a gap, lower is
    better).  ``loss1_gap`` is the worst over the first batch alone, whose
    events all start from the seed's weights: it shows the arithmetic's
    precision without the later events' spread (a different top-k support
    on one side moves the models apart).  Leaves whose first-batch
    reference update is under a thousandth of the median leaf's are left
    out of the norm gaps: they move by round-off alone.  ``detail`` adds
    each leaf's gaps and, for ``M`` after the first batch, how far the
    two supports overlap and the ratio of their values.  ``bytes_gap`` is
    relative: a dense message travels as (index, value) pairs where that
    is smaller, so its length follows the count of exact zeros, which
    rounding moves."""
    sizes = ref["sizes"]
    g1 = leaf_norms(ref["M1"], sizes)
    moving = g1 >= 1e-3 * np.median(g1)
    loss_gaps = np.abs(prog["losses"] - ref["losses"]) / np.abs(ref["losses"])
    grad1 = norm_gaps(prog["M1"], ref["M1"], sizes, moving)
    change = norm_gaps(prog["change"], ref["change"], sizes, moving)
    out = {
        "loss_gap": float(np.max(loss_gaps)),
        "loss1_gap": float(np.max(loss_gaps[:ref["n1"]])),
        "grad1_gap": float(np.max(grad1)),
        "change_gap": float(np.max(change)),
        "bytes_gap": float(abs(prog["bytes"] - ref["bytes"]) / ref["bytes"]),
    }
    if detail:
        overlap, ratio = supports(prog["M1"], ref["M1"], sizes)
        out["leaves"] = {"grad1": grad1.tolist(), "change": change.tolist(),
                         "overlap1": overlap, "ratio1": ratio}
    return out

