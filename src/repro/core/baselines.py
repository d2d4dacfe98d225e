"""Worker-side update strategies: DGS (ours) and the paper's baselines.

Every strategy shares the model-difference transport of server.py (the paper
ports GD and DGC onto the same transport to make them runnable async — §5:
"We implemented an asynchronous version of Gradient Dropping and DGC by
adding model difference based compression as in our DGS").

A strategy owns only the *worker-side* state and the upward message:

    init(params)                 -> state (arena-shaped pytree)
    step(state, grads, lr)       -> (state', msg)

State and messages live in the flat parameter arena (core/paramspace.py):
``msg`` is either ONE global-index SparseLeaf over the packed arena
(sparsified strategies — per-tensor top-k on offset-sliced views, indices
rebased by leaf offset) or ONE dense flat ``(total,)`` array (ASGD).  The
message always includes the learning rate (the server applies it verbatim:
M <- M - decode(msg)).

All top-k selection goes through core/engine.py: every sparse strategy has
an ``engine`` knob ("exact" | "sampled" | "blockwise" | "auto") and a
``quantize`` wire-quantization knob — they compose uniformly instead of
being DGS-only (DESIGN.md §10 Compression-engine).  ``message_seg`` exposes
the static per-tensor entry counts of the message (the wire codec's arena
frame segmentation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import engine as engine_lib
from . import samomentum
from .engine import CompressionSpec
from .paramspace import ParamSpace


class StrategyState(NamedTuple):
    inner: Any  # strategy-specific pytree (arena vectors)


@dataclasses.dataclass(frozen=True)
class Strategy:
    name: str = "base"
    sparse: bool = False
    engine: str = "exact"
    quantize: str = "none"
    # blockwise engine: per-block candidates (None = k, the exact mode —
    # a full per-block sort once k reaches the block size); r << k is the
    # oversampled production mode
    block_r: int | None = None

    @property
    def spec(self) -> CompressionSpec:
        """The compression-engine spec this strategy selects with."""
        return CompressionSpec(engine=self.engine, quantize=self.quantize,
                               block_r=self.block_r)

    @property
    def value_bits(self) -> int:
        """Wire bits per message value (byte accounting in async_sim)."""
        return self.spec.value_bits

    def message_seg(self, space: ParamSpace) -> tuple[int, ...] | None:
        """Static per-tensor entry counts of the upward message, or None
        for dense messages.  This is the arena wire frame's segmentation
        AND the per-segment quantization boundaries."""
        return None

    def init(self, params) -> StrategyState:
        raise NotImplementedError

    def step(self, state: StrategyState, grads, lr: float):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class _SparseStrategy(Strategy):
    """Shared plumbing for density-parameterized sparse strategies."""

    sparse: bool = True
    density: float = 0.01

    def message_seg(self, space: ParamSpace) -> tuple[int, ...]:
        return space.ks(self.density)


@dataclasses.dataclass(frozen=True)
class ASGD(Strategy):
    """Vanilla asynchronous SGD: dense eta*grad upward, dense diff downward."""

    name: str = "asgd"
    sparse: bool = False

    def init(self, params):
        return StrategyState(inner=())

    def step(self, state, grads, lr):
        space = ParamSpace.from_tree(grads)
        return state, lr * space.pack(grads)


@dataclasses.dataclass(frozen=True)
class GDAsync(_SparseStrategy):
    """Gradient Dropping (Aji & Heafield 2017), async port.

    Residual accumulation of raw (lr-scaled) gradients in one arena buffer;
    per-tensor top-k of the residual is sent; the remainder stays local
    (Alg. 1).  No momentum correction — this is the baseline whose
    convergence the paper shows degrading.
    """

    name: str = "gd_async"

    def init(self, params):
        space = ParamSpace.from_tree(params)
        return StrategyState(inner=jnp.zeros((space.total,), jnp.float32))

    def step(self, state, grads, lr):
        space = ParamSpace.from_tree(grads)
        r = state.inner + lr * space.pack(grads)
        msg = space.select(r, space.ks(self.density), self.spec)
        return StrategyState(inner=r.at[msg.indices].set(0.0)), msg


class _DGCState(NamedTuple):
    velocity: jax.Array   # momentum-corrected velocity arena (total,)
    residual: jax.Array   # accumulated unsent velocity arena (total,)


@dataclasses.dataclass(frozen=True)
class DGCAsync(_SparseStrategy):
    """Deep Gradient Compression (Lin et al. 2017), async port.

    Momentum correction: velocity u = m*u + lr*g accumulates into a residual
    r += u; per-tensor top-k of r is sent; *both* u and r are zeroed on sent
    coordinates (momentum factor masking) with one arena scatter each.
    Needs two buffers (contrast SAMomentum's one).
    """

    name: str = "dgc_async"
    momentum: float = 0.7
    clip_norm: float | None = None

    def init(self, params):
        space = ParamSpace.from_tree(params)
        # two separate allocations: the jitted client stage donates its
        # strategy-state buffers (in-place velocity/residual updates), and
        # donating one buffer twice through aliased leaves is an error
        return StrategyState(inner=_DGCState(
            velocity=jnp.zeros((space.total,), jnp.float32),
            residual=jnp.zeros((space.total,), jnp.float32)))

    def step(self, state, grads, lr):
        space = ParamSpace.from_tree(grads)
        g = space.pack(grads)
        if self.clip_norm is not None:
            # per-leaf partial sums, matching the pre-arena accumulation
            # order bit-for-bit
            gnorm = jnp.sqrt(sum(jnp.sum(v ** 2) for v in space.views(g)))
            g = g * jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-12))
        u = engine_lib.velocity_accumulate(
            state.inner.velocity, g, momentum=self.momentum, lr=lr)
        r = state.inner.residual + u
        msg = space.select(r, space.ks(self.density), self.spec)
        return (
            StrategyState(inner=_DGCState(
                velocity=u.at[msg.indices].set(0.0),  # momentum factor mask
                residual=r.at[msg.indices].set(0.0))),
            msg,
        )


@dataclasses.dataclass(frozen=True)
class DGS(_SparseStrategy):
    """Ours: SAMomentum worker (paper Algorithm 3). One buffer, no residual.

    ``quantize`` composes wire quantization with the sparse message — the
    paper's stated future work (TernGrad combination, §Conclusion):
    "none" | "bf16" | "int8" | "tern".  ``engine`` picks the top-k selector
    (core/engine.py registry).
    """

    name: str = "dgs"
    momentum: float = 0.7

    def init(self, params):
        return StrategyState(inner=samomentum.init(params))

    def step(self, state, grads, lr):
        msg, new_sam = samomentum.tree_update(
            state.inner,
            grads,
            momentum=self.momentum,
            lr=lr,
            density=self.density,
            spec=self.spec,
        )
        return StrategyState(inner=new_sam), msg


@dataclasses.dataclass(frozen=True)
class DGSPlain(_SparseStrategy):
    """Paper Algorithm 1: DGS transport without SAMomentum (residual top-k).

    Worker-side identical to GDAsync; kept as a distinct named strategy so
    ablations (SAMomentum on/off) are explicit.
    """

    name: str = "dgs_plain"

    def _delegate(self) -> GDAsync:
        return GDAsync(density=self.density, engine=self.engine,
                       quantize=self.quantize)

    def init(self, params):
        return self._delegate().init(params)

    def step(self, state, grads, lr):
        return self._delegate().step(state, grads, lr)


def msgd_step(params, velocity, grads, *, lr: float, momentum: float):
    """Single-node momentum SGD (the paper's MSGD baseline), Eq. (7)."""
    new_v = jax.tree.map(
        lambda u, g: engine_lib.velocity_accumulate(
            u, g, momentum=momentum, lr=lr),
        velocity, grads)
    new_p = jax.tree.map(lambda p, u: p - u, params, new_v)
    return new_p, new_v


STRATEGIES = {
    "asgd": ASGD,
    "gd_async": GDAsync,
    "dgc_async": DGCAsync,
    "dgs": DGS,
    "dgs_plain": DGSPlain,
}


def make_strategy(name: str, **kw) -> Strategy:
    try:
        cls = STRATEGIES[name]
    except KeyError:
        raise ValueError(f"unknown strategy {name!r}; have {sorted(STRATEGIES)}")
    return cls(**kw)
