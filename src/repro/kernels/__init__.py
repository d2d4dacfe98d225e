"""Pallas TPU kernels of the DGS hot path, their jit'd wrappers (ops.py)
and pure-jnp oracles (ref.py)."""
import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The kernels' ``interpret`` default: ``None`` runs Pallas interpret
    mode exactly when JAX's default backend is not a TPU, so a caller
    that omits the argument compiles the real kernel on the chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
