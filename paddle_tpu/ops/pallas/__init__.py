"""Pallas TPU kernels + the one shared gating policy for routing to them
(flash/decode attention, fused dequant matmul)."""
import os


def interpret_enabled() -> bool:
    """PADDLE_TPU_PALLAS_INTERPRET=1 runs every Pallas kernel in interpret
    mode AND makes the dispatch layers route to them — CI on CPU then
    exercises the same glue (slicing, padding, scalar plumbing) that runs
    on hardware."""
    return bool(os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"))


def tpu_backend() -> bool:
    """True on a TPU backend. A backend that fails to initialise raises
    here: it must never read as "not a TPU" and quietly select the dense
    paths."""
    import jax
    return jax.default_backend() == "tpu"


def kernels_enabled() -> bool:
    return interpret_enabled() or tpu_backend()
