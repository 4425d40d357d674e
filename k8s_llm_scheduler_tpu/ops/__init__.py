"""Attention and norm ops: XLA reference paths + Pallas TPU kernels."""

import jax


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel's `interpret` argument. An explicit value wins;
    None means compiled by Mosaic on `tpu` and interpreted on `cpu` (the
    hermetic test suite). Any other backend is an error: interpret mode
    is never inferred from "not a TPU", because a host whose chip failed
    to initialise would then quietly run every kernel in the interpreter."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels compile on 'tpu' and interpret on 'cpu'; the "
        f"default JAX backend here is {backend!r} (devices: {jax.devices()})"
    )
