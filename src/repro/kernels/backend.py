"""The one switch between compiled Pallas kernels and the interpreter."""
from __future__ import annotations

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend.

    The kernels target the TPU, where they compile. On the CPU they run in
    the Pallas interpreter, which checks semantics but says nothing about
    speed. Any other backend has no compiled kernels here, and interpreting
    there would hide which device ran the work, so it is an error.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; backend "
        f"{backend!r} has neither path")
