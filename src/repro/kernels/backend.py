"""Where the Pallas kernels run: compiled by Mosaic on a TPU, or op-for-op in
the Pallas interpreter on the CPU (tests, CPU rehearsals). Every kernel
call site asks :func:`interpret` — there is no other switch, and no platform
quietly falls back to the interpreter."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU backend (run the kernels in the interpreter), False
    on a TPU (compile them). Any other backend raises: these kernels are
    written for Mosaic, and interpreting them on an accelerator would hide
    the device behind an emulation."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and are interpreted only on "
        f"CPU; the default JAX backend is {backend!r}")
