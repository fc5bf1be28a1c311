"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU backend (the interpreter is the correctness gate there),
    False on a TPU.  Any other backend raises: the kernels have no compiled
    form for it, and silently interpreting would hide the device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither (use SearchSpec(kernel='jnp') there)"
    )

