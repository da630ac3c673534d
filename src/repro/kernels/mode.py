"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

This is the one place the choice is made.  Kernel entry points take
``interpret=None`` and resolve it here, so nothing in the engine, the
planner or the tests has to pass it; a kernel the TPU compiler refuses
raises on the chip instead of quietly running in the interpreter.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` (every caller in the program): interpret exactly when the
    default JAX backend is not a TPU.  An explicit flag wins — the compile
    tests force ``False`` against a described chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
