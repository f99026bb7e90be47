"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument.

    ``None`` follows the default backend: compiled Mosaic on a TPU, the
    Pallas interpreter on any other backend (the CPU test runs).  It is
    asked when a kernel is traced, never when a module is imported, so
    importing the kernels does not initialize a backend.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
