"""Where the Pallas kernels run: compiled on an accelerator, interpreted
on the CPU backend.  The one place the mode is decided."""
from __future__ import annotations

import jax

__all__ = ["interpret_default"]


def interpret_default() -> bool:
    """Pallas interpret mode exactly when JAX's default backend is the CPU
    (no Mosaic compiler target there); every kernel entry whose
    ``interpret`` argument is None resolves it through this function."""
    return jax.default_backend() == "cpu"
