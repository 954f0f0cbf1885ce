"""Pallas kernels of the broker's hot loop (``matchrank``) and of the
fleet bandwidth statistics (``bwstats``).

Every kernel wrapper takes ``interpret: Optional[bool] = None``; ``None``
resolves here, once, from the platform: the kernels compile through
Mosaic on a TPU and run in Pallas interpret mode on the CPU backend.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode: ``interpret`` when given, otherwise True
    exactly when JAX's default backend is the CPU."""
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() == "cpu"
