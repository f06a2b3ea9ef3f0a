"""Where Pallas kernels run: compiled by Mosaic on a TPU, interpreted on CPU.

Every kernel entry point and every ``impl="auto"`` switch asks
:func:`interpret_mode` instead of deciding on its own.  Interpret mode is a
CPU testing aid only; on any accelerator other than a TPU the kernels have
no lowering, so asking there is an error rather than a silent fallback to
the interpreter.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the ``cpu`` platform, False on ``tpu``; raises elsewhere."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here are written for TPU (Mosaic) and run "
        f"interpreted only on CPU; the default backend is {platform!r}")


__all__ = ["interpret_mode"]
