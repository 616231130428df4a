"""Backend selection for the placement score+argmin pass
(``placement_backend``): the fused jnp scan (``xla``), the Pallas kernel
emulated on the host (``pallas_interpret``) or the NumPy reference
(``ref``), chosen by REPRO_PLACEMENT_BACKEND.
"""
from __future__ import annotations

import os

_P_VALID = ("pallas", "pallas_interpret", "xla", "ref")


def placement_backend() -> str:
    """Backend for the placement score+argmin pass.

    ``xla`` (the fused jnp scan) on every platform unless
    REPRO_PLACEMENT_BACKEND names ``pallas_interpret`` or ``ref``.  The
    Pallas placement kernel's contract is float64, which Mosaic cannot
    lower for a TPU, so ``pallas`` raises instead of compiling (or of
    silently running in interpret mode off the TPU).
    """
    env = os.environ.get("REPRO_PLACEMENT_BACKEND")
    if not env:
        return "xla"
    if env not in _P_VALID:
        raise ValueError(f"REPRO_PLACEMENT_BACKEND={env!r}; expected one "
                         f"of {_P_VALID}")
    if env == "pallas":
        raise ValueError(
            "REPRO_PLACEMENT_BACKEND=pallas: the placement kernel's float64 "
            "contract cannot lower for a TPU (Mosaic refuses the f64 SMEM "
            "scalars: 'Only arrays with 32-bit element types can be "
            "converted to scalars'; in float32 it refuses the (1, 1) "
            "outputs: 'Cannot store scalars to VMEM').  Use 'xla' (the "
            "default) on the chip, or 'pallas_interpret' to emulate the "
            "kernel on the host."
        )
    return env


def placement_use_pallas() -> bool:
    return placement_backend() == "pallas_interpret"
