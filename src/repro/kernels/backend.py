"""Shared backend plumbing for the Pallas kernels.

Every kernel wrapper takes ``interpret: bool | None = None``:

  * ``None``  — autodetect: compile on the TPU backend, run Pallas
    interpret mode on the CPU backend (CI, rehearsals). Any other backend
    raises: a kernel never falls back to the interpreter on an
    accelerator, where it would run orders of magnitude slower unnoticed.
    This is what lets the SAME call sites run compiled on hardware
    without plumbing a flag through every layer.
  * ``True``/``False`` — explicit override. ``True`` is refused on any
    backend but the CPU for the same reason; ``False`` forces a Mosaic
    lowering (the described-topology compile tests use it on CPU).

Compiled TPU kernels also need hardware-aligned tiles: the last (lane)
axis must be a multiple of 128 and the second-to-last (sublane) axis a
multiple of 8 for f32 (see the Pallas TPU guide). ``lane_pad`` /
``sublane_pad`` return the padded extent — identity in interpret mode,
where padding would only burn emulation time.
"""
from __future__ import annotations

import jax

LANE = 128      # TPU lane width (last axis), f32
SUBLANE = 8     # TPU sublane width (second-to-last axis), f32


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → compile on TPU, interpret on CPU; raise elsewhere.

    Interpret mode is only ever granted on the CPU backend, explicitly
    requested or not."""
    backend = jax.default_backend()
    if interpret is None:
        if backend == "tpu":
            return False
        if backend == "cpu":
            return True
        raise RuntimeError(
            f"Pallas kernels compile on 'tpu' and interpret on 'cpu'; the "
            f"{backend!r} backend is neither")
    if interpret and backend != "cpu":
        raise RuntimeError(
            f"interpret=True refused on the {backend!r} backend: a kernel "
            f"on an accelerator must run compiled")
    return interpret


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lane_pad(n: int, interpret: bool) -> int:
    """Padded lane-axis extent: next multiple of 128 when compiled."""
    return n if interpret else _round_up(n, LANE)


def sublane_pad(n: int, interpret: bool) -> int:
    """Padded sublane-axis extent: next multiple of 8 when compiled."""
    return n if interpret else _round_up(n, SUBLANE)
