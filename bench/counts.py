"""Bytes computed from shapes: the yardstick the solver's per-layer
metrics divide by.  Nothing here reads the program.  A model's FLOPs per
evaluation are its module's ``forward_flops`` (``models/<arch>.py``)."""
from __future__ import annotations


def taa_kernel_bytes(T: int, D: int, m: int, lanes: int,
                     itemsize: int = 4) -> dict:
    """HBM bytes one call of each TAA Pallas kernel must move over
    ``lanes`` vmapped lanes: every input read once, every output written
    once.  D is padded to the 128-lane multiple the kernels work on.

    gram:  reads dF (m, T, D), R (T, D), mask (T, 1); writes the
           column-packed (T, 128) Gram/u tile.
    apply: reads x, R (T, D), dX, dF (m, T, D), the (T, 128) gamma tile
           and mask (T, 1); writes the updated (T, D) rows.
    """
    dpad = -(-D // 128) * 128
    gram = (m * T * dpad + T * dpad + T + T * 128) * itemsize
    apply = ((2 + 2 * m) * T * dpad + T * 128 + T + T * dpad) * itemsize
    return {"gram": gram * lanes, "apply": apply * lanes}
