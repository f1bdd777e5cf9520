"""Bytes and operations the SSD decode kernel needs, from shapes alone.

Kept with the benchmark, like ``costs_olmo_hybrid.py``, so that no later
change to the program can move a roofline share by recounting its own work.
One update is one slot's state in one mamba layer advanced by one position:
``d_state x heads x d_head`` float32 values read and written back, with the
token's B, C, dt x and decay beside them (a hundredth of that, left out).
"""

from __future__ import annotations

#: VPU operations a state value takes in one update of the additive rule:
#: the decay, the outer product of B with dt x and its add, the product
#: with C and its sum over d_state
OPS_PER_STATE_VALUE = 5


def ssd_decode_needed_bytes(updates: int, d_state: int, width: int) -> int:
    """State bytes ``updates`` updates move: each reads and writes its
    block of ``d_state x width`` float32 values (``width`` = heads x
    d_head)."""
    return updates * 2 * d_state * width * 4


def ssd_decode_needed_flops(updates: int, d_state: int, width: int) -> int:
    """Operations of the same updates (all on the vector unit)."""
    return updates * OPS_PER_STATE_VALUE * d_state * width


def ssd_decode_floor_seconds(updates: int, d_state: int, width: int,
                             peaks: dict) -> float:
    """The least time the chip could take for those updates: the larger
    of bytes over the memory bandwidth and operations over the peak. At
    5 operations a value against 8 bytes the bytes bind, by far."""
    return max(
        ssd_decode_needed_bytes(updates, d_state, width)
        / peaks["hbm_bytes_per_s"],
        ssd_decode_needed_flops(updates, d_state, width)
        / peaks["flops_per_s"],
    )
