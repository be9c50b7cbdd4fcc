"""Bytes one chip's sparse pass and one fit's state collectives need, from
their shapes, for a fixed effect whose rows AND coefficient-length state are
split over the chips (``logistic-criteo-4chip``), counted here so that a later
PR cannot move the yardstick.

A chip's pass = its gather (for each of its n k / chips slots: the index, the
value and ONE gathered coefficient read, from the whole vector the chip has
gathered) + its scatter-add (the index, the value and the row's multiplier
read, ONE update written into its local target) + its rows' pointwise work
(``shapes_sparse``'s count) + the local target, zero-filled once at the solve's
width. The reduce-scatter that turns the target into the chip's quarter and
the all-gather before the gather are the state's collectives, not the pass's:
they run over the chips' links, and their bytes are the program's counter's
(``photon_fe_collective_bytes_total``), against ``ICI_BYTES_PER_S``.
"""

from __future__ import annotations

from . import shapes_sparse

# The v5e's inter-chip interconnect: 1,600 Gbps a chip (Google Cloud
# documentation, TPU v5e: "Interchip Interconnect BW 1,600 Gbps"), 200 GB/s.
# A 2x2 host wires each chip to two neighbours of its four links' worth, so a
# collective among the four uses about half of it: a share of this peak reads
# well under 100% by construction. Kept here, not in peaks.json, whose
# entries are the roofline's per-chip compute and HBM peaks.
ICI_BYTES_PER_S = 1600e9 / 8


def chip_pass_bytes(n_chip: int, k: int, width: int, index_itemsize: int = 4, value_itemsize: int = 4,
                    scalar_itemsize: int = 4) -> int:
    """Bytes of one chip's value-and-gradient pass over its ``n_chip`` rows of
    ``k`` slots into a local target ``width`` columns wide."""
    slots = shapes_sparse.slot_bytes(n_chip, k, index_itemsize, value_itemsize, scalar_itemsize)
    rows = (3 + 2 + 1) * n_chip * scalar_itemsize  # labels/offsets/weights; margins written and read; multiplier
    return slots + rows + width * scalar_itemsize  # the local target, zero-filled


def chip_pass_flops(n_chip: int, k: int) -> int:
    """A multiply and an add a slot in each of the two sums, about 12 a row for
    the logistic loss and its derivative."""
    return 4 * n_chip * k + 12 * n_chip


def solve_width(d: int, chips: int) -> int:
    """The columns the split solve runs over: d rounded up to whole (8, 128)
    tiles and to whole rows of 128 on every chip (the rule of
    ``photon_ml_tpu/optimize/lbfgs.py`` ``history_row_width``, copied)."""
    import math

    step = math.lcm(8 * 128, 128 * chips)
    return -(-d // step) * step


def ici_share(bytes_per_chip: float, seconds: float) -> float:
    """Bytes a chip moved over the time its collectives took, in percent of
    ``ICI_BYTES_PER_S``."""
    return 100.0 * bytes_per_chip / ICI_BYTES_PER_S / seconds
