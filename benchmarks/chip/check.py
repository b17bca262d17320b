"""The comparison that decides ``correct``.

Three numbers, each against a limit of its own (``limits/<cell>.json``):

- ``loss_gap``: the largest relative gap, over the checked steps, between
  the loss the step returned and the reference's loss on the same batch;
- ``grad_norm_gap``: over leaves, the largest gap between the norms of the
  first clipped gradient (as Adam's first moment holds it after one step),
  relative to the reference leaf's norm or the median leaf's, whichever is
  larger;
- ``update_norm_gap``: the same for the norm of each leaf's change
  ``master_n - master_0`` over the checked steps. Leaves whose reference
  gradient is under a thousandth of the median leaf's are left out: Adam
  moves them by round-off alone.

A gap is between the two norms, not the norm of the difference. A run
whose readings do not cover the same leaves, or are not finite, is not
correct whatever the numbers say.
"""
from __future__ import annotations

import math
import statistics

EXCLUDE_BELOW = 1e-3  # of the median leaf's reference gradient norm
NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")


def _worst_gap(got: dict, want: dict, keys) -> float:
    floor = statistics.median(want[k] for k in keys)
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in keys)


def gaps(got, want) -> dict[str, float]:
    """The three numbers for program (or control) readings ``got`` against
    the reference's ``want`` (``reference.train.Readings``)."""
    if set(got.grad_norms) != set(want.grad_norms) or len(got.losses) != len(want.losses):
        raise ValueError("readings do not cover the same leaves and steps")
    values = [*got.losses, *got.grad_norms.values(), *got.change_norms.values()]
    if not all(math.isfinite(v) for v in values):
        return {n: math.inf for n in NUMBERS}
    med = statistics.median(want.grad_norms.values())
    moved = [k for k, g in want.grad_norms.items() if g >= EXCLUDE_BELOW * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got.losses, want.losses)),
        "grad_norm_gap": _worst_gap(got.grad_norms, want.grad_norms, want.grad_norms),
        "update_norm_gap": _worst_gap(got.change_norms, want.change_norms, moved),
    }


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    return all(numbers[n] <= limits[n] for n in NUMBERS)


def report(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """Each number beside its limit, for the result line and stderr."""
    return {n: {"value": numbers[n], "limit": limits[n]} for n in NUMBERS}
