"""The comparisons that decide ``correct``. Plain functions over what a
driver collected, so that a test can hand them a broken run."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

from .window import median


def losses_finite(losses: Sequence[float]) -> int:
    """How many steps returned a non-finite loss."""
    return sum(1 for x in losses if not math.isfinite(x))


def loss_went_down(losses: Sequence[float], pool: int, min_drop: float,
                   compare: str = "first_step") -> bool:
    """``losses`` holds every step's loss from the first (the untrained
    weights') on. On a pool served round-robin the learner sees the same
    batches again and again, so a working update lowers the loss; a dropped
    or zeroed update leaves it exactly where it was and fails either rule.

    ``first_step``: the median over the last pass through the pool lies below
    the first step's loss by at least ``min_drop`` of it (for a loss that
    falls fast from its untrained value and then flattens).
    ``same_batch``: each batch's loss at its last visit against its first;
    the median relative drop over the pool is at least ``min_drop`` (for a
    loss that differs more between batches than it falls in a run)."""
    if len(losses) < 2 * pool or not all(math.isfinite(x) for x in losses):
        return False
    if compare == "first_step":
        return (losses[0] - median(losses[-pool:])) >= min_drop * abs(losses[0])
    drops = []
    for j in range(pool):
        visits = losses[j::pool]
        drops.append((visits[0] - visits[-1]) / abs(visits[0]))
    return median(drops) >= min_drop


def off_reference(first: Dict[str, float], reference: Optional[Dict[str, float]],
                  keys: Sequence[str], rtol: float,
                  rtol_of: Optional[Dict[str, float]] = None) -> List[str]:
    """Where the run's first step parts from the plain reference: for every
    component of the reference's loss vector whose name contains one of ``keys``,
    the run reports a value within ``rtol * |reference|`` of it (``rtol_of``
    gives a component a tolerance of its own). The list says
    what is missing or off; empty means they agree. No reference (it failed,
    or ran out of time) is itself a fault."""
    if not reference:
        return ["no reference"]
    names = [k for k in reference if any(part in k for part in keys)]
    off = [f"{k}: not reported" for k in names if k not in first]
    off += [f"{k}: {first[k]!r} against {reference[k]!r}" for k in names if k in first
            and not abs(first[k] - reference[k])
            <= (rtol_of or {}).get(k, rtol) * abs(reference[k])]
    return off if names else ["the reference has no such component"]


def replicas_agree(checksums: Dict[str, float]) -> bool:
    """Data-parallel replicas hold the same parameters: one checksum per
    device, bit for bit."""
    return len(checksums) > 1 and len(set(checksums.values())) == 1


def batch_share_ok(per_device_bytes: Dict[str, int], share: float) -> bool:
    """Each device holds ``share`` of the global batch."""
    total = sum(per_device_bytes.values())
    return total > 0 and all(
        abs(b - share * total) <= 0.01 * total for b in per_device_bytes.values())


def failed_checks(checks: Dict[str, bool]) -> List[str]:
    return [k for k, ok in checks.items() if not ok]
