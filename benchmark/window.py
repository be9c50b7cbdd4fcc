"""The measured window: whole fits back to back until the time is up.

The clock and the collector hooks are arguments so the arithmetic can be
tested on a fake clock. A fit in flight when the time runs out is finished.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import sys
import time
import traceback
from typing import Callable, List, Optional


@dataclasses.dataclass
class Window:
    walls: List[float]  # wall of every fit that completed and passed its check
    starts: List[float]  # clock at the start of every attempted fit
    attempted: int
    failed: int
    start: float
    end: float

    @property
    def median_s(self) -> Optional[float]:
        return statistics.median(self.walls) if self.walls else None


def run_window(
    fit: Callable[[], object],
    check: Callable[[object], bool],
    seconds: float,
    max_fits: Optional[int] = None,
    clock: Callable[[], float] = time.perf_counter,
) -> Window:
    """Call ``fit`` until ``seconds`` have passed (or ``max_fits`` fits).

    Each fit is timed alone with the collector off; ``gc.collect()`` and
    ``check(result)`` (which may fetch from the device) run between fits and
    are not in any wall. A fit that raises, or whose check says no, counts as
    failed and gives no wall.
    """
    walls: List[float] = []
    starts: List[float] = []
    failed = 0
    start = clock()
    while clock() - start < seconds and (max_fits is None or len(starts) < max_fits):
        gc.collect()
        gc.disable()
        t0 = clock()
        starts.append(t0)
        try:
            result = fit()
            wall = clock() - t0
        except Exception:  # a fit that raises is a failed fit, not a dead run
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        finally:
            gc.enable()
        if check(result):
            walls.append(wall)
        else:
            failed += 1
    return Window(
        walls=walls, starts=starts, attempted=len(starts), failed=failed,
        start=start, end=clock(),
    )
