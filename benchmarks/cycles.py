"""The training rate: every row of the window over every second of it.

The refill is paced in quanta with a ceiling (``data/buffer.py``
``_cyc_segs_per_serve``), so the steps of one cycle are not alike and only a
whole cycle repeats. The loop logs (and syncs with the device) once per
cycle, so the time between two log steps is the wall time of one whole
cycle's work wherever the cycle boundary falls inside it, and a window that
opens and closes at log steps holds whole cycles only.
"""

from __future__ import annotations

import statistics

MIN_CYCLES = 10     # fewer whole cycles in the window: the run is refused (a traffic
                    # mix whose cycles are long names its own floor, ``min_cycles``)


def serves_per_cycle(buffer_size: int, batch_size: int) -> int:
    """Serves between two cycle swaps (``data/buffer.py`` ``_after_serve``)."""
    return (buffer_size // 2 - batch_size) // batch_size + 1


def summary(values: list[float]) -> dict:
    """count / least / median / greatest of a series, as printed by every run."""
    if not values:
        return {"n": 0}
    return {"n": len(values), "min": min(values),
            "median": statistics.median(values), "max": max(values)}


def rate(cycle_walls_s: list[float], rows_per_cycle: int, chips: int,
         min_cycles: int = MIN_CYCLES) -> dict:
    """``train_rows_per_s`` (rows/s/chip) from the window's cycle walls.

    The metric is all the rows of the window over all its seconds:
    rows_per_cycle * n / sum(cycle walls) / chips, so a stall anywhere in the
    window costs what it cost the job. The median cycle and the slowest one
    ride along (per-layer metrics): a rate off the median cycle is what the
    run would have read without its stalls. ``ok`` is False with fewer than
    ``min_cycles`` whole cycles.
    """
    n = len(cycle_walls_s)
    if n == 0:
        return {"ok": False, "cycles": 0}
    med = statistics.median(cycle_walls_s)
    total = sum(cycle_walls_s)
    return {
        "ok": n >= min_cycles,
        "cycles": n,
        "window_s": total,
        "rows_per_s": rows_per_cycle * n / total / chips,
        "rows_per_s_median_cycle": rows_per_cycle / med / chips,
        "cycle_s_median": med,
        "cycle_s_max": max(cycle_walls_s),
        "cycle_max_over_median": max(cycle_walls_s) / med,
    }
