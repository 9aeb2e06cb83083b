"""What the host did during a window: collector pauses and stolen CPU time.

Neither enters a metric. Both are printed on an earlier line of every run,
so that a run whose latency reads far from the rest shows whether its
process stood still for the garbage collector or its machine lent the
CPUs elsewhere (steal time).
"""

from __future__ import annotations

import gc
import os
import time


class GcWatch:
    """Counts the collector's passes per generation and their pauses while
    it is entered."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.pause_s = 0.0
        self.longest_s = 0.0
        self._t = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self._t = None
            self.passes[info["generation"]] += 1
            self.pause_s += d
            self.longest_s = max(self.longest_s, d)

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def summary(self) -> dict:
        return {"passes": list(self.passes), "pause_s": self.pause_s,
                "longest_s": self.longest_s}


def cpu_times() -> list[int]:
    """The machine's CPU time counters from ``/proc/stat`` (user, nice,
    system, idle, iowait, irq, softirq, steal, ...), in clock ticks; empty
    where there is no such file."""
    try:
        with open("/proc/stat") as f:
            return [int(x) for x in f.readline().split()[1:]]
    except (OSError, ValueError):
        return []


def steal_s(before: list[int], after: list[int]) -> float | None:
    """CPU seconds the machine's CPUs lost to others between two readings
    (summed over CPUs), or None where it cannot be read."""
    if len(before) < 8 or len(after) < 8:
        return None
    return (after[7] - before[7]) / os.sysconf("SC_CLK_TCK")
