"""The benchmark's clock: the measured window and what its stamps say.

The trainer's loop calls `on_step(it)` once an iteration, after the in-flight
window has drained down to two steps, so in steady state the gap between two
stamps is one device step. The window opens at the stamp of iteration
`warmup` and closes at the first stamp `seconds` or more after it. The rate
is all the window's steps over all its time. Beside it stand the median of
the per-step intervals, which one stall does not move, and
`stall_pct = 100 * (1 - window rate / median step's rate)`, which says how
much of the window went to stalls.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, List, Optional, Sequence

# on_step(it) runs with steps it-2 and it-1 still in flight, and stamp it+1
# follows the drain of step it-2: the first gaps that are one steady device
# step lie after stamp 4 (step 0 carries the program's first execution)
MIN_WARMUP = 6


def estimate(stamps: Sequence[float], tokens_a_step: float) -> dict:
    """Everything the window says, from its stamps (first boundary to last)."""
    if len(stamps) < 2:
        raise ValueError("a window needs two stamps, got %d" % len(stamps))
    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    window_s = stamps[-1] - stamps[0]
    rate = len(intervals) * tokens_a_step / window_s
    median_step_s = statistics.median(intervals)
    return {
        "steps": len(intervals), "tokens_a_step": tokens_a_step,
        "window_s": window_s, "rate": rate, "median_step_s": median_step_s,
        "stall_pct": 100.0 * (1.0 - rate * median_step_s / tokens_a_step),
        "intervals_s": intervals,
    }


class WindowClock:
    """The `on_step` hook. Stamps every iteration and ends the run, through
    `end_run(it)` (the harness sets `args.train_iters` there), at the first
    stamp `seconds` past the window's start. `end_run` may return a number
    of steps that follow the window (the traced tail of `--trace 2`): their
    stamps are kept apart (`tail_stamps`) and are no part of the window."""

    def __init__(self, seconds: float, warmup: int, end_run: Callable[[int], None],
                 clock: Callable[[], float] = time.perf_counter):
        if warmup < MIN_WARMUP:
            raise ValueError("warm-up of %d steps; at least %d are needed for the "
                             "dispatched-ahead steps to drain" % (warmup, MIN_WARMUP))
        self.seconds = seconds
        self.warmup = warmup
        self.end_run = end_run
        self.clock = clock
        self.stamps: List[float] = []
        self.last: Optional[int] = None  # the iteration whose stamp closes the window
        self.tail = 0  # steps after the window that `end_run` announced

    def on_step(self, it: int) -> None:
        if it != len(self.stamps) or (self.last is not None and it >= self.last + self.tail):
            raise RuntimeError("on_step(%d) after %d stamps: the loop replayed, skipped "
                               "or outran an iteration" % (it, len(self.stamps)))
        self.stamps.append(self.clock())
        if self.last is None and it > self.warmup \
                and self.stamps[it] - self.stamps[self.warmup] >= self.seconds:
            self.last = it
            self.tail = int(self.end_run(it) or 0)

    def window_stamps(self) -> List[float]:
        if self.last is None:
            raise RuntimeError("the run ended before the window did")
        return self.stamps[self.warmup:self.last + 1]

    def tail_stamps(self) -> List[float]:
        """The window's closing stamp and those of the steps after it."""
        return self.stamps[self.last:] if self.last is not None else []
