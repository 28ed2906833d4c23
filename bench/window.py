"""The measured window of a root stream, driven by the driver's stop rule.

The program's round loop calls a stop rule after it has drained each
dispatch block: the block's roots and the running accumulator have been
copied to the host, so the host clock read there marks the block's
completion on the device.  :class:`StreamWindow` is that rule.  The first
``warmup_blocks`` blocks compile and warm up and belong to set-up; the
window opens when the last of them completes and closes at the first
block completion at or after ``seconds`` later, where the rule fires.
Every accumulator it is shown is kept, so that the check can take the
contribution of any span of blocks as a difference of two of them.
"""
from __future__ import annotations

import dataclasses
import time

__all__ = ["StreamWindow", "WindowAccount", "account"]


class StreamWindow:
    """``BCDriver`` stop rule: ``(bc_running, blocks_done) -> bool``.

    ``on_block(blocks_done, now)`` is called on every completion once the
    window is open (the traced run starts and stops the profiler there).
    """

    def __init__(self, warmup_blocks: int, seconds: float, *,
                 clock=time.perf_counter, on_open=None, on_block=None,
                 annotate=None):
        if warmup_blocks < 1:
            raise ValueError("at least one warm-up block compiles the round")
        self.warmup_blocks = int(warmup_blocks)
        self.seconds = float(seconds)
        self.clock = clock
        self.on_open = on_open
        self.on_block = on_block
        self.annotate = annotate
        self.done_at: list[float] = []  # host time of each block's completion
        self.snapshots: list = []  # running accumulator after each block
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.fired = False

    def __call__(self, bc_running, blocks_done: int) -> bool:
        now = self.clock()
        if self.annotate is None:
            return self._step(bc_running, blocks_done, now)
        with self.annotate("bench.stop_rule"):
            return self._step(bc_running, blocks_done, now)

    def _step(self, bc_running, blocks_done: int, now: float) -> bool:
        if blocks_done != len(self.done_at) + 1:
            raise RuntimeError(
                f"stop rule saw block {blocks_done} after {len(self.done_at)}: "
                "the window accounting needs one call per dispatch block"
            )
        self.done_at.append(now)
        self.snapshots.append(bc_running)
        if blocks_done == self.warmup_blocks:
            self.t_open = now
            if self.on_open is not None:
                self.on_open(now)
            return False
        if self.t_open is None:
            return False
        if self.on_block is not None:
            self.on_block(blocks_done, now)
        if now - self.t_open >= self.seconds:
            self.t_close = now
            self.fired = True
            return True
        return False


@dataclasses.dataclass(frozen=True)
class WindowAccount:
    """What the window measured: blocks ``first_block .. last_block``
    (1-based, inclusive) completed inside it."""

    first_block: int
    last_block: int
    seconds: float
    roots: int
    solve_finished: bool  # the schedule ran out before the window's length

    @property
    def blocks(self) -> int:
        return self.last_block - self.first_block + 1


def account(win: StreamWindow, roots_per_block) -> WindowAccount:
    """Roots and seconds of the window.

    ``roots_per_block[i]`` is the number of roots of block ``i + 1``.
    Only blocks that completed after the window opened count, up to the
    one at which it closed; if the schedule ran out first, the window
    ends with the last block.
    """
    if win.t_open is None:
        raise RuntimeError(
            f"the window never opened: the schedule has {len(win.done_at)} "
            f"blocks, fewer than the {win.warmup_blocks} warm-up blocks"
        )
    last = len(win.done_at)
    if last == win.warmup_blocks:
        raise RuntimeError("the schedule ended with the warm-up: no block was timed")
    first = win.warmup_blocks + 1
    return WindowAccount(
        first_block=first,
        last_block=last,
        seconds=win.done_at[last - 1] - win.t_open,
        roots=int(sum(roots_per_block[first - 1:last])),
        solve_finished=not win.fired,
    )
