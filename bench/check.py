"""The comparison that decides ``correct``.

What the timed path produces is the driver's running accumulator, which
the window's stop rule is shown after every block, and the rescaled
result the entry returns.  Four numbers are compared, each against the
limit its configuration states (``check`` in the configuration file):

* ``span_err`` — a span of consecutive window blocks, a third of the
  window's blocks long and placed by the seed: the accumulator's growth
  over the span against the reference's Σ δ_s over the span's roots, as
  max |got − want| / max |want|.  This covers the partition and tile
  layout, both kernels, the level loop and the accumulation across rounds,
  and catches a wrong or missing score anywhere.
* ``span_med`` — over the same span, the median over vertices of
  |got − want| / |want| (vertices the span reaches): the precision of the
  scores as a whole, which a contraction computed in fewer bits moves
  while it leaves the largest error in the rounding of the accumulator.
* ``final_err`` — the returned scores against the last accumulator the
  stop rule saw, times N / k (N eligible roots counted here, k the roots
  of every dispatched block): the rescale that the entry applies.
* ``bad_roots`` — roots missing from, repeated in, or not eligible in the
  span's rounds (exact: limit 0), so that a schedule that quietly drops
  roots cannot shrink the work the reference is asked to repeat.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = ["CheckResult", "choose_span", "block_roots", "compare"]

#: the span is a third of the window's blocks: the reference then costs
#: about a third of what the window ran
SPAN_SHARE = 3


def choose_span(first: int, last: int, seed: int) -> tuple[int, int]:
    """Blocks ``a .. b`` (1-based, inclusive) of the window ``first .. last``."""
    blocks = last - first + 1
    m = max(1, math.ceil(blocks / SPAN_SHARE))
    seed %= 1 << 64
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x5EED])
    a = int(rng.integers(first, last - m + 2))
    return a, a + m - 1


def block_roots(schedule, block: int) -> np.ndarray:
    """Root ids of a dispatch block (one round per block)."""
    src = np.asarray(schedule.rounds[block - 1].sources)
    return src[src >= 0]


@dataclasses.dataclass
class CheckResult:
    numbers: dict  # name -> (value, limit)
    span: tuple[int, int]
    span_roots: int
    levels: list[int]

    @property
    def correct(self) -> bool:
        return all(
            (v == v) and v <= lim for v, lim in self.numbers.values()
        )

    def lines(self) -> list[str]:
        return [f"{k} {v!r} limit {lim!r}" for k, (v, lim) in self.numbers.items()]


def rel_median(got: np.ndarray, want: np.ndarray) -> float:
    if not np.all(np.isfinite(got)):
        return float("inf")
    reached = want != 0
    if not reached.any():
        return 0.0
    return float(np.median(np.abs(got[reached] - want[reached]) / np.abs(want[reached])))


def rel_max(got: np.ndarray, want: np.ndarray) -> float:
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    err = float(np.max(np.abs(got - want))) if want.size else 0.0
    if not np.all(np.isfinite(got)):
        return float("inf")
    return err / scale if scale > 0 else (0.0 if err == 0 else float("inf"))


def compare(*, win, acct, result, eligible: np.ndarray, batch_size: int,
            reference, limits: dict, seed: int) -> CheckResult:
    """Run the four comparisons; ``reference`` is a :class:`Reference`."""
    schedule = result.schedule
    a, b = choose_span(acct.first_block, acct.last_block, seed)
    roots = np.concatenate([block_roots(schedule, k) for k in range(a, b + 1)])

    bad = 0
    for k in range(a, b + 1):
        if k < len(schedule.rounds):  # only the schedule's last round may be short
            bad += batch_size - block_roots(schedule, k).size
    bad += roots.size - np.unique(roots).size
    bad += int((~eligible[roots]).sum())

    got = np.asarray(win.snapshots[b - 1], np.float64) - np.asarray(
        win.snapshots[a - 2], np.float64)
    want, levels = reference.contributions(roots)
    span_err = rel_max(got, want)
    span_med = rel_median(got, want)

    k_all = sum(block_roots(schedule, k).size for k in range(1, acct.last_block + 1))
    expect = np.asarray(win.snapshots[acct.last_block - 1], np.float64) * (
        int(eligible.sum()) / k_all)
    final_err = rel_max(np.asarray(result.bc, np.float64), expect)

    return CheckResult(
        numbers={
            "span_err": (span_err, float(limits["span_err"])),
            "span_med": (span_med, float(limits["span_med"])),
            "final_err": (final_err, float(limits["final_err"])),
            "bad_roots": (float(bad), 0.0),
        },
        span=(a, b),
        span_roots=int(roots.size),
        levels=levels,
    )
