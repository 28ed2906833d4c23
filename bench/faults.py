"""The control and the planted faults that the comparison must catch.

Each is a wrapper around the round function that the program's 2-D entry
builds (``repro.core.distributed.make_distributed_round_fn``), installed
for one run by :func:`installed`:

* ``high`` — the control: the plain reference at ``Precision.HIGH``
  (``bench/reference.py``) in the program's place, one round per call;
* ``unchanged`` — every round returns a zero contribution, so the
  accumulator stays as it was;
* ``half`` — half of each round's roots are left out and the rest counted
  twice;
* ``altered`` — each round's largest score is altered by 1% where it is
  produced;
* ``rescale`` — the entry rescales the scores by the planned sample
  instead of the roots it committed (``repro.core.bc.apply_sampling_rescale``);
* ``short`` — the scheduler leaves half of each round's roots out of the
  round (``build_schedule`` as the entry calls it);
* ``exchange`` — the ring's exchange between chips is left out: every
  ring hop sends a chip's chunk back to itself
  (``repro.core.operators._ring_perm``), so each chip multiplies its own
  chunk where its neighbour's belongs.  Only a cell on a ring overlap
  policy over more than one chip has that exchange.
"""
from __future__ import annotations

import contextlib

__all__ = ["MODES", "installed"]

MODES = ("program", "high", "unchanged", "half", "altered", "rescale", "short", "exchange")


def _wrap(mode: str, round_fn, control):
    import jax.numpy as jnp

    def unchanged(*args):
        out = tuple(round_fn(*args))
        return (jnp.zeros_like(out[0]),) + out[1:]

    def half(*args):
        *head, sources, derived = args
        cols = jnp.arange(sources.shape[-1])
        kept = jnp.where(cols % 2 == 0, sources, -1)
        out = tuple(round_fn(*head, kept, derived))
        return (out[0] * 2.0,) + out[1:]

    def altered(*args):
        out = tuple(round_fn(*args))
        bc = out[0]
        flat = bc.reshape(-1)
        i = jnp.argmax(flat)
        return (flat.at[i].multiply(1.01).reshape(bc.shape),) + out[1:]

    def high(*args):
        sources, derived = args[-2], args[-1]
        bc, levels = control(sources[0])
        roots = sources
        ns = jnp.zeros(roots.shape, jnp.float32)
        return bc[None, :], ns, roots, jnp.reshape(levels, (1,))

    return {"unchanged": unchanged, "half": half, "altered": altered, "high": high}[mode]


def _planned_rescale(rescale):
    def planned(result, plan):
        result = rescale(result, plan)
        if plan.mode != "off":
            result.bc = result.bc * (result.roots_accumulated / plan.k)
        return result

    return planned


def _short_schedule(build):
    def short(*args, **kwargs):
        schedule, *rest = build(*args, **kwargs)
        for rnd in schedule.rounds:
            rnd.sources[1::2] = -1
        return (schedule, *rest)

    return short


def _no_exchange(ring_perm):
    def to_self(axis_size):
        return [(s, s) for s, _ in ring_perm(axis_size)]

    return to_self


@contextlib.contextmanager
def _patched(module, name: str, make):
    original = getattr(module, name)
    setattr(module, name, make(original))
    try:
        yield
    finally:
        setattr(module, name, original)


@contextlib.contextmanager
def installed(mode: str, *, row_ptr=None, col=None, device=None):
    """Within the block, the program's entry runs with ``mode`` in place."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode == "program":
        yield
        return
    from repro.core import bc, distributed, operators

    if mode == "exchange":
        target = (operators, "_ring_perm", _no_exchange)
    elif mode == "rescale":
        target = (bc, "apply_sampling_rescale", _planned_rescale)
    elif mode == "short":
        target = (distributed, "build_schedule", _short_schedule)
    else:
        control = None
        if mode == "high":
            from bench.reference import Reference

            control = Reference(row_ptr, col, precision="high", device=device).dependencies

        def make_round_fn(make):
            return lambda *args, **kwargs: _wrap(mode, make(*args, **kwargs), control)

        target = (distributed, "make_distributed_round_fn", make_round_fn)
    with _patched(*target):
        yield
