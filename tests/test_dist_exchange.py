"""What the 2-D entry point's set-up spans say a level ships and streams.

``bc.setup.partition`` carries the mesh, the resolved overlap policy, the
chunk, the kernel calls per level (``ring_steps``) and the bytes one chip
sends per forward and backward level (``exchange_bytes_fwd`` /
``exchange_bytes_bwd``); ``bc.setup.layout`` the tiles a chip streams per
level (``tile_slots``) and the most any chip stores (``tiles_stored``).
The bytes are checked against what the traced round really hands its
collectives: ``jax.lax.ppermute`` / ``all_gather`` / ``psum_scatter`` are
wrapped while a level step is traced, and each call is priced at what one
chip sends (a ring hop its operand; a ring all-gather or reduce-scatter
over an axis of size a, (a − 1) owned blocks).
"""
import math

import numpy as np
import pytest

import jax

from repro.core import operators
from repro.core.distributed import (
    distributed_betweenness_centrality,
    exchange_bytes,
    level_time_estimates,
)
from repro.core.scheduler import build_schedule
from repro.graphs import rmat_graph
from repro.graphs.partition import partition_2d, partition_arcs_2d
from repro.roofline.model import V5E

pytestmark = pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")

MiB = 2**20
LEVEL_STEPS = {
    "forward_level": "fwd", "forward_level_checked": "fwd",
    "backward_level": "bwd", "backward_level_checked": "bwd",
}


class Shipped:
    """Bytes each traced level step hands the expand and fold collectives."""

    def __init__(self, monkeypatch, axis_sizes):
        self.axis_sizes = axis_sizes
        self.phase = None
        self.levels = []  # (phase, {collective: bytes}) per traced level step
        for cls in (operators.TraversalOperator, operators.DistributedPallasOperator):
            for name, phase in LEVEL_STEPS.items():
                if name in vars(cls):
                    monkeypatch.setattr(cls, name, self._step(getattr(cls, name), phase))
        for name, share in (("ppermute", lambda a: 1.0), ("all_gather", lambda a: a - 1),
                            ("psum_scatter", lambda a: (a - 1) / a)):
            monkeypatch.setattr(jax.lax, name,
                                self._collective(getattr(jax.lax, name), name, share))

    def _step(self, fn, phase):
        def step(op, *args, **kwargs):
            if self.phase is not None:  # an outer level step records
                return fn(op, *args, **kwargs)
            self.phase = phase
            self.levels.append((phase, {}))
            try:
                return fn(op, *args, **kwargs)
            finally:
                self.phase = None
        return step

    def _collective(self, fn, name, share):
        def collective(x, axis_name, *args, **kwargs):
            if self.phase is not None:
                nbytes = math.prod(x.shape) * x.dtype.itemsize
                sent = self.levels[-1][1]
                sent[name] = sent.get(name, 0) + nbytes * share(self.axis_sizes[axis_name])
            return fn(x, axis_name, *args, **kwargs)
        return collective

    def per_level(self, phase):
        """The set of byte totals of the traced steps of ``phase``."""
        return {sum(sent.values()) for p, sent in self.levels if p == phase}

    def by(self, phase, name):
        return {sent.get(name, 0) for p, sent in self.levels if p == phase}


def _run(grid, engine_kind, overlap, integrity="off"):
    R, C = grid
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:R * C]).reshape(R, C),
                             ("data", "model"))
    result = distributed_betweenness_centrality(
        rmat_graph(6, 8, seed=1), mesh, batch_size=8, engine_kind=engine_kind,
        overlap=overlap, integrity=integrity, sampling="fixed", sample_frac=1.0,
        stop_rule=lambda bc, blocks_done: True, full_result=True,
    )
    return {s.name: s.attrs for s in result.spans}


CASES = [
    ((2, 2), "pallas_sparse", "expand+fold", "off"),
    ((2, 2), "pallas_sparse", "expand", "off"),
    ((2, 2), "pallas_sparse", "none", "off"),
    ((4, 2), "pallas_sparse", "expand+fold", "off"),
    ((2, 2), "pallas_sparse", "expand+fold", "checksum"),
    ((2, 2), "sparse", "expand+fold", "off"),
    ((1, 1), "pallas_sparse", "none", "off"),
]


@pytest.mark.parametrize("grid, engine_kind, overlap, integrity", CASES,
                         ids=[f"{r}x{c}-{e}-{o}-{i}" for (r, c), e, o, i in CASES])
def test_exchange_bytes_are_what_the_round_ships(monkeypatch, grid, engine_kind, overlap,
                                                 integrity):
    R, C = grid
    shipped = Shipped(monkeypatch, {"data": R, "model": C})
    attrs = _run(grid, engine_kind, overlap, integrity)["bc.setup.partition"]
    ring = overlap != "none"
    assert {k: attrs[k] for k in ("mesh", "overlap", "ring_steps")} == {
        "mesh": f"{R}x{C}", "overlap": overlap, "ring_steps": R if ring else 1}
    assert attrs["chunk"] == 64 // (R * C)
    assert shipped.per_level("fwd") == {attrs["exchange_bytes_fwd"]}
    assert shipped.per_level("bwd") == {attrs["exchange_bytes_bwd"]}
    if grid == (1, 1):
        assert attrs["exchange_bytes_fwd"] == attrs["exchange_bytes_bwd"] == 0
        return
    assert attrs["exchange_bytes_fwd"] > 0 and attrs["exchange_bytes_bwd"] > 0
    if overlap == "expand+fold":  # every byte rides a ring hop
        for phase in ("fwd", "bwd"):
            assert shipped.by(phase, "ppermute") == shipped.per_level(phase)
    if overlap == "none":  # the barrier schedule has no ring hop
        assert shipped.by("fwd", "ppermute") == shipped.by("bwd", "ppermute") == {0}


@pytest.mark.parametrize("grid, overlap", [((2, 2), "expand+fold"), ((2, 2), "none"),
                                           ((1, 1), "none")])
def test_layout_tile_counts_are_the_partitions(grid, overlap):
    R, C = grid
    attrs = _run(grid, "pallas_sparse", overlap)["bc.setup.layout"]
    _, _, residual, _ = build_schedule(rmat_graph(6, 8, seed=1), batch_size=8,
                                       heuristics="h0")
    counts = partition_2d(residual, R, C).blocked_sparse_counts()
    slots = counts["stored_tiles_ring" if overlap != "none" else "stored_tiles_full"]
    assert attrs["tile_slots"] == slots
    assert attrs["tiles_stored"] == counts["stored_tiles_full"]


def test_the_four_chip_cells_level_ships_56_mib():
    """R-MAT SCALE 16 on a 2x2 mesh, 128 roots, chunk 16,384: one expand
    hop of (σ, d) and one fold hop forward, of (σ, d, δ, ω) and one fold
    hop backward."""
    empty = np.zeros(0, np.int64)
    part = partition_arcs_2d(empty, empty, 65536, 2, 2)
    assert part.chunk == 16384
    fwd, bwd = exchange_bytes(part, "pallas_sparse", 128)
    assert fwd == 16 * MiB + 8 * MiB
    assert bwd == 24 * MiB + 64 * 1024 + 8 * MiB
    assert (fwd + bwd) / MiB == pytest.approx(56.0625)
    one = partition_arcs_2d(empty, empty, 32768, 1, 1)
    assert exchange_bytes(one, "pallas_sparse", 128) == (0, 0)


@pytest.mark.parametrize("engine_kind", ["sparse", "pallas", "pallas_sparse"])
def test_level_estimates_price_the_forward_exchange_bytes(engine_kind):
    """The overlap policy's collective prices are the forward level's
    bytes at the link bandwidth: one model of what a level sends."""
    part = partition_2d(rmat_graph(6, 8, seed=1), 2, 2)
    _, expand_s, fold_s = level_time_estimates(part, engine_kind, 8, hw=V5E)
    fwd, _ = exchange_bytes(part, engine_kind, 8)
    assert (expand_s + fold_s) * V5E.ici_link_bandwidth == pytest.approx(fwd)
    assert fold_s * V5E.ici_link_bandwidth == pytest.approx(part.chunk * 8 * 4)
