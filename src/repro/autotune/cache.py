"""Persistent measured-cost cache behind the engine/tile/overlap/prior picks.

The roofline model (:mod:`repro.roofline.model`) prices every candidate
config analytically; this cache stores what a config actually *measured*
(:mod:`repro.autotune.measure`) so the four choice seams — the hybrid
per-cell kernel choice, ``overlap="auto"``, the straggler EWMA prior,
and the BCSR tile-shape pick — can consult a measurement before falling
back to the model.

Keying (measure-once semantics):

  graph key  — graph stats + mesh shape + device:
               ``n{n}_m{m}_r{R}x{C}x{fr}_t{nnz_tiles}_k{skew}@{kind}``
               where ``skew`` is the degree skew ``max(deg)/mean(deg)``
               rounded to one decimal (a topology signature: RMAT vs
               uniform graphs land on different keys, re-runs of the
               same graph on the same mesh land on the same one) and
               ``kind`` is the measuring device's ``device_kind`` — a
               wall measured on the CPU (interpreted kernels) is never
               served to a chip run, nor one chip's to another.
  config key — candidate config: ``{engine}|{overlap}|b{batch}|``
               ``t{bm}x{bk}`` (``t-`` for untiled engines).

A record under (graph key, config key) is the measured per-level wall
seconds of that config.  Same keys on a later run ⇒ cache hit ⇒ no
re-measurement; the hit/miss/measured counters make that auditable
(``tools/autotune_smoke.py`` asserts the round trip).

The JSON file is versioned and corrupt-tolerant: an unreadable or
wrong-version file is treated as empty rather than crashing the run.
``path=None`` keeps the cache in-memory (unit tests, one-shot runs).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import tempfile

import numpy as np

logger = logging.getLogger(__name__)

#: autotune modes (single source of truth — check_docs enforces that the
#: README/ARCHITECTURE flag tables mention every value):
#:   "off"     — roofline-only status quo (default; no cache, no timing)
#:   "cache"   — consult the cache; on a miss fall back to the roofline,
#:               never measure (safe for dry-runs and CI gates)
#:   "measure" — consult the cache; on a miss micro-bench the candidate
#:               and record it (measure-once: the next run hits)
AUTOTUNE_MODES = ("off", "cache", "measure")

CACHE_VERSION = 1


def normalize_autotune(mode: str | None) -> str:
    """Validate an ``autotune=`` mode (None ⇒ "off")."""
    if mode is None:
        return "off"
    if mode not in AUTOTUNE_MODES:
        raise ValueError(
            f"autotune must be one of {AUTOTUNE_MODES}, got {mode!r}"
        )
    return mode


def graph_key(
    n: int,
    m: int,
    *,
    R: int,
    C: int,
    fr: int = 1,
    nnz_tiles: int = 0,
    degree_skew: float = 1.0,
) -> str:
    """Graph-stats + mesh-shape + device cache key (see module
    docstring); the device is the process's first."""
    import jax

    return (
        f"n{int(n)}_m{int(m)}_r{int(R)}x{int(C)}x{int(fr)}"
        f"_t{int(nnz_tiles)}_k{float(degree_skew):.1f}"
        f"@{jax.devices()[0].device_kind}"
    )


def graph_key_for(
    partition, graph=None, *, fr: int = 1, nnz_tiles: int = 0
) -> str:
    """Graph key from a :class:`TwoDPartition` (+ the graph for degree
    stats; without it the skew falls back to 1).  ``nnz_tiles`` is the
    caller's tile count when a tile pass already ran (tiled engines);
    untiled engines key on 0 — the key only needs to be stable across
    runs of the same configuration."""
    m = int(partition.arc_counts.sum())
    if graph is not None and graph.n > 0:
        deg = graph.degrees().astype(np.float64)
        skew = float(deg.max() / max(deg.mean(), 1.0))
    else:
        skew = 1.0
    return graph_key(
        partition.n, m, R=partition.R, C=partition.C, fr=fr,
        nnz_tiles=nnz_tiles, degree_skew=skew,
    )


def config_key(
    engine_kind: str,
    overlap: str,
    batch_size: int,
    tile: tuple[int, int] | None = None,
) -> str:
    """Candidate-config cache key (see module docstring)."""
    t = f"t{int(tile[0])}x{int(tile[1])}" if tile is not None else "t-"
    return f"{engine_kind}|{overlap}|b{int(batch_size)}|{t}"


@dataclasses.dataclass(frozen=True)
class CostRecord:
    """One measured config: per-level wall seconds + raw evidence."""

    level_s: float
    levels: int = 0
    walls: tuple[float, ...] = ()

    def to_json(self) -> dict:
        return {
            "level_s": self.level_s,
            "levels": self.levels,
            "walls": list(self.walls),
        }

    @classmethod
    def from_json(cls, obj: dict) -> "CostRecord":
        return cls(
            level_s=float(obj["level_s"]),
            levels=int(obj.get("levels", 0)),
            walls=tuple(float(w) for w in obj.get("walls", ())),
        )


class CostCache:
    """Persistent JSON cost cache with hit/miss/store accounting.

    ``path=None`` ⇒ in-memory only.  Loads eagerly (corrupt or
    wrong-version files are treated as empty), saves atomically
    (write-temp + rename) on every :meth:`put` so a killed run never
    loses or corrupts earlier measurements.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = pathlib.Path(path) if path is not None else None
        self.entries: dict[str, dict[str, CostRecord]] = {}
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self._load()

    def _load(self) -> None:
        if self.path is None or not self.path.exists():
            return
        try:
            obj = json.loads(self.path.read_text())
        except (OSError, ValueError) as e:  # bad JSON / non-UTF-8 bytes
            # corrupt-tolerant, but never silent: a garbled cache means
            # every measurement is gone and the run re-measures cold
            logger.warning(
                "autotune cache %s is unreadable (%s: %s); starting empty",
                self.path, type(e).__name__, e,
            )
            return
        if not isinstance(obj, dict) or obj.get("version") != CACHE_VERSION:
            logger.warning(
                "autotune cache %s has an unexpected version/shape "
                "(want version %s); starting empty",
                self.path, CACHE_VERSION,
            )
            return
        for gkey, configs in obj.get("entries", {}).items():
            try:
                self.entries[gkey] = {
                    ckey: CostRecord.from_json(rec)
                    for ckey, rec in configs.items()
                }
            except (KeyError, TypeError, ValueError):
                logger.warning(
                    "autotune cache %s: malformed record group %s skipped",
                    self.path, gkey,
                )
                continue  # skip a malformed group, keep the rest

    def save(self) -> None:
        if self.path is None:
            return
        obj = {
            "version": CACHE_VERSION,
            "entries": {
                gkey: {ckey: rec.to_json() for ckey, rec in configs.items()}
                for gkey, configs in self.entries.items()
            },
        }
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=str(self.path.parent), prefix=self.path.name, suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(obj, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def get(self, gkey: str, ckey: str) -> CostRecord | None:
        rec = self.entries.get(gkey, {}).get(ckey)
        if rec is None:
            self.misses += 1
        else:
            self.hits += 1
        return rec

    def put(self, gkey: str, ckey: str, record: CostRecord) -> None:
        self.entries.setdefault(gkey, {})[ckey] = record
        self.stores += 1
        self.save()

    def num_records(self) -> int:
        return sum(len(c) for c in self.entries.values())

    def stats(self) -> dict:
        return {
            "path": str(self.path) if self.path else None,
            "records": self.num_records(),
            "hits": self.hits,
            "misses": self.misses,
            "stores": self.stores,
        }


def as_cache(cache) -> "CostCache":
    """Coerce a ``CostCache | path | None`` into a CostCache."""
    if isinstance(cache, CostCache):
        return cache
    return CostCache(cache)
