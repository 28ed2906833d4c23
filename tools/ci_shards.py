"""CI shard map: the tier-1 suite split into balanced parallel legs.

The distributed-overlap CI job used to run one 11-file pytest list that
drifted from the suite on disk whenever a test file was added — the new
file ran only in the slow everything-at-once tier1 job.  This map is the
single source of truth: every ``tests/test_*.py`` must belong to exactly
one shard, and ``--check`` fails CI when a file on disk appears in no
shard (or a shard lists a file that no longer exists).

Shards are balanced by measured wall time (local 8-fake-device run; the
per-shard figures below are from that measurement).  Rebalance by moving
files between lists — ``--check`` only cares about exact coverage.

Usage::

    python tools/ci_shards.py --list          # shard names, one per line
    python tools/ci_shards.py --files NAME    # space-separated file list
    python tools/ci_shards.py --check         # drift gate (exit 1 on drift)
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
TESTS = ROOT / "tests"

# Shard -> test files, every path relative to the repo root.  Keep the
# per-shard wall times (comments, local 8-fake-device measurement)
# roughly level when editing.
SHARDS: dict[str, tuple[str, ...]] = {
    "dist-core": (  # ~96s
        "tests/test_dist_bc.py",
        "tests/test_dist_overlap.py",
        "tests/test_dist_gnn2d.py",
        "tests/test_dist_exchange.py",
        "tests/test_dist_derived_width.py",
    ),
    "dist-weighted": (  # ~97s
        "tests/test_weighted.py",
        "tests/test_dist_weighted.py",
        "tests/test_blocked_spmm.py",
        "tests/test_hybrid.py",
        "tests/test_serving.py",
        "tests/test_roofline.py",
        "tests/test_tpu_compile.py",
    ),
    "engines": (  # ~103s
        "tests/test_operators.py",
        "tests/test_kernels.py",
        "tests/test_substrates.py",
        "tests/test_bc_core.py",
        "tests/test_properties.py",
        "tests/test_system.py",
        "tests/test_chip_smoke.py",
    ),
    "system": (  # ~106s
        "tests/test_autotune.py",
        "tests/test_chaos.py",
        "tests/test_straggler.py",
        "tests/test_sampling.py",
        "tests/test_bench_check.py",
        "tests/test_arch_smoke.py",
        "tests/test_spans.py",
    ),
}


def check() -> int:
    on_disk = {f"tests/{p.name}" for p in TESTS.glob("test_*.py")}
    listed: dict[str, str] = {}
    bad = 0
    for shard, files in SHARDS.items():
        for f in files:
            if f in listed:
                print(f"ci_shards: {f} listed in both {listed[f]!r} and {shard!r}")
                bad += 1
            listed[f] = shard
            if f not in on_disk:
                print(f"ci_shards: shard {shard!r} lists missing file {f}")
                bad += 1
    for f in sorted(on_disk - listed.keys()):
        print(f"ci_shards: {f} exists on disk but appears in no shard — "
              "add it to a shard list in tools/ci_shards.py")
        bad += 1
    if bad:
        return 1
    print(f"ci_shards: OK ({len(on_disk)} files across {len(SHARDS)} shards)")
    return 0


def main(argv: list[str]) -> int:
    if argv == ["--list"]:
        print("\n".join(SHARDS))
        return 0
    if len(argv) == 2 and argv[0] == "--files":
        files = SHARDS.get(argv[1])
        if files is None:
            print(f"ci_shards: unknown shard {argv[1]!r} "
                  f"(have: {', '.join(SHARDS)})", file=sys.stderr)
            return 2
        print(" ".join(files))
        return 0
    if argv == ["--check"]:
        return check()
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
