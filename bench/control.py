"""Readings of the comparison's numbers under the control and the faults.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 4 \\
        [--modes program,high,unchanged,half,altered]

Runs the cell once per mode and seed, in one process, with
``bench/faults.py``'s control or fault under the timed path, and prints
one JSON line per run with the numbers compared.  The limits in a
configuration's ``check`` lie between the largest reading of sound runs
(``program``) and the smallest of the control (``high``).  The benchmark's
own runs never run this; it needs the chip, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.faults import MODES  # noqa: E402
from bench.run import ROOT, NoChip, cells, load_peaks, require_chips, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--modes", default=",".join(MODES))
    args = ap.parse_args(argv)
    bench = cells.load_benchmark(ROOT)
    cell = bench.cell(args.workload)
    peaks = load_peaks()
    try:
        devices = require_chips(cell.chips, peaks)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for mode in args.modes.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_cell(bench, cell, seed=seed, seconds=args.seconds, trace=False,
                           devices=devices, peaks=peaks, mode=mode)
            print("reading " + json.dumps({
                "cell": cell.name, "mode": mode, "seed": seed, "correct": out["correct"],
                **{k: v["value"] for k, v in out["check"].items()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
