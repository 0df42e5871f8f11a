#!/usr/bin/env python3
"""Readings that set a cell's ``correct`` limit (``bench/limits/<cell>``).

    python3 bench/calibrate.py --workload dense-gqa-2b.chat \
        --seeds 101,102,103,104 --control-seeds 101,102,103 --seconds 51

In one process, runs the cell once per seed exactly as ``bench/run.py``
does, and on each run's sample reads the program's widest logit gap (the
lower reading) and, for the control seeds, the widest gap of the float8
control put in the program's place (the upper reading), with ``correct``
judged on the control's gap: it has to come out false.  One JSON line
per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    device, peak = R.check_device(cell.chips)
    R.enable_compile_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctl = seed in control
        res = R.run(cell, seed, args.seconds, False, device=device,
                    peak=peak, control=ctl)
        gap = res["checks"]["widest_logit_gap"]["value"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program": res["program_widest_logit_gap"] if ctl else gap,
            "control": gap if ctl else None,
            "tokens": res["checks"]["served_tokens_compared"]["value"],
            # with the control in the program's place, correct must be false
            "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
