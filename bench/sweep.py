#!/usr/bin/env python3
"""Find the highest rate a prefill-only cell sustains, once, on the chip.

    python3 bench/sweep.py --workload dense-gqa-2b.score \
        --fractions 0.6,0.8,0.9,1.0,1.1 --seconds 30

One process builds the cell as ``bench/run.py`` does, then measures the
saturated completion rate (a burst of requests all due at once) and runs
the cell's open loop at each rate, printing one JSON line per rate: the
completion rate, the backlog left when the window shut, and time to first
token.  A rate is sustained when its backlog does not grow over the
window.  The cell's ``rate_per_s`` (``bench/traffic/<mix>.json``) is
written by hand as 0.8 of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import program, stats, traffic  # noqa: E402
from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="",
                    help="rates per second; by default --fractions of the "
                         "saturated rate")
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.1")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    R.check_device(cell.chips)
    R.enable_compile_cache()
    cfg, sv, fam = cell.config, cell.config["serving"], cell.family
    md, mc = fam.plain.dims(cfg), program.model_config(cfg, fam)
    srv = program.server(
        mc, program.params(fam.plain.draw(md, args.seed), mc, fam), sv)
    R._warm(srv, program, 1)
    burst = traffic.make(dict(cell.mix, rate_per_s=1.0), args.seed,
                         slots=sv["max_slots"], vocab=md["vocab"],
                         seconds=40)
    t = time.perf_counter()
    for r in burst:
        srv.submit(r.prompt.tolist(), program.request_params(1))
    srv.drain()
    cap = len(burst) / (time.perf_counter() - t)
    print(json.dumps({"saturated_per_s": cap, "requests": len(burst)}),
          flush=True)
    rates = ([float(x) for x in args.rates.split(",")] if args.rates else
             [round(f * cap, 3) for f in map(float,
                                             args.fractions.split(","))])
    for rate in rates:
        mix = dict(cell.mix, rate_per_s=rate)
        reqs = traffic.make(mix, args.seed, slots=sv["max_slots"],
                            vocab=md["vocab"], seconds=args.seconds)
        book = R.Book()
        srv.scheduler.on_token = book.on_token
        pending = [R.Rec(r.prompt.tolist(), 1, due=r.due) for r in reqs]
        t0, t1, late = R._open_window(srv, program, book, pending,
                                      args.seconds)
        done = sum(1 for r in pending if r.times and r.times[0] <= t1)
        half = [r for r in pending if r.due < t0 + args.seconds / 2]
        wait = [1e3 * ((min(r.times[0], t1) if r.times else t1) - r.due)
                for r in pending]
        print(json.dumps({
            "rate_per_s": rate, "due": len(pending), "answered": done,
            "answered_per_s": done / args.seconds,
            "backlog_at_end": len(pending) - done,
            "backlog_first_half": sum(1 for r in half if not r.times or
                                      r.times[0] > t0 + args.seconds / 2),
            "ttft_p50_ms": stats.percentile(wait, 50),
            "ttft_p95_ms": stats.percentile(wait, 95),
            "generator_late_ms_max": 1e3 * max(late) if late else 0.0}),
            flush=True)
        srv.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
