#!/usr/bin/env python3
"""Run a cell as ``bench/run.py --trace 1`` does, reduce its trace with the
program's spans and scopes (``bench/scopes.py``), and print where the
device's time and idle time went.

    python3 bench/scope_run.py --workload dense-gqa-2b.chat \
        --seeds 7 8 --seconds 51 [--dump-stats 12] [--long-ms 100] \
        [--out DIR]

For each seed, one JSON line on standard output: the run's result line
(``correct``, ``metrics``, ``device``, ``breakdown``) and ``scopes``:

  * ``kv_write_share.decode`` (``bench/metrics/kv_write_share.decode.py``);
  * ``decode`` / ``prefill``: leaf-op seconds by scope in each program,
    the ``copy`` ops' seconds and result bytes by scope, and by scope and
    consumer (``bench/hlo.py``, on the program's compiled text the trace
    keeps);
  * ``idle_by_span``: idle seconds by the innermost program span;
  * ``fetches``: how long the device idled inside each ``fetch`` (the
    fetch itself also spans the program it waits for), and every fetch in
    which it idled more than ``--long-ms``, with its ``fetch.ready`` and
    ``fetch.to_host`` parts;
  * with ``--dump-stats N`` (first seed only), the metadata stats of N
    device ops as the profiler wrote them.

Like ``bench/run.py`` it needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from bench import run as R, scopes, trace  # noqa: E402
from bench.names import DECODE, PREFILL  # noqa: E402


def _reader(name: str):
    return R._module(HERE / "metrics" / f"{name}.py",
                     f"bench_metric_{name.replace('.', '_')}").read


def _stat_dump(meta: dict, n: int) -> list:
    """The metadata stats of ``n`` device ops, copies first."""
    ops = [(name, stats) for plane in meta.values()
           for name, stats in plane.items() if name.startswith("%")]
    ops.sort(key=lambda o: "copy" not in o[0])
    return [{"name": name[:400], "stats": {k: str(v)[:400]
                                           for k, v in stats.items()}}
            for name, stats in ops[:n]]


def program_table(red: scopes.Scoped, module: str) -> dict:
    total = red.op_s("", module)
    return {"leaf_s": total,
            "by_scope_s": red.by_scope(module),
            "copy_s": red.by_scope(module, "copy"),
            "copy_out_bytes": red.bytes_by_scope(module, "copy"),
            "copy_accessed_bytes": red.bytes_by_scope(module, "copy",
                                                      "accessed"),
            "copy_by_consumer": red.copies_by_consumer(module),
            "unscoped_share": (red.by_scope(module).get("", 0.0) / total
                               if total > 0 else None)}


def fetches(red: scopes.Scoped, long_ms: float) -> dict:
    idle, long = [], []
    for f in red.spans("fetch"):
        ms = (f.end - f.start) / 1e6
        idle_ms = ms - 1e3 * red.busy_in(f.start, f.end)
        idle.append(idle_ms)
        if idle_ms < long_ms:
            continue
        part = {n: sum(c.end - c.start for c in red.children(f, n)) / 1e6
                for n in ("fetch.ready", "fetch.to_host")}
        long.append({"t_s": (f.start - red.window[0]) / 1e9, "ms": ms,
                     "device_idle_ms": idle_ms,
                     "ready_ms": part["fetch.ready"],
                     "to_host_ms": part["fetch.to_host"]})
    idle.sort()
    return {"n": len(idle),
            "device_idle_ms": {"median": idle[len(idle) // 2] if idle
                               else None, "max": max(idle, default=None)},
            "long": long}


def analyse(red: scopes.Scoped, long_ms: float) -> dict:
    ctx = R.Context(red, [], {}, {}, {}, None)
    return {"kv_write_share.decode": _reader("kv_write_share.decode")(ctx),
            "decode": program_table(red, DECODE),
            "prefill": program_table(red, PREFILL),
            "idle_by_span": scopes.idle_by_span(red),
            "fetches": fetches(red, long_ms)}


def scoped_run(cell, seed: int, seconds: float, *, device: dict,
               peak: dict, dump: int = 0, long_ms: float = 100.0) -> dict:
    """``bench.run.run`` with ``--trace 1``, its trace reduced by
    :mod:`bench.scopes`; the result line with ``scopes`` added."""
    kept = {}

    def load(trace_dir):
        kept["red"] = scopes.load(trace_dir)
        return kept["red"]

    # bench/run.py reduces its trace with bench.trace.load and keeps no
    # handle on it: stand the scoped reduction in for the one run
    loader, trace.load = trace.load, load
    try:
        result = R.run(cell, seed, seconds, True, device=device, peak=peak)
    finally:
        trace.load = loader
    result["scopes"] = analyse(kept["red"], long_ms)
    if dump:
        result["scopes"]["op_stats"] = _stat_dump(kept["red"].meta, dump)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump-stats", type=int, default=0)
    ap.add_argument("--long-ms", type=float, default=100.0)
    ap.add_argument("--out", help="also write each line to "
                    "<out>/<cell>.<seed>.json")
    args = ap.parse_args(argv)
    try:
        cell = R.load_cell(args.workload)
        device, peak = R.check_device(cell.chips)
        R.enable_compile_cache()
    except (R.BenchError, FileNotFoundError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = Path(args.out) if args.out else None
    if out is not None:
        out.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(args.seeds):
        res = scoped_run(cell, seed, args.seconds, device=device, peak=peak,
                         dump=args.dump_stats if i == 0 else 0,
                         long_ms=args.long_ms)
        line = json.dumps(res)
        if out is not None:
            (out / f"{cell.name}.{seed}.json").write_text(line + "\n")
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
