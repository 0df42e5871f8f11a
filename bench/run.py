#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload dense-gqa-2b.chat --seed 7 \
        --seconds 51 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The configuration names its family
(``"family"``), the directory ``bench/families/<family>/`` that holds
what depends on the architecture: ``plain.py`` (its sizes, weights,
reference forward and work counts) and ``served.py`` (its program
config and parameter tree).  One process builds the model through the
program's public API with weights drawn from ``--seed`` on the device,
warms up the cell's own programs from the persistent compilation cache,
puts the traffic in place, and drives ``Server.submit``/``Server.step``
for ``--seconds``.  Then it compares what the window served with the plain
reference (``bench/reference.py`` over the family's forward) and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each compared number
beside its limit.

It exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, a device kind missing from ``bench/peaks.json``,
or a compilation inside the measured window.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import stats, traffic  # noqa: E402

FAMILIES = HERE / "families"


class BenchError(Exception):
    """A run that must not print a result."""


@dataclasses.dataclass(frozen=True)
class Family:
    """A configuration's architecture: the two modules of
    ``bench/families/<name>/``."""
    name: str
    plain: types.ModuleType     # dims, draw, forward, work counts, tiny
    served: types.ModuleType    # model_config, params (the program's types)


def _module(path: Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family_at(where: Path) -> Family:
    tag = re.sub(r"\W", "_", where.name)
    return Family(where.name,
                  _module(where / "plain.py", f"bench_family_{tag}_plain"),
                  _module(where / "served.py", f"bench_family_{tag}_served"))


def load_family(cfg: dict, root: Path = FAMILIES) -> Family:
    """The family a configuration file names, from ``root/<family>/``:
    loaded once a process, so its functions (and what is compiled for
    them) are the same on every call."""
    name = cfg.get("family")
    if not name:
        raise BenchError(f"configuration {cfg.get('name')!r} names no "
                         f"family: give it a \"family\" key, a directory "
                         f"of {root}")
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", str(name)):
        raise BenchError(f"family {name!r} is not a directory name")
    where = Path(root).resolve() / name
    missing = [f for f in ("plain.py", "served.py")
               if not (where / f).is_file()]
    if missing:
        raise BenchError(f"configuration {cfg.get('name')!r} names family "
                         f"{name!r}, but {where} has no {', '.join(missing)}")
    return _family_at(where)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    family: Family          # bench/families/<config's family>/
    mix: dict               # bench/traffic/<mix>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: list        # BENCHMARK.json metric entries for this cell
    per_layer: list


def _for_cell(entries: list, cell: str, reported: set | None = None):
    out = []
    for e in entries:
        if "workloads" in e:
            if cell in e["workloads"]:
                out.append(e)
        elif reported is None or e["moves"] in reported:
            out.append(e)
    return out


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = _for_cell(spec["end_to_end"], name)
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        family=load_family(config),
        mix=json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        limits=json.loads((HERE / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=_for_cell(spec["per_layer"], name,
                            {m["name"] for m in e2e}))


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed directory of the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_device(chips: int) -> tuple[dict, dict]:
    """(device record, peaks) of the chips JAX found; refuses a host
    without a TPU, with too few chips, or of a kind without peaks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return device_record(devs), peak_for(devs[0].device_kind)


def device_record(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_for(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} has no entry in "
                         f"bench/peaks.json")
    return peaks[kind]


class CompileWatch:
    """Records every compilation (tracing included) that the thread
    running the block makes inside it: the server's steps run there."""

    def __enter__(self):
        import jax
        self.names, self.thread = [], threading.get_ident()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, name, *_, **__):
        if (name.startswith("/jax/core/compile/")
                and threading.get_ident() == self.thread):
            self.names.append(name)


class GcWatch:
    """Records the generation and the length of every collection that
    Python's garbage collector makes inside the block."""

    def __enter__(self):
        self.spans, self._t = [], 0.0
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.spans.append((info["generation"],
                               time.perf_counter() - self._t))

    def summary(self) -> str:
        secs = [s for _, s in self.spans]
        full = sum(1 for g, _ in self.spans if g == 2)
        return (f"{len(secs)} collections in {sum(secs):.3f} s, {full} full, "
                f"longest {max(secs, default=0.0):.3f} s")


# ---------------------------------------------------------------------------
# bookkeeping of the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    prompt: list
    max_new: int
    due: float | None = None        # absolute perf_counter time
    submitted: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    prefills: list          # real prompt lengths admitted in the step
    contexts: list          # live context of each slot the decode advanced
    queue: int              # due and unanswered when the step began
    t0: float = 0.0         # host clock around Server.step
    t1: float = 0.0


class Book:
    """The window's requests and steps."""

    def __init__(self):
        self.recs: dict[int, Rec] = {}      # submitted, by request id
        self.due_recs: list[Rec] = []       # open loop: every due request
        self.steps: list[Step] = []
        self._cur: Step | None = None

    def on_token(self, rid: int, tok: int):
        t = time.perf_counter()
        rec = self.recs.get(rid)
        if rec is None:             # a warm-up request
            return
        rec.tokens.append(int(tok))
        rec.times.append(t)
        if self._cur is not None:
            if len(rec.tokens) == 1:
                self._cur.prefills.append(len(rec.prompt))
            else:
                self._cur.contexts.append(len(rec.prompt)
                                          + len(rec.tokens) - 1)

    def begin(self, queue: int = 0):
        self._cur = Step([], [], queue, t0=time.perf_counter())

    def end(self):
        self._cur.t1 = time.perf_counter()
        self.steps.append(self._cur)
        self._cur = None

    def stalls(self, factor: float) -> list[int]:
        """Steps longer than ``factor`` times the median step that
        admitted as many requests."""
        by: dict[int, list] = {}
        for st in self.steps:
            by.setdefault(len(st.prefills), []).append(st.t1 - st.t0)
        med = {k: sorted(v)[len(v) // 2] for k, v in by.items()}
        return [i for i, st in enumerate(self.steps)
                if st.t1 - st.t0 > factor * med[len(st.prefills)]]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

# a stall: a step longer than this many times the median step that
# admitted as many requests (Book.stalls)
STALL_FACTOR = 1.4


def _annot(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _warm(srv, program, max_new: int):
    """Run the cell's own programs once (prefill; decode when the cell
    decodes), so they load or compile before the window."""
    prompt = list(range(1, 17))
    for _ in range(2):
        srv.submit(prompt, program.request_params(max_new))
        srv.drain()


def _closed_window(srv, book, seconds):
    """Step the saturated cell until ``seconds`` have passed; the window
    shuts at the end of that step."""
    t0 = time.perf_counter()
    with _annot("bench.window"):
        while True:
            if not srv.has_work:
                raise BenchError("the backlog ran dry inside the window")
            book.begin()
            with _annot("bench.step"):
                srv.step()
            book.end()
            if time.perf_counter() - t0 >= seconds:
                break
        _sync(srv)
    return t0, time.perf_counter()


def _open_window(srv, program, book, pending, seconds):
    """Submit each request when it is due; step while there is work,
    sleep until the next due time when there is none."""
    t0 = time.perf_counter()
    end = t0 + seconds
    for rec in pending:
        rec.due += t0
    book.due_recs = pending
    i, late = 0, []
    with _annot("bench.window"):
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            with _annot("bench.submit"):
                while i < len(pending) and pending[i].due <= now:
                    rec = pending[i]
                    rid = srv.submit(rec.prompt,
                                     program.request_params(rec.max_new))
                    rec.submitted = time.perf_counter()
                    late.append(rec.submitted - rec.due)
                    book.recs[rid] = rec
                    i += 1
            if srv.has_work:
                book.begin(sum(1 for r in pending[:i] if not r.tokens))
                with _annot("bench.step"):
                    srv.step()
                book.end()
            else:
                nxt = pending[i].due if i < len(pending) else end
                with _annot("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        _sync(srv)
    return t0, end, late


def _sync(srv):
    import jax
    jax.block_until_ready(srv.pool.pages)


def end_to_end(cell: Cell, book: Book, t0: float, t1: float) -> dict:
    """The cell's end-to-end metrics from the window's token timestamps."""
    out = {}
    names = {m["name"] for m in cell.end_to_end}
    if "output_tokens_per_s" in names:
        n = sum(1 for r in book.recs.values() for t in r.times
                if t0 < t <= t1)
        out["output_tokens_per_s"] = n / (t1 - t0)
    if "itl_p95_ms" in names:
        gaps = [1e3 * (b - a) for r in book.recs.values()
                for a, b in zip(r.times, r.times[1:]) if a > t0 and b <= t1]
        out["itl_p95_ms"] = stats.percentile(gaps, 95)
    if "ttft_p95_ms" in names:
        waits = [1e3 * (min(r.times[0], t1) - r.due if r.times
                        else t1 - r.due)
                 for r in book.due_recs if t0 <= r.due < t1]
        out["ttft_p95_ms"] = stats.percentile(waits, 95)
    return out


def sample(book: Book, rids: list, n: int, seed: int) -> list:
    """The requests the comparison reads: the one with the most served
    tokens, then others drawn from the seed."""
    served = [r for r in rids if book.recs[r].tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(book.recs[r].tokens), -r))
    rest = [r for r in served if r != longest]
    rng = traffic.rng_for(seed, 3)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def check(cell: Cell, md: dict, seed: int, chosen: list, *,
          control: bool = False) -> tuple[dict, bool, float]:
    """Compare the sampled requests with the reference on fresh weights.
    With ``control`` (``bench/calibrate.py``; runs never do), the float8
    control's picks stand in the program's place on the same prompts and
    tokens, and ``correct`` is judged on its gap.  Also returns the
    program's own widest gap."""
    from bench import reference
    serving, plain = cell.config["serving"], cell.family.plain
    w = plain.draw(md, seed)
    widest, ctrl = 0.0, 0.0
    for prompt, served in chosen:
        g, c = reference.gaps(plain.forward, w, md, prompt, served,
                              bucket=serving["max_context"],
                              kv_bits=serving["kv_bits"],
                              kv_group=serving["kv_group"], control=control)
        widest, ctrl = max(widest, float(g.max())), max(ctrl, float(c.max()))
    del w
    gap = ctrl if control else widest
    limit = float(cell.limits["widest_logit_gap"])
    n_tok = sum(len(s) for _, s in chosen)
    checks = {"widest_logit_gap": {"value": gap, "limit": limit},
              "served_tokens_compared": {"value": n_tok,
                                         "limit": cell.limits["min_tokens"]}}
    ok = gap <= limit and n_tok >= cell.limits["min_tokens"]
    return checks, ok, widest


def per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = _module(HERE / "metrics" / f"{m['name']}.py",
                      f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads."""
    trace: object            # bench.trace.Reduced
    steps: list              # Step records of the window
    md: dict                 # model sizes (the family's plain.dims)
    serving: dict            # the configuration's serving geometry
    peak: dict               # bench/peaks.json entry
    family: Family | None    # the work counts: family.plain


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        device: dict, peak: dict, control: bool = False) -> dict:
    import jax
    from bench import program
    cfg, serving, fam = cell.config, cell.config["serving"], cell.family
    md = fam.plain.dims(cfg)
    mc = program.model_config(cfg, fam)
    book = Book()
    marks = [("start", time.perf_counter() - T0)]
    w = fam.plain.draw(md, seed)
    srv = program.server(mc, program.params(w, mc, fam), serving,
                         on_token=book.on_token)
    del w
    _sync(srv)
    marks.append(("build", time.perf_counter() - T0))
    mix = cell.mix
    decodes = mix["loop"] == "closed"
    _warm(srv, program, 2 if decodes else 1)
    marks.append(("warm", time.perf_counter() - T0))
    reqs = traffic.make(mix, seed, slots=serving["max_slots"],
                        vocab=md["vocab"], seconds=seconds)
    pending = [Rec(r.prompt.tolist(), r.max_new_tokens, due=r.due)
               for r in reqs if r.due is not None]
    for r in reqs:
        if r.due is None:
            rid = srv.submit(r.prompt.tolist(),
                             program.request_params(r.max_new_tokens))
            book.recs[rid] = Rec(r.prompt.tolist(), r.max_new_tokens)
    if decodes:
        srv.step()                  # every slot admitted
    _sync(srv)
    # What set-up leaves (JAX's import and tracing, the traffic) is
    # frozen, so that no collection inside the window walks it.
    gc.collect()
    gc.freeze()
    marks.append(("gc", time.perf_counter() - T0))
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans: annotations only
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - T0
    marks.append(("traffic", setup_s))
    print("info: set-up " + ", ".join(f"{k} {v:.2f} s" for k, v in marks)
          + f"; {gc.get_freeze_count()} objects frozen", file=sys.stderr)
    with CompileWatch() as watch, GcWatch() as gcw:
        if decodes:
            t0, t1 = _closed_window(srv, book, seconds)
            late = []
        else:
            t0, t1, late = _open_window(srv, program, book, pending, seconds)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    if watch.names:
        raise BenchError(f"{len(watch.names)} compilation events inside "
                         f"the window: {sorted(set(watch.names))}")
    st = srv.stats()
    if st["preemptions"]:
        raise BenchError(f"{st['preemptions']} preemptions: the pool is "
                         f"sized so that none happen")
    mem = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        mem.get("peak_bytes_in_use", 0)))
    metrics = {k: {"value": v, "unit": _unit(cell, k)}
               for k, v in end_to_end(cell, book, t0, t1).items()}
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    attempted = (len(book.due_recs) if not decodes else
                 sum(1 for r in book.recs.values()
                     if any(t0 < t <= t1 for t in r.times)))
    result = {"correct": False, "attempted": attempted, "failed": 0}
    breakdown = None
    if trace:
        from bench import trace as tr
        try:
            red = tr.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = Context(red, book.steps, md, serving, peak, fam)
        metrics = per_layer(cell, ctx)
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(), "idle_gaps":
                     red.idle_gaps()}
    # the comparison: a sample of what the window served
    rids = [r for r, rec in book.recs.items() if rec.tokens]
    picked = sample(book, rids, int(cell.limits["sample_requests"]), seed)
    chosen = [(book.recs[r].prompt, book.recs[r].tokens) for r in picked]
    srv = None
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())
    slow = sorted(range(len(book.steps)),
                  key=lambda i: book.steps[i].t0 - book.steps[i].t1)[:3]
    stalls = book.stalls(STALL_FACTOR)
    print(f"info: window {t1 - t0:.3f} s, {len(book.steps)} steps, "
          f"{attempted} requests; slowest steps " + ", ".join(
              f"#{i} {book.steps[i].t1 - book.steps[i].t0:.3f} s "
              f"({len(book.steps[i].prefills)} admitted)" for i in slow)
          + f"; stalls (over {STALL_FACTOR}x the median step admitting as "
          f"many) {stalls}; gc {gcw.summary()}; {live} bytes live before "
          "the reference", file=sys.stderr)
    checks, ok, prog = check(cell, md, seed, chosen, control=control)
    result.update(correct=ok, metrics=metrics, device=device)
    if control:
        result["program_widest_logit_gap"] = prog
    if breakdown is not None:
        result["breakdown"] = breakdown
    if late:
        result["generator_late_ms"] = {"median": 1e3 * stats.median(late),
                                       "max": 1e3 * max(late)}
    result["checks"] = checks
    return result


def _unit(cell: Cell, name: str) -> str:
    return {m["name"]: m["unit"] for m in cell.end_to_end}[name]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        device, peak = check_device(cell.chips)
        enable_compile_cache()
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     device=device, peak=peak)
    except (BenchError, FileNotFoundError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
