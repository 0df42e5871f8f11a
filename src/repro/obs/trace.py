"""Span-based request-lifecycle tracing with Chrome ``trace_event`` export.

A :class:`Tracer` records three kinds of events against an injectable
clock:

* **spans** — ``with tracer.span("decode", n_slots=3): ...`` records a
  Chrome *complete* event (``ph: "X"``) whose ``ts``/``dur`` bound the
  body.  Spans nest per thread lane (``tid``); nesting depth is tracked
  explicitly so span trees reconstruct deterministically even under a
  frozen fake clock (where ts/dur containment is ambiguous).
* **retro spans** — ``tracer.complete(name, t0, dur)`` records a span
  whose bounds the caller timed itself (e.g. a request's submit ->
  complete lifetime, only known at completion).
* **instant events** — ``tracer.event("preempt", rid=3)`` records a
  Chrome *instant* event (``ph: "i"``).

``to_chrome()`` renders the whole timeline as a ``chrome://tracing`` /
Perfetto-loadable JSON object; ``save(path)`` writes it.

The serving convention for lanes: ``tid 0`` is the engine lane (step /
admit / prefill / decode / fetch / emit / draft / verify spans,
serialized host-side), and every request gets its own lane from
:meth:`Tracer.new_tid` carrying its lifecycle spans (``queued``,
``request``) and events (``first_token``, ``preempt``, ``rewind``).

Every span, enabled or not, is also a profiler TraceMe
(``jax.profiler.TraceAnnotation(name, **args)``): under a
``jax.profiler`` session it lands on the host plane, on the clock the
device ops are stamped with, its args as the event's stats.  With no
session a TraceMe records nothing and costs one object.

:class:`NoopTracer` is the disabled counterpart: it records nothing of
its own, and ``span()`` is the TraceMe alone.  ``recording`` says
whether a span's args are kept anywhere (always for :class:`Tracer`,
only under a profiler session for :class:`NoopTracer`), so an args
computation that costs more than a lookup runs only then.
"""
from __future__ import annotations

import json
import time

PID = 0   # one serving cell == one trace process


_TRACE_ME = None


def _trace_me_cls():
    """``jax.profiler.TraceAnnotation``; jax is imported on the first
    span, so the package's lightweight consumers never load it."""
    global _TRACE_ME
    if _TRACE_ME is None:
        from jax.profiler import TraceAnnotation
        _TRACE_ME = TraceAnnotation
    return _TRACE_ME


def _trace_me(name: str, args: dict):
    return _trace_me_cls()(name, **args)


def profiling() -> bool:
    """Whether a profiler session is collecting TraceMes."""
    return _trace_me_cls().is_enabled()


class NoopTracer:
    """Tracing disabled: records nothing of its own; a span is still a
    profiler TraceMe."""
    enabled = False
    events: tuple = ()

    @property
    def recording(self) -> bool:
        """Whether a span's args are kept anywhere: only while a profiler
        session runs, so callers compute them only then."""
        return profiling()

    def span(self, name, *, tid=0, **args):
        return _trace_me(name, args)

    def complete(self, name, start, duration, *, tid=0, **args):
        pass

    def event(self, name, *, tid=0, **args):
        pass

    def new_tid(self, name=None) -> int:
        return 0

    def name_thread(self, tid, name):
        pass


NOOP_TRACER = NoopTracer()


class _Span:
    """Context manager backing :meth:`Tracer.span`: the span's TraceMe,
    and ``dur`` filled on exit."""
    __slots__ = ("_tracer", "_ev", "_tm")

    def __init__(self, tracer, ev, tm):
        self._tracer, self._ev, self._tm = tracer, ev, tm

    def __enter__(self):
        self._tm.__enter__()
        return self

    def set_metadata(self, **args):
        """Add args once the body knows them (a TraceMe's own
        ``set_metadata``, which the disabled tracer's span is)."""
        self._tm.set_metadata(**args)
        self._ev.setdefault("args", {}).update(args)

    def __exit__(self, *exc):
        self._tm.__exit__(*exc)
        ev = self._ev
        tr = self._tracer
        ev["dur"] = tr._ts_now() - ev["ts"]
        tr._depth[ev["tid"]] -= 1
        if tr.listener is not None:
            tr.listener(ev)
        return False


class Tracer:
    """Event recorder.  Timestamps are microseconds relative to the
    tracer's construction instant (Chrome's ``ts`` unit), taken from the
    injectable ``clock`` (seconds, default ``time.perf_counter``)."""
    enabled = True
    recording = True     # the Chrome JSON keeps every span's args

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._epoch = clock()
        self.events: list[dict] = []     # in span-ENTER order
        self._depth: dict[int, int] = {}
        self._threads: dict[int, str] = {}
        self._next_tid = 0
        # optional tap: called with each finished event dict (span on exit,
        # retro span, instant event) — the flight recorder's feed
        # (obs/flight.py).  None costs one attribute check per record.
        self.listener = None

    # ------------------------------------------------------------- clock
    def _ts_now(self) -> float:
        return (self._clock() - self._epoch) * 1e6

    def _ts_of(self, t: float) -> float:
        """Absolute clock reading (seconds) -> trace microseconds."""
        return (t - self._epoch) * 1e6

    # ------------------------------------------------------------- lanes
    def new_tid(self, name: str | None = None) -> int:
        """Allocate a fresh thread lane (e.g. one per request)."""
        self._next_tid += 1
        if name is not None:
            self._threads[self._next_tid] = name
        return self._next_tid

    def name_thread(self, tid: int, name: str):
        self._threads[tid] = name

    # ------------------------------------------------------------ record
    def span(self, name: str, *, tid: int = 0, **args):
        tm = _trace_me(name, args)
        d = self._depth.get(tid, 0)
        ev = {"name": name, "ph": "X", "ts": self._ts_now(), "dur": 0.0,
              "pid": PID, "tid": tid, "depth": d}
        if args:
            ev["args"] = args
        self._depth[tid] = d + 1
        self.events.append(ev)
        return _Span(self, ev, tm)

    def complete(self, name: str, start: float, duration: float, *,
                 tid: int = 0, **args):
        """Record a caller-timed span: ``start`` is an absolute clock
        reading (seconds), ``duration`` is seconds."""
        ev = {"name": name, "ph": "X", "ts": self._ts_of(start),
              "dur": duration * 1e6, "pid": PID, "tid": tid,
              "depth": self._depth.get(tid, 0)}
        if args:
            ev["args"] = args
        self.events.append(ev)
        if self.listener is not None:
            self.listener(ev)

    def event(self, name: str, *, tid: int = 0, **args):
        ev = {"name": name, "ph": "i", "ts": self._ts_now(), "pid": PID,
              "tid": tid, "s": "t", "depth": self._depth.get(tid, 0)}
        if args:
            ev["args"] = args
        self.events.append(ev)
        if self.listener is not None:
            self.listener(ev)

    # ----------------------------------------------------------- inspect
    def span_tree(self, tid: int = 0) -> list[dict]:
        """The lane's spans as a nested forest (children inside parents),
        reconstructed from recorded depths — deterministic under any
        clock.  Each node: ``{name, ts, dur, args, children}``."""
        roots: list[dict] = []
        stack: list[dict] = []
        for ev in self.events:
            if ev["tid"] != tid or ev["ph"] != "X":
                continue
            node = {"name": ev["name"], "ts": ev["ts"], "dur": ev["dur"],
                    "args": ev.get("args", {}), "children": []}
            del stack[ev["depth"]:]
            (stack[-1]["children"] if stack else roots).append(node)
            stack.append(node)
        return roots

    # ------------------------------------------------------------ export
    def to_chrome(self) -> dict:
        """The Chrome/Perfetto ``trace_event`` JSON object."""
        meta = [{"name": "process_name", "ph": "M", "pid": PID, "tid": 0,
                 "args": {"name": "repro.serve"}}]
        for tid, name in sorted(self._threads.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": PID,
                         "tid": tid, "args": {"name": name}})
        evs = []
        for ev in self.events:
            out = {k: v for k, v in ev.items() if k != "depth"}
            evs.append(out)
        return {"traceEvents": meta + evs, "displayTimeUnit": "ms"}

    def to_json(self, indent: int | None = None) -> str:
        return json.dumps(self.to_chrome(), indent=indent)

    def save(self, path: str):
        with open(path, "w") as f:
            f.write(self.to_json())
