"""Reduction of a profiler trace to the numbers the readers need.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU, each ``/device:TPU:<n>`` plane has a line ``XLA
Modules`` (one event per program run, ``jit__step_paged_impl(<id>)``) and
a line ``XLA Ops`` (one event per HLO instruction, named by its text,
``%quant_matmul_b4g128.54 = bf16[2048,8192]... custom-call(...)``; a
Pallas kernel keeps the ``name`` it was given).  Ops nest: a ``while``
(the scan over layers) spans the ops of its body, so time is summed over
leaf ops only.  The line ``Async XLA Ops`` (copies in flight beside
compute) is not busy time of its own and is left out.  Host planes carry
``TraceAnnotation`` spans: the benchmark's own (``bench.window``,
``bench.step``, ``bench.wait``, ``bench.submit``) and the engine's
(``prefill``, ``decode_step``), among the runtime's own.

Everything is clipped to the ``bench.window`` span, and averaged over the
device planes in use.
"""
from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict

WINDOW = "bench.window"
DEVICE = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns
    end: int
    module: str = ""
    leaf: bool = True   # holds no other op of its line


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]
    devices: int
    ops: list[Event]            # device ops, all device planes
    modules: list[Event]        # device program runs
    host: list[Event]           # host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # ------------------------------------------------------------ device
    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over the devices."""
        return union_ns([(e.start, e.end) for e in self.ops]) / 1e9 \
            / max(self.devices, 1)

    def module_s(self, part: str) -> float:
        """Device seconds of the runs of programs whose name holds
        ``part`` (e.g. ``_step_paged_impl``)."""
        return sum(e.end - e.start for e in self.modules
                   if part in e.name) / 1e9 / max(self.devices, 1)

    def module_runs(self, part: str) -> int:
        return sum(1 for e in self.modules if part in e.name) \
            // max(self.devices, 1)

    def op_s(self, prefix: str, module: str | None = None) -> float:
        """Device seconds of leaf ops named ``prefix``..., optionally only
        those run inside programs whose name holds ``module``."""
        return sum(e.end - e.start for e in self.ops
                   if e.leaf and e.name.startswith(prefix)
                   and (module is None or module in e.module)) / 1e9 \
            / max(self.devices, 1)

    def top_ops(self, n: int = 10) -> list[list]:
        tot = defaultdict(int)
        for e in self.ops:
            if e.leaf:
                tot[op_family(e.name)] += e.end - e.start
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / max(self.devices, 1)] for k, v in ranked]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest gaps with no device op, each labelled with the
        innermost host span open at its middle."""
        spans = sorted((e.start, e.end) for e in self.ops)
        gaps, cur = [], self.window[0]
        for s, e in spans:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            gaps.append((cur, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_label((a + b) // 2), (b - a) / 1e9]
                for a, b in gaps[:n]]

    def host_label(self, t: int) -> str:
        best = None
        for e in self.host:
            if e.start <= t < e.end and e.name != WINDOW and (
                    best is None or e.end - e.start < best.end - best.start):
                best = e
        return best.name if best else "none"


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``; kernels keep their given name."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _mark_leaves(ops: list[Event]) -> None:
    """An op that holds the start of the next one (in start order, the
    longer first) holds a nested op: it is not a leaf."""
    ops.sort(key=lambda e: (e.start, -e.end))
    for a, b in zip(ops, ops[1:]):
        if b.start < a.end:
            a.leaf = False


def union_ns(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(dataclasses.replace(e, start=s, end=t))
    return out


def _module_of(ops: list[Event], modules: list[Event]) -> None:
    """Ops without an ``hlo_module`` stat take the program run that
    holds them in time."""
    mods = sorted(modules, key=lambda m: m.start)
    j = 0
    for op in sorted(ops, key=lambda o: o.start):
        if op.module:
            continue
        while j < len(mods) and mods[j].end <= op.start:
            j += 1
        if j < len(mods) and mods[j].start <= op.start:
            op.module = mods[j].name


def reduce(planes) -> Reduced:
    """``planes``: ``ProfileData(...).planes`` (or equivalent objects with
    ``name``, ``lines``, and events with ``name``/``start_ns``/
    ``duration_ns``/``stats``)."""
    host, dev_ops, dev_mods, devices = [], [], [], set()
    for plane in planes:
        is_dev = plane.name.startswith(DEVICE)
        for line in plane.lines:
            if is_dev and line.name not in (MODULES, OPS):
                continue
            plane_ops = []
            for ev in line.events:
                s = int(ev.start_ns)
                e = Event(ev.name, s, s + int(ev.duration_ns))
                if not is_dev:
                    if ev.duration_ns > 0:
                        host.append(e)
                elif line.name == MODULES:
                    dev_mods.append(e)
                else:
                    e.name = op_name(ev.name)
                    e.module = str(_stat(ev, "hlo_module") or "")
                    plane_ops.append(e)
            if is_dev:
                devices.add(plane.name)
                _mark_leaves(plane_ops)
                dev_ops += plane_ops
    wins = [e for e in host if e.name == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = wins[0].start, wins[0].end
    ops, mods = _clip(dev_ops, lo, hi), _clip(dev_mods, lo, hi)
    _module_of(ops, mods)
    return Reduced(window=(lo, hi), devices=len(devices), ops=ops,
                   modules=mods, host=_clip(host, lo, hi))


def load(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(ProfileData.from_file(sorted(paths)[-1]).planes)
