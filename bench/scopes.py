"""The trace reduction of ``bench/trace.py``, with what the program's own
spans and scopes add: host spans keep their args, device ops their scope
and the bytes of their result.

Every ``repro.obs`` span is a profiler TraceMe, so the serving loop's
spans (``step``, ``admit``, ``prefill``, ``decode``, ``decode_step``,
``fetch``, ``fetch.ready``, ``fetch.to_host``, ``emit``) sit on the host
plane beside the benchmark's own, their args (``admit``'s ``rid``,
``prompt_len``, ``queued_ms``; ``decode``'s ``step``, ``n_slots``,
``live_tokens``; ``emit``'s ``tokens``) as the event's stats.  Inside the
programs, ``jax.named_scope`` names each layer kind (:data:`SCOPES`); on
a TPU an op's ``op_name`` path is the ``tf_op`` stat of its event's
metadata (``bench/xplane.py``), and its scope is the innermost of those
names on the path.  An op with no ``op_name`` (a copy XLA inserted) takes
the scope of its first operand, named in its own HLO text.

:func:`idle_by_span` and :func:`idle_share` label the device's idle time
with the program spans open over it and need only span names, so they
read ``bench/trace.py``'s reduction as well as this one.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict

from bench import hlo, trace, xplane

# the layer-kind scopes of the model and engine code, and the layer
# scan's own ops (slicing each layer out of the stack, stacking the new
# cache)
SCOPES = ("norm", "qkv", "kv_write", "attention", "attn_out", "ffn",
          "lm_head", "sample", "layer_scan")
# the label of an op inside the scan's loop body that no layer-kind scope
# holds and that is not one of the scan's own (the residual adds, the
# cache plumbing between the layer's pieces)
LAYER_BODY = "layer_body"
# what jax.lax.scan itself puts in its loop body, at the body's top
# level: each layer's slice of the stacked weights and cache, and the
# layer's outputs stacked back
SCAN_OWN = ("dynamic_slice", "dynamic_update_slice", "squeeze",
            "broadcast_in_dim", "reshape")
# the device op stat that holds its op_name path, ``jit(f)/scope/op:``
OP_NAME_STAT = "tf_op"
FETCH = ("fetch", "fetch.ready", "fetch.to_host")
# the spans the program and the benchmark open on the host; the
# runtime's own TraceMes are left out of the labelling
PROGRAM_SPANS = ("step", "admit", "prefill", "decode", "decode_step",
                 "emit", "draft", "verify") + FETCH
BENCH_SPANS = ("bench.step", "bench.submit", "bench.wait")

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


@dataclasses.dataclass
class Op(trace.Event):
    scope: str = ""
    out_bytes: int = 0      # of the result shape in its HLO text
    accessed: int = 0       # bytes_accessed, as the compiler counts them
    path: str = ""          # op_name path; "" where the op has none
    operand: str = ""       # first operand's name, in the op's HLO text


@dataclasses.dataclass
class Span(trace.Event):
    args: dict = dataclasses.field(default_factory=dict)


def scope_of(path: str) -> str:
    """The innermost of :data:`SCOPES` on an ``op_name`` path, else
    ``""``.  Under ``layer_scan``, an op inside the loop body
    (``while/body/...``) that is not one of :data:`SCAN_OWN` at the
    body's top level is :data:`LAYER_BODY`, not the scan's."""
    parts = [p.rstrip(":") for p in path.split("/")]
    best, at = "", 0
    for i, part in enumerate(parts):
        if part in SCOPES:
            best, at = part, i
    rest = parts[at + 1:]
    if best == "layer_scan" and rest[:2] == ["while", "body"] \
            and rest[2:] and not (len(rest) == 3 and rest[2] in SCAN_OWN):
        return LAYER_BODY
    return best


def result_bytes(text: str) -> int:
    """Bytes of the result shape in an op's HLO text
    (``%copy.5 = f32[8,4]{1,0} copy(%p)`` -> 128); a tuple sums its
    arrays."""
    if " = " not in text:
        return 0
    rhs = text.split(" = ", 1)[1]
    depth, end = 0, len(rhs)
    for i, ch in enumerate(rhs):      # the shape ends at the first space
        if ch in "({[":               # outside brackets
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    total = 0.0
    for dtype, dims in _ARRAY.findall(rhs[:end]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES.get(dtype, 0)
    return int(total)


def first_operand(text: str) -> str:
    """``%copy.5 = f32[8] copy(f32[8] %fusion.3), ...`` -> ``fusion.3``
    (a result shape holds no ``%``)."""
    if " = " not in text:
        return ""
    m = re.search(r"%([\w.-]+)", text.split(" = ", 1)[1])
    return m.group(1) if m else ""


@dataclasses.dataclass
class Scoped(trace.Reduced):
    """:class:`bench.trace.Reduced` whose ops are :class:`Op` and whose
    host spans are :class:`Span`; ``meta``: the device planes' metadata
    stats it read (:func:`bench.xplane.metadata_stats`); ``hlo``: the
    compiled text of each program (:func:`bench.xplane.hlo_modules`)."""
    meta: dict = dataclasses.field(default_factory=dict)
    hlo: dict = dataclasses.field(default_factory=dict)

    def scope_s(self, scope: str, module: str | None = None) -> float:
        """Device seconds of leaf ops under ``scope``, optionally only
        in programs whose name holds ``module``."""
        return self.by_scope(module).get(scope, 0.0)

    def by_scope(self, module: str | None = None,
                 family: str | None = None) -> dict[str, float]:
        """Leaf-op seconds by scope (``""``: none), optionally of one
        program and one op family (``copy``)."""
        tot = defaultdict(int)
        for e in self._leaves(module, family):
            tot[e.scope] += e.end - e.start
        return {k: v / 1e9 / max(self.devices, 1) for k, v in tot.items()}

    def bytes_by_scope(self, module: str | None = None,
                       family: str | None = None,
                       what: str = "out_bytes") -> dict[str, int]:
        """Bytes of leaf ops by scope, per device: ``out_bytes`` (their
        results) or ``accessed`` (what they read and write)."""
        tot = defaultdict(int)
        for e in self._leaves(module, family):
            tot[e.scope] += getattr(e, what)
        return {k: v // max(self.devices, 1) for k, v in tot.items()}

    def _leaves(self, module, family):
        return [e for e in self.ops if e.leaf
                and (module is None or module in e.module)
                and (family is None or trace.op_family(e.name) == family)]

    def copies_by_consumer(self, module: str) -> dict[str, list]:
        """Leaf ``copy`` ops of the programs whose name holds ``module``,
        by where their data comes from and goes to:
        ``"<named|nameless> <scope> -> <consumer>"`` (consumer by
        :func:`bench.hlo.copy_consumers` on the program's text, ``?``
        where the text lacks the copy) -> ``[seconds, result bytes,
        runs]``, per device."""
        consumers, tot = {}, defaultdict(lambda: [0, 0, 0])
        for e in self._leaves(module, "copy"):
            if e.module not in consumers:
                text = self._program_text(e.module)
                consumers[e.module] = hlo.copy_consumers(text) if text \
                    else {}
            to = consumers[e.module].get(e.name, "?")
            key = (f"{'named' if e.path else 'nameless'} "
                   f"{e.scope or '-'} -> {to}")
            row = tot[key]
            row[0] += e.end - e.start
            row[1] += e.out_bytes
            row[2] += 1
        n = max(self.devices, 1)
        return {k: [v[0] / 1e9 / n, v[1] // n, v[2] // n]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1][0])}

    def _program_text(self, module: str) -> str:
        """The compiled text of the program named ``module``, else of the
        one program of the same name before its ``(id)``, else ``""``."""
        if module in self.hlo:
            return self.hlo[module]
        head = module.split("(")[0]
        same = [t for k, t in self.hlo.items() if k.split("(")[0] == head]
        return same[0] if len(same) == 1 else ""

    def spans(self, name: str) -> list[Span]:
        return [e for e in self.host if e.name == name]

    def children(self, span: Span, name: str) -> list[Span]:
        return [e for e in self.host if e.name == name
                and span.start <= e.start and e.end <= span.end]

    def busy_in(self, lo: int, hi: int) -> float:
        """Seconds of [lo, hi) in which an op ran on the device."""
        return trace.union_ns([(max(e.start, lo), min(e.end, hi))
                               for e in self.ops
                               if e.end > lo and e.start < hi]) / 1e9 \
            / max(self.devices, 1)


def _resolve_scopes(ops: list[Op]) -> None:
    """An op without an ``op_name`` takes its first operand's scope,
    within its program, through chains of such ops (the ``copy-done`` of
    a ``copy-start`` of a copy)."""
    known = {(op.module, op.name): op.scope for op in ops if op.path}
    for _ in range(4):
        for op in ops:
            if not op.path and (op.module, op.operand) in known:
                op.scope = known[(op.module, op.operand)]
                known[(op.module, op.name)] = op.scope


def reduce(planes, meta: dict | None = None,
           hlo_text: dict | None = None) -> Scoped:
    """As :func:`bench.trace.reduce`, keeping span args and op scopes.
    ``meta``: :func:`bench.xplane.metadata_stats` of the same trace, whose
    stats join each device op's own; ``hlo_text``: its
    :func:`bench.xplane.hlo_modules`."""
    host, dev_ops, dev_mods, devices = [], [], [], set()
    for plane in planes:
        is_dev = plane.name.startswith(trace.DEVICE)
        op_meta = (meta or {}).get(plane.name, {})
        for line in plane.lines:
            if is_dev and line.name not in (trace.MODULES, trace.OPS):
                continue
            plane_ops = []
            for ev in line.events:
                s = int(ev.start_ns)
                end = s + int(ev.duration_ns)
                stats = dict(ev.stats)
                if not is_dev:
                    if ev.duration_ns > 0:
                        host.append(Span(ev.name, s, end, args=stats))
                elif line.name == trace.MODULES:
                    dev_mods.append(trace.Event(ev.name, s, end))
                else:
                    stats.update(op_meta.get(ev.name, {}))
                    path = str(stats.get(OP_NAME_STAT) or "")
                    plane_ops.append(Op(
                        trace.op_name(ev.name), s, end,
                        module=str(stats.get("hlo_module") or ""),
                        scope=scope_of(path), out_bytes=result_bytes(ev.name),
                        accessed=int(stats.get("bytes_accessed") or 0),
                        path=path, operand=first_operand(ev.name)))
            if is_dev:
                devices.add(plane.name)
                trace._mark_leaves(plane_ops)
                dev_ops += plane_ops
    wins = [e for e in host if e.name == trace.WINDOW]
    if not wins:
        raise ValueError(f"no {trace.WINDOW!r} span in the trace")
    lo, hi = wins[0].start, wins[0].end
    ops, mods = trace._clip(dev_ops, lo, hi), trace._clip(dev_mods, lo, hi)
    trace._module_of(ops, mods)
    _resolve_scopes(ops)
    return Scoped(window=(lo, hi), devices=len(devices), ops=ops,
                  modules=mods, host=trace._clip(host, lo, hi),
                  meta=meta or {}, hlo=hlo_text or {})


def load(trace_dir: str) -> Scoped:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(sorted(paths)[-1], "rb") as f:
        data = f.read()
    return reduce(ProfileData.from_serialized_xspace(data).planes,
                  xplane.metadata_stats(data, trace.DEVICE),
                  xplane.hlo_modules(data))


# ---------------------------------------------------------------- idle

def _idle(red) -> list[tuple[int, int]]:
    """The window's intervals with no device op, in order."""
    out, cur = [], red.window[0]
    for s, e in sorted((e.start, e.end) for e in red.ops):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < red.window[1]:
        out.append((cur, red.window[1]))
    return out


def _segments(red, names):
    """The window cut where a span of ``names`` opens or shuts:
    ``[(start, end, open spans' names, innermost first)]``."""
    spans = [e for e in red.host if e.name in names]
    marks = sorted([(e.end, 0, i) for i, e in enumerate(spans)]
                   + [(e.start, 1, i) for i, e in enumerate(spans)])
    out, open_, prev = [], set(), red.window[0]
    for t, opens, i in marks:
        if t > prev:
            out.append((prev, t, open_))
            prev = t
        open_ = open_ | {i} if opens else open_ - {i}
    out.append((prev, red.window[1], open_))
    return [(a, b, tuple(spans[i].name for i in sorted(
                open_, key=lambda i: (-spans[i].start,
                                      spans[i].end - spans[i].start))))
            for a, b, open_ in out if b > a]


def idle_pieces(red, names=PROGRAM_SPANS + BENCH_SPANS):
    """The device's idle time cut where a span of ``names`` opens or
    shuts: ``[(ns, open spans' names, innermost first)]``."""
    segs, out, j = _segments(red, names), [], 0
    for a, b in _idle(red):
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                out.append((hi - lo, segs[k][2]))
            k += 1
    return out


def idle_by_span(red, names=PROGRAM_SPANS + BENCH_SPANS) -> dict:
    """Seconds with no op on any device, by the innermost span of
    ``names`` open over them (``"none"``: no such span)."""
    tot = defaultdict(int)
    for ns, open_ in idle_pieces(red, names):
        tot[open_[0] if open_ else "none"] += ns
    return {k: v / 1e9 for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])}


def idle_share(red, pick) -> float | None:
    """Share (%) of the window that is idle while ``pick(open spans)``
    holds; None where the trace has no program span at all."""
    if not any(e.name in PROGRAM_SPANS for e in red.host):
        return None
    ns = sum(n for n, open_ in idle_pieces(red) if pick(open_))
    return 100.0 * ns / 1e9 / red.window_s
