"""The scoped trace reduction (``bench/scopes.py``, ``bench/xplane.py``)
and the readers of the program's spans and scopes, on a small
recorded-shape trace: host spans keep their args, ops the scope of the
``tf_op`` stat of their metadata (a copy with none takes its operand's)
and their bytes; idle time is labelled by the innermost program span.
The reduction's existing numbers stay what ``bench/trace.py`` gives, and
on a tiny cell the counts the program's spans carry equal what the
harness counts from its callbacks."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import bench_tiny
import test_bench_trace as base

from bench import hlo, run as R, scope_run, scopes, trace, xplane

METRICS = Path(bench_tiny.ROOT) / "bench" / "metrics"
PATH = "jit(_step_paged_impl)/paged_decode_step/layer_scan/while/body/" \
    "closed_call/"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


ev, op = base.ev, base.op


# each op's metadata stats as a TPU trace holds them (bench/xplane.py):
# its op_name path as tf_op, ``jit(f)/.../op:``, and bytes_accessed
TF_OP = {
    "while.6": PATH[:-len("/body/closed_call/")] + ":",
    "quant_matmul_b4g128.1": PATH + "qkv/quant_matmul:",
    "paged_attention_lut_b4.2": PATH + "attention/paged_attention:",
    "fusion.3": PATH + "kv_write/scatter:",
    "quant_matmul_b4g128.4": "jit(_prefill_paged_impl)/prefill/layer_scan/"
                             "while/body/closed_call/ffn/quant_matmul:",
}


def meta(ps):
    """The metadata stats of ``ps``'s device ops, keyed as
    :func:`bench.xplane.metadata_stats` keys them."""
    out = {}
    for plane in ps:
        for line in plane.lines:
            for e in line.events:
                name = trace.op_name(e.name)
                if plane.name.startswith(trace.DEVICE) and name in TF_OP:
                    out.setdefault(plane.name, {})[e.name] = {
                        "tf_op": TF_OP[name], "bytes_accessed": 1000}
    return out


def planes():
    """``test_bench_trace.planes()`` with the program's spans: a decode
    step (dispatch, fetch, emit) and an admission (prefill and its fetch),
    and ops whose metadata carries their op_name path, one copy without
    one."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 1000, 10000),
        ev("bench.step", 1500, 3900),
        ev("step", 1550, 3800),
        ev("decode", 1580, 3720, step=0, n_slots=2, live_tokens=300),
        ev("decode_step", 1600, 300),
        ev("fetch", 1900, 3360),
        ev("fetch.ready", 1900, 3100),
        ev("fetch.to_host", 5000, 250),
        ev("emit", 5300, 40, tokens=2),
        ev("bench.step", 5500, 3000),
        ev("step", 5520, 2930),
        ev("admit", 5550, 2750, rid=7, prompt_len=40, queued_ms=2.5),
        ev("prefill", 5600, 2600, n_tokens=40),
        ev("fetch", 5800, 2400),
        ev("fetch.ready", 5800, 2250),
        ev("fetch.to_host", 8050, 150),
        ev("bench.wait", 8600, 2300),
        ev("np.asarray", 8060, 100),           # a runtime TraceMe
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__step_paged_impl(3)", 2000, 3000),
            ev("jit__prefill_paged_impl(4)", 6000, 2000),
            ev("jit__step_paged_impl(3)", 200, 300),
        ]),
        NS(name="XLA Ops", events=[
            op("while.6", 2000, 2500, " = (s32[]) while(...)"),
            op("quant_matmul_b4g128.1", 2000, 1000, " = bf16[32,2048] "
               "custom-call(...)"),
            op("paged_attention_lut_b4.2", 3000, 1500),
            op("fusion.3", 4500, 200, " = f32[4097,16,8,4]{3,2,1,0} "
               "fusion(u8[4097,16,8,32]{3,2,1,0} %param.1)"),
            op("copy.11", 4700, 200, " = f32[4097,16,8,4]{3,2,1,0} "
               "copy(f32[4097,16,8,4]{3,2,1,0:T(4,128)} %fusion.3)"),
            op("quant_matmul_b4g128.4", 6000, 1500,
               hlo_module="jit__prefill_paged_impl"),
            op("copy.5", 7500, 500, " = u8[16,32]{1,0} copy(%param.2)",
               hlo_module="jit__prefill_paged_impl"),
            op("fusion.9", 200, 300),
            op("fusion.7", 10500, 1000),
        ]),
        NS(name="Async XLA Ops", events=[op("copy-start.1", 1000, 9000)]),
    ])
    return [host, dev, NS(name="/device:CUSTOM:Megascale Trace", lines=[])]


@pytest.fixture
def red():
    ps = planes()
    return scopes.reduce(ps, meta(ps))


def ctx_of(red):
    return R.Context(red, [], {}, {}, {}, None)


def test_existing_numbers_unchanged():
    """On the recorded shape of ``test_bench_trace``, the scoped reduction
    gives every number ``bench/trace.py`` gives."""
    a, b = trace.reduce(base.planes()), scopes.reduce(base.planes())
    assert (a.window, a.devices) == (b.window, b.devices)
    assert a.busy_s() == b.busy_s()
    for part in ("_step_paged_impl", "_prefill_paged_impl"):
        assert a.module_s(part) == b.module_s(part)
        assert a.module_runs(part) == b.module_runs(part)
        for prefix in ("", "quant_matmul_b", "paged_attention_", "copy"):
            assert a.op_s(prefix, part) == b.op_s(prefix, part)
    assert a.top_ops() == b.top_ops()
    assert a.idle_gaps() == b.idle_gaps()


def test_span_args_and_op_scopes(red):
    (admit,) = red.spans("admit")
    assert admit.args == {"rid": 7, "prompt_len": 40, "queued_ms": 2.5}
    (decode,) = red.spans("decode")
    assert decode.args["live_tokens"] == 300
    scope = {e.name: e.scope for e in red.ops}
    assert scope["quant_matmul_b4g128.1"] == "qkv"
    assert scope["paged_attention_lut_b4.2"] == "attention"
    assert scope["while.6"] == "layer_scan"
    assert scope["copy.11"] == "kv_write"       # its operand's, fusion.3
    assert scope["copy.5"] == ""                # its operand ran no op
    assert scope["fusion.7"] == ""


def test_scope_time_and_copy_bytes(red):
    dec = "_step_paged_impl"
    assert red.by_scope(dec) == pytest.approx(
        {"qkv": 1e-6, "attention": 1.5e-6, "kv_write": 4e-7})
    assert red.scope_s("ffn", "_prefill_paged_impl") == pytest.approx(1.5e-6)
    assert red.by_scope(dec, "copy") == pytest.approx({"kv_write": 2e-7})
    assert red.bytes_by_scope(dec, "copy") == {"kv_write": 4097 * 16 * 8 * 16}
    assert red.bytes_by_scope("_prefill_paged_impl", "copy") == {"": 512}
    assert red.bytes_by_scope(dec, what="accessed") == {
        "qkv": 1000, "attention": 1000, "kv_write": 1000}


def test_result_bytes_and_operands():
    assert scopes.result_bytes("%c.1 = bf16[32,2048]{1,0:T(8,128)} copy(%a)") \
        == 32 * 2048 * 2
    assert scopes.result_bytes(
        "%s.2 = (f32[8]{0}, u32[]{:S(2)}) copy-start(%a)") == 36
    assert scopes.first_operand("%c.1 = f32[8] copy(%fusion.3), x=y") \
        == "fusion.3"
    assert scopes.first_operand(
        "%d = f32[8]{0} copy-done((f32[8]{0}, u32[]) %copy-start.2)") \
        == "copy-start.2"
    assert scopes.scope_of(PATH + "attention/jit(paged_attention)/x") \
        == "attention"
    assert scopes.scope_of("jit(f)/paged_decode_step/while") == ""


SCAN = "jit(_step_paged_impl)/paged_decode_step/layer_scan/"


@pytest.mark.parametrize("path,scope", [
    (SCAN + "while", "layer_scan"),                     # the loop itself
    (SCAN + "while/cond/lt", "layer_scan"),
    (SCAN + "while/body/dynamic_slice", "layer_scan"),  # a layer's slice
    (SCAN + "while/body/dynamic_update_slice:", "layer_scan"),   # stacking
    (SCAN + "while/body/closed_call", "layer_body"),
    (SCAN + "while/body/closed_call/add", "layer_body"),         # residual
    (SCAN + "while/body/add", "layer_body"),
    (SCAN + "while/body/closed_call/dynamic_slice", "layer_body"),
    (SCAN + "while/body/closed_call/kv_write/scatter:", "kv_write"),
    ("jit(f)/segment0/layer_scan/while/body/closed_call/ffn/dot_general",
     "ffn"),
])
def test_scan_body_ops_without_a_scope_are_the_layers(path, scope):
    """Inside the scan's loop body, an op with no layer-kind scope is the
    layer's own (``layer_body``) unless it is what the scan itself puts at
    the body's top level."""
    assert scopes.scope_of(path) == scope


# a compiled decode program's text, cut to the copies' paths: a layer's
# slice relayouted for the scatter, the scatter's result relayouted for
# the kernel, the layer's pages relayouted for the scan's stacking, a
# carried scalar, and a copy the program returns
HLO = """HloModule jit__step_paged_impl, entry_computation_layout={()}

%fused_slice (p0: f32[2,64,4], p1: s32[]) -> f32[1,64,4] {
  %p0 = f32[2,64,4]{2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %ds = f32[1,64,4]{2,1,0} dynamic-slice(%p0, %p1), dynamic_slice_sizes={1,64,4}
}

%fused_scatter (q0: f32[64,4], q1: f32[8,4]) -> f32[64,4] {
  %q0 = f32[64,4]{1,0} parameter(0)
  %q1 = f32[8,4]{1,0} parameter(1)
  ROOT %sc = f32[64,4]{1,0} scatter(%q0, %q1), to_apply=%add
}

%fused_dus (r0: f32[2,64,4], r1: f32[1,64,4]) -> f32[2,64,4] {
  %r0 = f32[2,64,4]{2,1,0} parameter(0)
  %r1 = f32[1,64,4]{2,1,0} parameter(1)
  ROOT %dus = f32[2,64,4]{2,1,0} dynamic-update-slice(%r0, %r1), metadata={op_name="x"}
}

%body (arg: (s32[], f32[2,64,4], f32[2,64,4])) -> (s32[], f32[2,64,4], f32[2,64,4]) {
  %arg = (s32[], f32[2,64,4], f32[2,64,4]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %stack = f32[2,64,4]{2,1,0} get-tuple-element(%arg), index=1
  %out = f32[2,64,4]{2,1,0} get-tuple-element(%arg), index=2
  %slice = f32[1,64,4]{2,1,0} fusion(%stack, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="SCANwhile/body/dynamic_slice"}
  %copy.1 = f32[1,64,4]{1,2,0:T(4,128)} copy(%slice), metadata={op_name="SCANwhile/body/dynamic_slice"}
  %bitcast.1 = f32[64,4]{0,1:T(4,128)} bitcast(%copy.1)
  %fusion.2 = f32[64,4]{0,1:T(4,128)} fusion(%bitcast.1, %bitcast.1), kind=kLoop, calls=%fused_scatter, metadata={op_name="SCANwhile/body/closed_call/kv_write/scatter"}
  %copy.2 = f32[64,4]{1,0:T(8,128)} copy(%fusion.2), metadata={op_name="SCANwhile/body/closed_call/kv_write/scatter"}
  %paged_attention_lut_b4.3 = f32[8,4]{1,0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="SCANwhile/body/closed_call/attention/jit(paged_attention)/pallas_call"}
  %bitcast.4 = f32[1,64,4]{2,1,0:T(4,128)} bitcast(%fusion.2)
  %copy.4 = f32[1,64,4]{2,1,0} copy(%bitcast.4), metadata={op_name="SCANwhile/body/dynamic_update_slice"}
  %fusion.5 = f32[2,64,4]{2,1,0} fusion(%out, %copy.4), kind=kLoop, calls=%fused_dus, metadata={op_name="SCANwhile/body/dynamic_update_slice"}
  %copy.6 = s32[] copy(%i)
  ROOT %tuple.7 = (s32[], f32[2,64,4], f32[2,64,4]) tuple(%copy.6, %stack, %fusion.5)
}

ENTRY %main (a: f32[2,64,4]) -> (f32[2,64,4]) {
  %a = f32[2,64,4]{2,1,0} parameter(0)
  %w = (s32[], f32[2,64,4], f32[2,64,4]) while(%a), condition=%cond, body=%body
  %gte = f32[2,64,4]{2,1,0} get-tuple-element(%w), index=2
  %copy.9 = f32[2,64,4]{1,2,0} copy(%gte)
  ROOT %t = (f32[2,64,4]) tuple(%copy.9)
}
""".replace("SCAN", SCAN)


def test_copy_consumers():
    """Each copy is labelled by what its result feeds, through bitcasts,
    tuple plumbing and the loop's carry."""
    assert hlo.copy_consumers(HLO) == {
        "copy.1": "scatter@kv_write",
        "copy.2": "paged_attention_lut_b4@attention",
        "copy.4": "dynamic-update-slice@layer_scan",
        "copy.6": "loop-carry",
        "copy.9": "output",
    }
    insts = hlo.parse(HLO)
    assert insts["copy.1"].path == SCAN + "while/body/dynamic_slice"
    assert insts["copy.6"].path == "" and insts["copy.6"].operands == ["i"]
    assert insts["w"].body == "body" and insts["fusion.5"].calls == "fused_dus"


def test_copies_by_consumer(red):
    """The decode program's copies by scope and consumer, the consumer
    read from the program's text; a program without its text gives
    ``?``."""
    text = HLO.replace("copy.2 = f32[64,4]{1,0:T(8,128)} copy(%fusion.2)",
                       "copy.11 = f32[64,4]{1,0:T(8,128)} copy(%fusion.2)") \
              .replace("custom-call(%copy.2)", "custom-call(%copy.11)")
    red.hlo = {"jit__step_paged_impl(3)": text}
    assert red.copies_by_consumer("_step_paged_impl") == {
        "nameless kv_write -> paged_attention_lut_b4@attention":
            [pytest.approx(2e-7), 4097 * 16 * 8 * 16, 1]}
    assert red.copies_by_consumer("_prefill_paged_impl") == {
        "nameless - -> ?": [pytest.approx(5e-7), 512, 1]}
    # the program's name as the trace's host metadata gives it, another id
    red.hlo = {"jit__step_paged_impl(77)": text}
    assert list(red.copies_by_consumer("_step_paged_impl")) == [
        "nameless kv_write -> paged_attention_lut_b4@attention"]


def test_idle_by_innermost_span(red):
    idle = scopes.idle_by_span(red)
    # fetch.ready: 1900-2000, 4900-5000, 5800-6000, 8000-8050
    assert idle["fetch.ready"] == pytest.approx(450e-9)
    assert idle["fetch.to_host"] == pytest.approx(400e-9)
    assert idle["decode_step"] == pytest.approx(300e-9)
    assert idle["bench.wait"] == pytest.approx(1900e-9)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s())


def test_new_readers_by_hand(red):
    ctx = ctx_of(red)
    # fetch*: 100 + (100 + 250 + 10) + 200 + (50 + 150) of 10000 ns
    assert reader("fetch_idle_share.chat")(ctx) == pytest.approx(8.6)
    # in step, outside fetch*: 350 + 90 + 280 + 250 of 10000 ns
    assert reader("sched_idle_share.chat")(ctx) == pytest.approx(9.7)
    # kv_write: 400 of the decode program's 2900 ns of leaf ops
    assert reader("kv_write_share.decode")(ctx) == pytest.approx(
        100 * 400 / 2900)


def test_readers_give_nothing_without_the_programs_spans():
    """A program with neither spans nor scopes (the trace
    ``bench/trace.py`` reduces) gives none of the three."""
    for red in (trace.reduce(base.planes()), scopes.reduce(base.planes())):
        ctx = ctx_of(red)
        assert reader("fetch_idle_share.chat")(ctx) is None
        assert reader("sched_idle_share.chat")(ctx) is None
    assert reader("kv_write_share.decode")(
        ctx_of(trace.reduce(base.planes()))) is None


def test_counts_inside_equal_the_harness_counts(monkeypatch):
    """On a tiny chat cell, each ``admit``'s ``prompt_len`` and each
    ``decode``'s ``live_tokens`` equal what the harness's ``Book`` counted
    from the token callbacks: the prompts admitted and the live contexts
    advanced, step by step."""
    import jax
    seen = {}
    per_layer = R.per_layer

    def keep(cell, ctx):
        seen["ctx"] = ctx
        return per_layer(cell, ctx)

    monkeypatch.setattr(R, "per_layer", keep)
    cell = bench_tiny.tiny_cell()
    peak = json.loads((bench_tiny.ROOT / "bench" / "peaks.json")
                      .read_text())["TPU v5 lite"]
    res = scope_run.scoped_run(cell, 2200013001, 1.0,
                               device=R.device_record(jax.devices()),
                               peak=peak)
    red, steps = seen["ctx"].trace, seen["ctx"].steps
    assert res["correct"]
    admits = [e.args["prompt_len"] for e in red.spans("admit")]
    decodes = [e.args["live_tokens"] for e in red.spans("decode")]
    assert admits and decodes
    assert admits == [n for s in steps for n in s.prefills]
    assert decodes == [sum(s.contexts) for s in steps if s.contexts]
    assert res["metrics"]["fetch_idle_share.chat"]["value"] > 0


def test_hlo_modules_from_a_trace(tmp_path):
    """The profiler keeps each compiled program on its host metadata
    plane; its text names the instructions the device ops are named by,
    with their ``op_name`` paths."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("kv_write"):
            return (x.T @ x).sum(0)

    x = jnp.ones((16, 8))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    (path,) = tmp_path.rglob("*.xplane.pb")
    mods = xplane.hlo_modules(path.read_bytes())
    (text,) = [t for k, t in mods.items() if k.startswith("jit_f(")]
    insts = hlo.parse(text)
    assert any(i.opcode == "dot" and scopes.scope_of(i.path) == "kv_write"
               for i in insts.values())


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _f(num: int, payload) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_xplane_metadata_stats():
    """An ``XSpace`` written field by field as the profiler writes it: the
    device plane's event metadata stats come back by the event's name,
    stat names through the plane's stat metadata (a ref value too); the
    host plane is skipped."""
    text = "%copy.11 = f32[8]{0} copy(f32[8]{0} %fusion.3)"
    stat_meta = b"".join(_f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n)))
                         for i, n in ((1, b"tf_op"), (2, b"bytes_accessed"),
                                      (3, b"hlo_category"), (4, b"copy")))
    event = (_f(1, 7) + _f(2, text.encode())
             + _f(5, _f(1, 1) + _f(5, b"jit(f)/kv_write/scatter:"))
             + _f(5, _f(1, 2) + _f(4, 123)) + _f(5, _f(1, 3) + _f(7, 4)))
    device = (_f(1, 2) + _f(2, b"/device:TPU:0")
              + _f(3, _f(2, b"XLA Ops")) + _f(4, _f(1, 7) + _f(2, event))
              + stat_meta)
    host = _f(1, 1) + _f(2, b"/host:CPU") + _f(4, _f(1, 7) + _f(2, event))
    data = _f(1, host) + _f(1, device) + _f(4, b"a-host")
    assert xplane.metadata_stats(data, trace.DEVICE) == {
        "/device:TPU:0": {text: {"tf_op": "jit(f)/kv_write/scatter:",
                                 "bytes_accessed": 123,
                                 "hlo_category": "copy"}}}
