"""The trace reduction on a small recorded-shape trace: busy union,
per-program time, kernel time, idle gaps and their host labels."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import bench_tiny  # noqa: F401

from bench import trace


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def op(name, start, dur, text=" = f32[8] fusion(...)", **stats):
    return ev(f"%{name}{text}", start, dur, **stats)


def planes():
    """The shape of a TPU trace: host annotations; per device a line of
    program runs, a line of ops (named by their HLO text, a ``while``
    holding the ops of its body) and a line of async copies."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 1000, 10000),
        ev("bench.step", 1500, 3900),
        ev("decode_step", 1600, 300),
        ev("bench.step", 5500, 3000),
        ev("prefill", 5600, 200),
        ev("bench.wait", 8600, 2300),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__step_paged_impl(3)", 2000, 3000),
            ev("jit__prefill_paged_impl(4)", 6000, 2000),
            ev("jit__step_paged_impl(3)", 200, 300),      # before the window
        ]),
        NS(name="XLA Ops", events=[
            op("while.6", 2000, 2500, " = (s32[]) while(...)"),
            op("quant_matmul_b4g128.1", 2000, 1000, " = bf16[32,2048] "
               "custom-call(...)"),
            op("paged_attention_lut_b4.2", 3000, 1500),
            op("fusion.3", 4500, 400),
            op("quant_matmul_b4g128.4", 6000, 1500,
               hlo_module="jit__prefill_paged_impl"),
            op("copy.5", 7500, 500),
            op("fusion.9", 200, 300),                      # before the window
            op("fusion.7", 10500, 1000),                   # cut at 11000
        ]),
        NS(name="Async XLA Ops", events=[op("copy-start.1", 1000, 9000)]),
    ])
    return [host, dev, NS(name="/device:CUSTOM:Megascale Trace", lines=[])]


@pytest.fixture
def red():
    return trace.reduce(planes())


def test_window_and_busy(red):
    assert red.window == (1000, 11000)
    assert red.window_s == pytest.approx(1e-5)
    # 2000-4900, 6000-8000, 10500-11000
    assert red.busy_s() == pytest.approx((2900 + 2000 + 500) / 1e9)
    assert red.devices == 1


def test_program_and_kernel_time(red):
    assert red.module_runs("_step_paged_impl") == 1
    assert red.module_s("_step_paged_impl") == pytest.approx(3e-6)
    assert red.module_s("_prefill_paged_impl") == pytest.approx(2e-6)
    assert red.op_s("quant_matmul_b", "_step_paged_impl") == pytest.approx(1e-6)
    assert red.op_s("quant_matmul_b", "_prefill_paged_impl") == \
        pytest.approx(1.5e-6)
    assert red.op_s("paged_attention_", "_step_paged_impl") == \
        pytest.approx(1.5e-6)
    # leaf ops only: the while that holds the layer body is not counted
    assert red.op_s("", "_step_paged_impl") == pytest.approx(2.9e-6)
    assert red.op_s("copy") == pytest.approx(5e-7)
    assert red.op_s("while") == 0


def test_top_ops_by_family(red):
    top = dict(red.top_ops())
    assert top["quant_matmul_b4g128"] == pytest.approx(2.5e-6)
    assert top["fusion"] == pytest.approx(9e-7)
    assert list(top)[0] == "quant_matmul_b4g128"


def test_idle_gaps_labelled_by_host_span(red):
    gaps = red.idle_gaps()
    # 8000-10500 (bench.wait), 1000-2000 (bench.step), 4900-6000 (none
    # open at 5450: between the two steps)
    assert gaps[0] == ["bench.wait", pytest.approx(2.5e-6)]
    labels = {label for label, _ in gaps}
    assert {"bench.wait", "bench.step", "none"} <= labels
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s())


def test_union_of_overlapping_intervals():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (29, 31)]) == 26
    assert trace.union_ns([]) == 0


def test_op_names_and_families():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert trace.op_name("fusion.12") == "fusion.12"
    assert trace.op_family("fusion.12") == "fusion"
    assert trace.op_family("quant_matmul_b4g128") == "quant_matmul_b4g128"


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace.reduce(p)
