"""The decode span's counts for sliding-window and MoE layers: while a
trace records, ``window_pages`` (the table entries the windowed layers
skip), ``experts_hit`` and ``expert_rows_max`` (the step program's routing
counts, summed over the layers) ride on each ``decode`` span, on the
profiler's host plane and in an enabled tracer's Chrome JSON; with no
trace recording, the engine fetches no counts."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.models import transformer
from repro.obs import Observability
from repro.obs.profile import xprof_capture
from repro.serve import EngineConfig, PagedConfig, RequestParams, Server

CFG = configs.smoke("mellum2-12b-a2.5b")      # window 4, 3 of 4 layers
PAGE, PROMPTS, MAX_NEW = 4, (9, 6), 5


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _drive(params, obs=None):
    server = Server(CFG, params,
                    EngineConfig(max_len=32, kv_bits=8, kv_group=16,
                                 backend="ref"),
                    PagedConfig(max_slots=2, page_size=PAGE, n_pages=24,
                                max_context=32), seed=0, obs=obs)
    rng = np.random.default_rng(3)
    for n in PROMPTS:
        server.submit(list(map(int, rng.integers(0, 256, size=n))),
                      RequestParams(max_new_tokens=MAX_NEW))
    server.drain()
    return server


def _decode_args(trace_dir) -> list:
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    return [dict(ev.stats) for plane in ProfileData.from_file(
        str(path)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name == "decode"]


def _check(args: list):
    assert len(args) == MAX_NEW - 1
    local = sum(1 for i in range(CFG.n_layers)
                if CFG.layer_spec(i)[0] == "attn_local")
    for a in args:
        # every MoE layer routes each of the 2 slots' tokens to top_k
        # experts: at least top_k hit, groups of 1 to 2 rows
        assert CFG.n_layers * CFG.top_k <= int(a["experts_hit"]) \
            <= CFG.n_layers * 2 * CFG.top_k
        assert CFG.n_layers <= int(a["expert_rows_max"]) <= CFG.n_layers * 2
    # step j: slot positions 9 + j and 6 + j; entries before the window's
    # first live page, max(pos - window + 1, 0) // page, in each of the
    # windowed layers
    want = [local * sum(max(p + j - CFG.window + 1, 0) // PAGE
                        for p in PROMPTS) for j in range(MAX_NEW - 1)]
    assert [int(a["window_pages"]) for a in args] == want
    assert max(want) > 0


@pytest.mark.parametrize("enabled", [False, True], ids=["noop", "enabled"])
def test_decode_spans_carry_window_and_expert_counts(params, tmp_path,
                                                     enabled):
    obs = Observability() if enabled else None
    with xprof_capture(str(tmp_path)):
        _drive(params, obs)
    _check(_decode_args(tmp_path))
    if enabled:
        _check([e["args"] for e in obs.tracer.events
                if e["name"] == "decode"])


def test_no_counts_fetched_unless_recording(params):
    server = _drive(params)
    assert server.scheduler.engine.step_counts == {}
