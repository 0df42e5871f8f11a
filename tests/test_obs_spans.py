"""The serving loop's spans on the profiler's clock: under a
``jax.profiler`` session, with observability off (``NOOP``) and on, the
scheduler's and engine's spans land on the host plane with their args as
the event's stats, the fetch of a program's tokens splits into waiting
and copying, the enabled tracer's Chrome JSON still passes ``obs.check``,
and the profiler changes neither the tokens nor the decode compilation
count."""
import jax
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs import Observability
from repro.obs.check import check_trace
from repro.obs.profile import xprof_capture
from repro.serve import EngineConfig, PagedConfig, RequestParams, Server

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")
PROMPTS = (5, 9, 3, 7)          # 4 requests behind 2 slots
MAX_NEW = 4


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _drive(params, obs=None, prompts=PROMPTS):
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24, max_context=32)
    server = Server(TINY, params, ecfg, pcfg, seed=0, obs=obs)
    rng = np.random.default_rng(5)
    rids = [server.submit(list(map(int, rng.integers(0, 256, size=n))),
                          RequestParams(max_new_tokens=MAX_NEW))
            for n in prompts]
    server.drain()
    return server, [server.output(r) for r in rids]


def _host_spans(trace_dir) -> list:
    """``[(name, start_ns, end_ns, stats)]`` of the host plane, in start
    order."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def plain(params):
    return _drive(params)[1]


@pytest.fixture(scope="module", params=[False, True],
                ids=["noop", "enabled"])
def captured(request, params, tmp_path_factory):
    obs = Observability() if request.param else None
    d = tmp_path_factory.mktemp("xprof")
    with xprof_capture(str(d)):
        server, out = _drive(params, obs)
    return {"obs": obs, "server": server, "out": out,
            "spans": _host_spans(d)}


def _named(captured, name):
    return [e for e in captured["spans"] if e[0] == name]


def test_serving_spans_on_host_plane(captured):
    names = {e[0] for e in captured["spans"]}
    assert {"step", "admit", "prefill", "decode", "decode_step", "fetch",
            "fetch.ready", "fetch.to_host", "emit"} <= names
    for _, _, _, a in _named(captured, "admit"):
        assert set(a) == {"rid", "prompt_len", "queued_ms"}
        assert a["queued_ms"] >= 0
    for _, _, _, a in _named(captured, "decode"):
        assert set(a) == {"step", "n_slots", "live_tokens", "live_pages"}
    for _, _, _, a in _named(captured, "emit"):
        assert set(a) == {"tokens"}
    assert all("n_tokens" in a for *_, a in _named(captured, "prefill"))


def test_spans_nest_under_step(captured):
    steps = _named(captured, "step")
    for name in ("admit", "decode", "emit", "fetch"):
        for _, s, e, _ in _named(captured, name):
            assert any(a <= s and e <= b for _, a, b, _ in steps), name


def test_fetch_splits_into_ready_then_to_host(captured):
    fetches = _named(captured, "fetch")
    # one per admission (prefill) and one per decode step
    assert len(fetches) == (len(_named(captured, "admit"))
                            + len(_named(captured, "decode")))
    for _, s, e, _ in fetches:
        kids = [(n, a) for n, a, b, _ in captured["spans"]
                if n.startswith("fetch.") and s <= a and b <= e]
        assert [n for n, _ in sorted(kids, key=lambda k: k[1])] == \
            ["fetch.ready", "fetch.to_host"]


def test_work_counts_inside(captured):
    """``admit.prompt_len`` is each admitted prompt; ``emit.tokens`` and
    ``decode.live_tokens`` add up to the tokens decoded and the contexts
    they attended over."""
    admits = _named(captured, "admit")
    assert sorted(a["prompt_len"] for *_, a in admits) == sorted(PROMPTS)
    assert sorted(a["rid"] for *_, a in admits) == list(range(len(PROMPTS)))
    emitted = sum(a["tokens"] for *_, a in _named(captured, "emit"))
    assert emitted == len(PROMPTS) * (MAX_NEW - 1)   # first tokens: admit
    # a request of prompt n attends over n+1 .. n+MAX_NEW-1 positions
    live = sum(a["live_tokens"] for *_, a in _named(captured, "decode"))
    assert live == sum(n + k for n in PROMPTS for k in range(1, MAX_NEW))


def test_tokens_identical_and_one_compilation(captured, plain):
    assert captured["out"] == plain
    assert captured["server"].engine.decode_compilations == 1


def test_no_args_and_no_split_unless_recording(params, monkeypatch):
    """With obs off and no profiler session, the spans carry no args the
    loop computes for them and the fetch does not split: the hot path
    does no work for a trace nobody keeps.  Under a session it does."""
    from repro.obs import trace as trace_mod
    from contextlib import nullcontext
    seen = []

    def trace_me(name, args):
        seen.append((name, dict(args)))
        return nullcontext()

    monkeypatch.setattr(trace_mod, "_trace_me", trace_me)
    for on in (False, True):
        seen.clear()
        monkeypatch.setattr(trace_mod, "profiling", lambda: on)
        _drive(params)
        spans = {}
        for name, args in seen:
            spans.setdefault(name, []).append(args)
        assert {"step", "admit", "decode", "fetch", "emit"} <= set(spans)
        assert ("fetch.ready" in spans) is on
        assert ("fetch.to_host" in spans) is on
        assert all(bool(a) is on for a in spans["admit"] + spans["emit"])
        assert all(("live_tokens" in a) is on for a in spans["decode"])
        assert all(("live_pages" in a) is on for a in spans["decode"])


def test_live_pages_counts_each_slot_to_its_last_live_page(params,
                                                          monkeypatch):
    """``decode.live_pages`` is, step by step, the fused kernel's table
    entries a layer: every slot (idle ones at pos 0 too) up to its last
    live page, ``pos // page_size + 1``.  Three requests behind two
    slots leave one slot idle at the end."""
    from repro.obs import trace as trace_mod
    from repro.serve.engine import PagedEngine
    from contextlib import nullcontext
    spans, seen_pos = [], []

    def trace_me(name, args):
        if name == "decode":
            spans.append(dict(args))
        return nullcontext()

    advance = PagedEngine.advance_slots

    def spy(self, pool, tokens, table, pos, *a, **k):
        seen_pos.append(np.asarray(pos).copy())
        return advance(self, pool, tokens, table, pos, *a, **k)

    monkeypatch.setattr(trace_mod, "_trace_me", trace_me)
    monkeypatch.setattr(trace_mod, "profiling", lambda: True)
    monkeypatch.setattr(PagedEngine, "advance_slots", spy)
    server, _ = _drive(params, prompts=PROMPTS[:3])
    page_size = server.engine.pcfg.page_size
    want = [int(sum(p // page_size + 1 for p in pos)) for pos in seen_pos]
    assert [a["live_pages"] for a in spans] == want
    assert len(set(want)) > 1 and 0 in np.concatenate(seen_pos)


def test_enabled_chrome_json_passes_check(params):
    obs = Observability()
    _drive(params, obs)
    names = check_trace(obs.tracer.to_chrome())
    assert {"step", "admit", "fetch", "fetch.ready", "fetch.to_host",
            "emit"} <= set(names)
