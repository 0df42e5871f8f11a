"""The harness refuses what it cannot measure, and BENCHMARK.json keeps
to the benchmark's contract."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT

from bench import run as R

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_refuses_without_a_tpu(capsys):
    rc = R.main(["--workload", "dense-gqa-2b.chat", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


def test_refuses_an_unknown_device_kind():
    with pytest.raises(R.BenchError, match="no entry"):
        R.peak_for("TPU v99 imaginary")
    peak = R.peak_for("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]


def test_refuses_an_unknown_workload():
    with pytest.raises(R.BenchError):
        R.load_cell("no-such.cell")


def test_refuses_in_a_directory_without_the_program(tmp_path):
    for p in SPEC["paths"] + ["BENCHMARK.json"]:
        src = ROOT / p
        if src.is_dir():
            shutil.copytree(src, tmp_path / p, ignore=shutil.ignore_patterns(
                "__pycache__"))
        else:
            shutil.copy(src, tmp_path / p)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, *SPEC["command"][1:], "--workload",
                        "dense-gqa-2b.chat", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = R.load_cell(cell)
    assert c.chips == 1
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer
    for m in c.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    assert 0 < c.limits["widest_logit_gap"]


def test_benchmark_json_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert ".." not in p and not p.startswith("/")
    cfg_names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert set(c["reduced"]) == set(json.loads(
            (ROOT / c["file"]).read_text())["reduced"])
        cfg_names.add(c["name"])
    used, pairs = set(), set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == cfg_names
    names = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        for cell in m["workloads"]:
            reported = e2e[m["moves"]].get("workloads", [cell])
            assert cell in reported


def test_compile_watch_sees_a_compilation_in_its_block():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 3)
    f(jnp.ones(2))
    with R.CompileWatch() as w:
        f(jnp.ones(2))
    assert w.names == []
    with R.CompileWatch() as w:
        f(jnp.ones(3))
    assert w.names
    # a compilation on another thread is not the window's
    import threading
    with R.CompileWatch() as w:
        t = threading.Thread(target=lambda: f(jnp.ones(4)))
        t.start()
        t.join(timeout=60)
    assert not t.is_alive() and w.names == []


def test_a_closed_loop_whose_backlog_runs_dry_is_refused():
    class Idle:
        has_work = False

    with pytest.raises(R.BenchError, match="ran dry"):
        R._closed_window(Idle(), R.Book(), 1.0)


def test_gc_watch_records_each_collection():
    import gc
    n = len(gc.callbacks)
    with R.GcWatch() as w:
        gc.collect()
    assert w.spans and w.spans[-1][0] == 2 and w.spans[-1][1] >= 0
    assert "1 full" in w.summary()
    assert len(gc.callbacks) == n


def test_a_stall_is_a_step_slow_for_its_admissions():
    book = R.Book()
    for took, admitted in [(0.4, 0), (0.4, 0), (1.0, 1), (1.0, 1),
                           (1.0, 0), (0.4, 0), (1.6, 1), (1.1, 1)]:
        book.steps.append(R.Step([100] * admitted, [], 0, 0.0, took))
    assert book.stalls(1.4) == [4, 6]
