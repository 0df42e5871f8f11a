"""A configuration brings its architecture as a family of files
(``bench/families/<family>/``): adding one edits nothing under ``bench/``,
the dense family gives the numbers pinned from the harness before it had
families, and only ``bench/program.py`` imports the program."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench_tiny import ROOT, run_tiny, tiny_cell, tiny_config

from bench import program, reference, work
from bench import run as R

CONFIGS = ["dense-gqa-2b", "qwen3-8b"]
FAMILY_DIRS = sorted(p.name for p in R.FAMILIES.iterdir()
                     if (p / "plain.py").is_file())
PEAK = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
CONTEXTS = [1, 15, 16, 17, 300, 1020, 2047, 2048]
REL = 1e-12

# What the harness counted, drew and read before the dense code moved into
# its family, recorded from that tree (bench/weights.py, bench/work.py,
# bench/reference.py, bench/metrics and tests/bench/bench_tiny.py as they
# were): a move that changes any of them changes a measured number.
PINNED = {
    "dense-gqa-2b": {
        "dims": {"d": 2048, "heads": 32, "kv": 8, "hd": 64, "ff": 8192,
                 "vocab": 49155, "vocab_pad": 49408, "layers": 40,
                 "tied": True, "qk_norm": False, "rope_theta": 10000.0,
                 "eps": 1e-06},
        # rows, calls, sum of FLOPs, sum of bytes
        "qmm_decode": (32, 280, 155692564480.0, 1481113600),
        "qmm_prefill": (1020, 280, 4962700492800.0, 4961402880),
        # one layer's (FLOPs, bytes) over CONTEXTS, and the layers
        "attention": ((44761088.0, 5701632), 40),
        "attention_least_s": 0.00027846798534798535,
        "decode_flops": 42324295680.0,
        "prefill_flops": (5133528084480.0, 10652055777280.0),
        "tiny_weights_sha256": "e7c814d04439c21eef1e69480126a53988fcb5ca"
                               "9b4ad67aeb033596acb501ac",
        "tiny_gaps": [
            5.380131721496582, 5.141829490661621, 3.021080732345581,
            3.405576229095459, 4.685450553894043, 2.485037326812744,
            3.2201480865478516, 1.3029158115386963, 4.906680107116699,
            1.8410804271697998, 3.0195553302764893, 3.888613700866699,
            3.9174644947052, 2.6523051261901855, 2.887899875640869,
            4.253166675567627, 2.7428152561187744, 4.163110733032227,
            4.342762470245361, 4.212372779846191],
        "tiny_control_gaps": [
            0.0, 0.0, 0.0, 0.0, 0.5317928791046143, 0.0, 0.0, 0.0, 0.0,
            0.4013071060180664, 0.0, 0.0, 0.0, 0.21719789505004883, 0.0,
            0.2567412853240967, 0.32584452629089355, 0.0,
            0.25077319145202637, 0.0],
        "readers": {
            "mfu.chat": 0.0811593128078247,
            "mfu.score": 0.0811593128078247,
            "paged_attention_roofline": 0.015803858363858364,
            "quant_matmul_roofline.decode": 0.1530430783185069,
            "quant_matmul_roofline.prefill": 1.606237873806735},
    },
    "qwen3-8b": {
        "dims": {"d": 4096, "heads": 32, "kv": 8, "hd": 128, "ff": 12288,
                 "vocab": 151936, "vocab_pad": 152064, "layers": 36,
                 "tied": False, "qk_norm": True, "rope_theta": 1000000.0,
                 "eps": 1e-06},
        "qmm_decode": (16, 253, 242179112960.0, 4349341696),
        "qmm_prefill": (1020, 253, 14170610204672.0, 9822356224),
        "attention": ((89522176.0, 11403264), 36),
        "attention_least_s": 0.0005012423736263736,
        "decode_flops": 124312354816.0,
        "prefill_flops": (14477737459712.0, 29688662589440.0),
        "tiny_weights_sha256": "3952e7ccf2dca4f64fa2b8bcb7c0bf1edc1b994b"
                               "02769f352061434f79e2c2c3",
        "tiny_gaps": [
            3.8025617599487305, 4.152100563049316, 3.7838261127471924,
            5.294863224029541, 4.512026786804199, 4.378413200378418,
            3.721647024154663, 2.7297322750091553, 4.125012397766113,
            5.065192699432373, 4.1126861572265625, 4.253209590911865,
            3.651872158050537, 4.472150802612305, 1.8914555311203003,
            3.5386123657226562, 4.849325656890869, 2.5618107318878174,
            0.9886600971221924, 3.201083183288574],
        "tiny_control_gaps": [
            0.0, 0.0, 0.20536303520202637, 0.0, 0.0, 0.46209263801574707,
            0.0, 0.19892191886901855, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
            0.0, 0.0, 0.0, 0.0, 0.0],
        "readers": {
            "mfu.chat": 0.2236520336687384,
            "mfu.score": 0.2236520336687384,
            "paged_attention_roofline": 0.012797524395604396,
            "quant_matmul_roofline.decode": 0.453580380254666,
            "quant_matmul_roofline.prefill": 4.630902471929872},
    },
}

# a prompt and served tokens the reference reads at test size
PROMPT = list(range(3, 40))
SERVED = [(7 * i + 5) % 1000 for i in range(20)]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _family(name):
    cfg = _cfg(name)
    fam = R.load_family(cfg)
    return cfg, fam, fam.plain.dims(cfg)


def _sums(calls):
    return (len(calls), sum(f for f, _ in calls), sum(b for _, b in calls))


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _bench_files() -> dict:
    return {str(p.relative_to(ROOT)): p.read_bytes()
            for p in sorted((ROOT / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


# ---------------------------------------------------------------------------
# a family is only files
# ---------------------------------------------------------------------------

def test_a_family_is_only_files(tmp_path):
    """The dense family copied under another name outside ``bench/`` and
    named by the configuration runs the tiny cell with the dense family's
    weights, program config and comparison, and nothing under ``bench/``
    changes."""
    before = _bench_files()
    shutil.copytree(R.FAMILIES / "dense", tmp_path / "dense-copy",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = tiny_cell()
    cfg = dict(cell.config, family="dense-copy")
    copy = dataclasses.replace(cell, config=cfg,
                               family=R.load_family(cfg, tmp_path))
    assert copy.family.name == "dense-copy"
    assert copy.family.plain is not cell.family.plain
    assert copy.family.plain.__file__ == str(
        (tmp_path / "dense-copy" / "plain.py").resolve())
    md = cell.family.plain.dims(cell.config)
    assert copy.family.plain.dims(cfg) == md
    assert _digest(copy.family.plain.draw(md, 7)) == _digest(
        cell.family.plain.draw(md, 7))
    assert program.model_config(cfg, copy.family) == program.model_config(
        cell.config, cell.family)
    chosen = [(PROMPT, SERVED)]
    assert R.check(copy, md, 7, chosen) == R.check(cell, md, 7, chosen)
    res = run_tiny(copy, 3)
    assert res["correct"], res["checks"]
    assert _bench_files() == before


@pytest.mark.parametrize("family, says", [
    (None, "names no family"),
    ("no-such-family", "has no plain.py, served.py"),
    ("../dense", "not a directory name"),
])
def test_a_configuration_must_name_a_family(family, says):
    cfg = {k: v for k, v in _cfg("dense-gqa-2b").items() if k != "family"}
    if family is not None:
        cfg["family"] = family
    with pytest.raises(R.BenchError, match=re.escape(says)):
        R.load_family(cfg)


def test_a_family_loads_once():
    """One family, one set of functions: the reference compiled for its
    forward is found again by every later cell of the process."""
    a, b = R.load_family(_cfg("dense-gqa-2b")), R.load_family(_cfg("qwen3-8b"))
    assert a is b and a.plain.forward is b.plain.forward


# ---------------------------------------------------------------------------
# the dense family's numbers are the ones pinned before the move
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_dims_are_pinned(name):
    _, _, md = _family(name)
    assert md == PINNED[name]["dims"]


@pytest.mark.parametrize("name", CONFIGS)
def test_quant_matmul_calls_are_pinned(name):
    cfg, fam, md = _family(name)
    rows = cfg["serving"]["max_slots"]
    want = PINNED[name]
    assert want["qmm_decode"][0] == rows
    for got, (n, calls, flops, nbytes) in [
            (_sums(fam.plain.quant_matmul_calls(md, rows, rows)),
             want["qmm_decode"]),
            (_sums(fam.plain.quant_matmul_calls(md, 1020, 1)),
             want["qmm_prefill"])]:
        assert got[0] == calls
        assert got[1] == pytest.approx(flops, rel=REL)
        assert got[2] == nbytes


@pytest.mark.parametrize("name", CONFIGS)
def test_attention_calls_are_pinned(name):
    """One call a layer, each the pinned one-layer count; their least
    times summed give one layer's times the layers."""
    cfg, fam, md = _family(name)
    (flops, nbytes), layers = PINNED[name]["attention"]
    calls = fam.plain.attention_calls(md, CONTEXTS, cfg["serving"])
    assert len(calls) == layers
    for f, b in calls:
        assert f == pytest.approx(flops, rel=REL) and b == nbytes
    least = sum(work.least_time(f, b, PEAK) for f, b in calls)
    assert least == pytest.approx(PINNED[name]["attention_least_s"], rel=REL)


@pytest.mark.parametrize("name", CONFIGS)
def test_a_window_of_layer_sums_is_the_product_of_one_layer(name):
    """``paged_attention_roofline`` sums every layer's least time where
    it once multiplied one layer's by the layers: over a full window of
    decode steps the two differ by rounding alone."""
    cfg, fam, md = _family(name)
    sv = cfg["serving"]
    rng = np.random.default_rng(15)
    steps = [rng.integers(1, sv["max_context"] + 1,
                          int(rng.integers(1, sv["max_slots"] + 1))).tolist()
             for _ in range(300)]
    ours = sum(work.least_time(f, b, PEAK) for ctx in steps
               for f, b in fam.plain.attention_calls(md, ctx, sv))
    one = [fam.plain.attention_calls(md, ctx, sv)[0] for ctx in steps]
    product = sum(work.least_time(f, b, PEAK) for f, b in one) * md["layers"]
    assert abs(ours - product) <= REL * product


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_are_pinned(name):
    _, fam, md = _family(name)
    want = PINNED[name]
    assert fam.plain.decode_flops(md, CONTEXTS) == pytest.approx(
        want["decode_flops"], rel=REL)
    for n, f in zip((1020, 2048), want["prefill_flops"], strict=True):
        assert fam.plain.prefill_flops(md, n) == pytest.approx(f, rel=REL)


@pytest.mark.parametrize("name", CONFIGS)
def test_tiny_weights_are_pinned(name):
    """The same seed draws bit-identical weights at test size."""
    cfg = tiny_config(name)
    fam = R.load_family(cfg)
    assert _digest(fam.plain.draw(fam.plain.dims(cfg), 1)) == PINNED[name][
        "tiny_weights_sha256"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_gaps_are_pinned(name):
    """The reference over the family's forward reads the pinned gaps,
    the float8 control's too, on one prompt and its tokens."""
    cfg = tiny_config(name)
    fam, sv = R.load_family(cfg), cfg["serving"]
    md = fam.plain.dims(cfg)
    g, c = reference.gaps(fam.plain.forward, fam.plain.draw(md, 1), md,
                          PROMPT, SERVED, bucket=sv["max_context"],
                          kv_bits=sv["kv_bits"], kv_group=sv["kv_group"],
                          control=True)
    np.testing.assert_allclose(g, PINNED[name]["tiny_gaps"], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(c, PINNED[name]["tiny_control_gaps"],
                               rtol=1e-6, atol=1e-6)


class _Trace:
    """A reduced trace with fixed kernel and program times."""
    window_s = 51.25

    def op_s(self, name, module):
        t = {"": 40.0}.get(name, 3.5 if "quant" in name else 20.0)
        return t * (1.0 if "decode" in module or "step" in module else 0.7)


def _reader(name):
    return R._module(ROOT / "bench" / "metrics" / f"{name}.py",
                     f"bench_metric_{name.replace('.', '_')}").read


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_give_the_pinned_readings(name):
    """The five readers of the family's counts, on a fixed window of
    admissions and decode steps, read the pinned readings."""
    cfg, fam, md = _family(name)
    slots = cfg["serving"]["max_slots"]
    ctxs = [(37 * i * i + 11) % 2048 + 1 for i in range(slots)]
    steps = [R.Step([1020, 33], [], 0), R.Step([], ctxs, 0),
             R.Step([500], ctxs[:slots // 2], 0),
             R.Step([], [c + 1 for c in ctxs], 0)]
    ctx = R.Context(_Trace(), steps, md, cfg["serving"], PEAK, fam)
    for metric, want in PINNED[name]["readers"].items():
        assert _reader(metric)(ctx) == pytest.approx(want, rel=REL), metric


# ---------------------------------------------------------------------------
# what imports the program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILY_DIRS)
def test_plain_imports_nothing_of_the_program(family, tmp_path):
    """Each family's ``plain.py``, with ``src`` off ``sys.path``: it loads,
    draws, runs the reference and counts work, and ``repro`` is never
    imported."""
    code = f"""
import importlib.util, json, sys
sys.path.insert(0, {str(ROOT)!r})
assert importlib.util.find_spec("repro") is None
from bench import reference
spec = importlib.util.spec_from_file_location(
    "plain", {str(R.FAMILIES / family / "plain.py")!r})
plain = importlib.util.module_from_spec(spec)
spec.loader.exec_module(plain)
cfg = json.loads(open({str(ROOT / "bench" / "configs"
                           / "dense-gqa-2b.json")!r}).read())
cfg = plain.tiny(cfg)
sv = dict(cfg["serving"], max_context=64)
md = plain.dims(cfg)
g, _ = reference.gaps(plain.forward, plain.draw(md, 1), md, [1, 2, 3],
                      [4, 5], bucket=64, kv_bits=sv["kv_bits"],
                      kv_group=sv["kv_group"])
assert len(g) == 2
assert plain.decode_flops(md, [3]) > 0 and plain.prefill_flops(md, 3) > 0
assert plain.quant_matmul_calls(md, 1, 1) and plain.attention_calls(md, [3], sv)
assert not [m for m in sys.modules if m.split(".")[0] == "repro"]
print("ok")
"""
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-3000:]


IMPORTS_REPRO = re.compile(r"^\s*(import\s+repro\b|from\s+repro\b)", re.M)


def test_only_program_imports_the_program():
    importers = sorted(str(p.relative_to(ROOT))
                       for p in (ROOT / "bench").rglob("*.py")
                       if IMPORTS_REPRO.search(p.read_text()))
    assert importers == ["bench/program.py"]


# what the shared modules held for the dense decoder before families, and
# the keys of its sizes and weights that no other architecture is bound
# to have; a family's own functions are reached through the family
DENSE_FUNCTIONS = ["dims", "make", "draw", "projections",
                   "quant_matmul_calls", "attention", "attention_calls",
                   "decode_flops", "prefill_flops", "_forward", "forward",
                   "tiny"]
DENSE_KEYS = ["layers", "ff", "heads", "tied", "qk_norm", "rope_theta",
              "eps", "wq", "wk", "wv", "wo", "wi_gate", "wi_up", "wo_ffn",
              "norm1", "norm2", "q_norm", "k_norm", "lm_head",
              "final_norm", "embed"]
SHARED = ["bench/run.py", "bench/program.py", "bench/weights.py",
          "bench/reference.py", "bench/work.py",
          *sorted(str(p.relative_to(ROOT))
                  for p in (ROOT / "bench" / "metrics").glob("*.py"))]


@pytest.mark.parametrize("path", SHARED)
def test_shared_code_names_no_dense_function_or_key(path):
    """The harness, the shared modules and the readers reach the dense
    decoder only through a family: they neither define nor call a dense
    function of the shared modules, and read no dense key."""
    src = (ROOT / path).read_text()
    names = "|".join(DENSE_FUNCTIONS)
    defs = re.findall(rf"^def ({names})\(", src, re.M)
    calls = re.findall(rf"\b(?:weights|work|reference)\.({names})\b", src)
    keys = re.findall(r"""\[["']({})["']\]""".format("|".join(DENSE_KEYS)),
                      src)
    assert not defs and not calls and not keys, (defs, calls, keys)
