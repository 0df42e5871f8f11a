"""``bench/work.py`` and the dense family's counts against the program's
own byte accounting, and the reason no roofline reading can pass 100%."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench_tiny import ROOT

from bench import program, work
from bench import run as R
from repro.core import schemes
from repro.kernels.ops import QWeight
from repro.models import transformer
from repro.serve.pool import pool_nbytes

CONFIGS = ["dense-gqa-2b", "qwen3-8b"]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _family(cfg):
    fam = R.load_family(cfg)
    return fam, fam.plain.dims(cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_params_are_the_programs_own_tree(name):
    """The seeded arrays, handed over as the program's parameter types,
    have the tree, shapes and dtypes of ``init_params(qcfg=lq4w)``."""
    cfg = _cfg(name)
    fam, md = _family(cfg)
    mc = program.model_config(cfg, fam)
    ours = program.params(jax.eval_shape(lambda: fam.plain.draw(md, 1)), mc,
                          fam)
    theirs = jax.eval_shape(lambda: transformer.init_params(
        mc, jax.random.key(0), qcfg=schemes.get("lq4w")))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_bytes_equal_qweight_nbytes(name):
    cfg = _cfg(name)
    fam, md = _family(cfg)
    mc = program.model_config(cfg, fam)
    p = program.params(jax.eval_shape(lambda: fam.plain.draw(md, 1)), mc,
                       fam)
    qws = [x for x in jax.tree.leaves(p, is_leaf=lambda x: isinstance(
        x, QWeight)) if isinstance(x, QWeight)]
    layers = md["layers"]
    ours = layers * sum(work.packed_bytes(k, n)
                        for k, n in fam.plain.projections(md).values())
    if not md["tied"]:
        ours += work.packed_bytes(md["d"], md["vocab_pad"])
    assert ours == sum(q.nbytes() for q in qws)
    # one call's bytes: the weight's wire bytes plus bf16 x and out
    f, b = work.matmul(32, md["d"], md["ff"])
    assert f == 2 * 32 * md["d"] * md["ff"]
    assert b == work.packed_bytes(md["d"], md["ff"]) + 2 * 32 * (
        md["d"] + md["ff"])


@pytest.mark.parametrize("name", CONFIGS)
def test_page_bytes_equal_the_pools(name):
    cfg = _cfg(name)
    fam, md = _family(cfg)
    mc, sv = program.model_config(cfg, fam), cfg["serving"]
    whole = pool_nbytes(mc, n_pages=sv["n_pages"], page_size=sv["page_size"],
                        kv_bits=sv["kv_bits"], kv_group=sv["kv_group"])
    assert whole == work.page_bytes(md, sv) * sv["n_pages"] * md["layers"]


@pytest.mark.parametrize("name", CONFIGS)
def test_needed_work_never_exceeds_what_the_kernels_do(name):
    """Needed work counts live pages and real rows; the kernels read every
    page of the grid and compute padded rows.  So the least time of the
    needed work is at most the least time of the work done, which is at
    most the kernel's time: a share cannot pass 100%."""
    cfg = _cfg(name)
    (fam, md), sv = _family(cfg), cfg["serving"]
    plain = fam.plain
    peak = json.loads((ROOT / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(1, sv["max_slots"] + 1))
        ctx = rng.integers(1, sv["max_context"] + 1, n).tolist()
        grid = [sv["max_context"]] * sv["max_slots"]
        for need, done in zip(plain.attention_calls(md, ctx, sv),
                              plain.attention_calls(md, grid, sv),
                              strict=True):
            assert work.least_time(*need, peak) <= work.least_time(*done,
                                                                    peak)
        for f, b in plain.quant_matmul_calls(md, n, n):
            f_pad, b_pad = f * sv["max_slots"] / n, b
            assert work.least_time(f, b, peak) <= work.least_time(
                f_pad, b_pad, peak)
    one = plain.prefill_flops(md, 700)
    bucket = plain.prefill_flops(md, sv["max_context"])
    assert one < bucket


def test_flops_of_a_decode_step_and_a_prefill():
    plain = R.load_family({"family": "dense"}).plain
    md = {"d": 8, "heads": 2, "kv": 1, "hd": 4, "ff": 16, "vocab": 10,
          "layers": 3, "tied": True}
    proj = 2 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 8 * 16 * 2 + 16 * 8)
    assert plain.decode_flops(md, [5]) == 3 * (proj + 4 * 2 * 4 * 5) \
        + 2 * 8 * 10
    assert plain.prefill_flops(md, 2) == 3 * (2 * proj + 2 * 2 * 4 * 2 * 3) \
        + 2 * 8 * 10
