"""The routed-expert decoder served through the paged pool, against the
benchmark family's plain float32 reference (``bench/families/moe``) on
the same seeded weights: the prompt's prefill and each later decode step
give the reference's logits, on the fused (interpret) and XLA attention
paths, across the sliding window's edge and a page boundary."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program, reference  # noqa: E402
from bench import run as R  # noqa: E402
from repro.serve.engine import EngineConfig, PagedConfig, PagedEngine  # noqa: E402

PAGE, CTX, STEPS = 16, 64, 8
# slot 0's contexts cross the 32-token window and the page boundary at
# 32; slot 1's lie past the window and cross the page boundary at 48
PROMPTS = (28, 43)


@pytest.fixture(scope="module")
def model():
    cfg = json.loads((ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
                     .read_text())
    fam = R.load_family(cfg)
    cfg = fam.plain.tiny(cfg)
    cfg["torch_dtype"] = "float32"
    md = fam.plain.dims(cfg)
    assert md["window"] == 32
    mc = program.model_config(cfg, fam)
    w = fam.plain.draw(md, 11)
    return fam, md, mc, w, program.params(w, mc, fam)


def _served(model, fused):
    """Prefill both prompts, then STEPS decode steps fed fixed tokens;
    every program's logits (the engine's sampler hands them back)."""
    fam, md, mc, w, p = model
    eng = PagedEngine(mc, p, EngineConfig(
        max_len=CTX, kv_bits=4, kv_group=16, weight_scheme="lq4w",
        fused_attention=fused), PagedConfig(
        max_slots=2, page_size=PAGE, n_pages=2 * CTX // PAGE + 1,
        max_context=CTX))
    eng._sample = lambda logits, key: logits
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, md["vocab"], n + STEPS) for n in PROMPTS]
    pool, key = eng.new_pool(), jax.random.key(0)
    got = [[], []]
    for b, n in enumerate(PROMPTS):
        pool.alloc(b, -(-n // PAGE))
        padded = np.zeros((1, CTX), np.int32)
        padded[0, :n] = seqs[b][:n]
        ids = np.zeros((CTX // PAGE,), np.int32)
        ids[:len(pool.pages_of(b))] = pool.pages_of(b)
        lg, pool.pages = eng._prefill_paged(
            eng.params, jnp.asarray(padded), pool.pages, jnp.asarray(ids),
            jnp.asarray(n - 1, jnp.int32), (key, np.uint32(b)))
        got[b].append(np.asarray(lg[0]))
    for j in range(STEPS):
        pos = np.asarray([n + j for n in PROMPTS], np.int32)
        for b in range(2):
            while len(pool.pages_of(b)) <= pos[b] // PAGE:
                pool.alloc(b, 1)
        table = np.stack([pool.table_array(b, CTX // PAGE) for b in (0, 1)])
        toks = np.asarray([seqs[b][pos[b]] for b in (0, 1)], np.int32)
        (lg, counts), pool.pages = eng._step_paged(
            eng.params, pool.pages, jnp.asarray(toks), jnp.asarray(table),
            jnp.asarray(pos), (key, np.uint32(j)))
        assert int(counts[0]) >= md["top_k"]
        for b in range(2):
            got[b].append(np.asarray(lg[b]))
    return seqs, got


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "fused"])
def test_served_logits_match_the_plain_reference(model, fused):
    """Tolerance: both sides compute in float32 and differ by summation
    order only (the kernel's online softmax, the grouped expert rows,
    blocked matmuls): measured under 1e-5 on logits of up to ~4 in
    magnitude, so 1e-4 is ten times that.  A layer's window, YaRN or a
    routed expert left out moves the logits by far more."""
    fam, md, mc, w, p = model
    seqs, got = _served(model, fused)
    for b, n in enumerate(PROMPTS):
        tokens = np.zeros((CTX,), np.int32)
        tokens[:n + STEPS] = seqs[b]
        rows = np.arange(n - 1, n + STEPS)
        with jax.default_matmul_precision("highest"):
            want = fam.plain.forward(w, md, jnp.asarray(tokens), n,
                                     jnp.asarray(rows), kv_bits=4,
                                     kv_group=16, ops=reference.Ops())
        have = np.stack(got[b])[:, :md["vocab"]]
        np.testing.assert_allclose(have, np.asarray(want), rtol=0,
                                   atol=1e-4)
