"""Compile the main-path kernels for a TPU v5e without a chip.

The TPU compiler is installed with jax: ``get_topology_desc`` describes a
v5e that is not attached, and ``jit(...).lower(shapes).compile()``
raises whatever Mosaic or XLA would refuse on the chip (tile alignment,
unsupported layouts, VMEM limits).  Shapes are granite-3-2b's published
widths: d_model 2048, d_ff 8192, 8 kv heads x 64, GQA group 4, vocab
49155, 16-token pages.  Nothing runs, so these tests say nothing about
results or times; interpret-mode parity lives in test_kernels.py and
test_paged_attention.py.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every xdist worker imports this
file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.models import attention

qm = importlib.import_module("repro.kernels.quant_matmul")
aq = importlib.import_module("repro.kernels.act_quant")
lm = importlib.import_module("repro.kernels.lut_matmul")
pa = importlib.import_module("repro.kernels.paged_attention")
em = importlib.import_module("repro.kernels.expert_matmul")

D_MODEL, D_FF, VOCAB = 2048, 8192, 49155
KV, G, HEAD_DIM, PAGE = 8, 4, 64, 16
GS = 128                                 # every lq* scheme's region size
SLOTS, BUCKET = 4, 160                   # decode M, prefill bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> list:
    """Names of the Pallas kernels in a compiled program."""
    return [line.split("=")[0].strip().lstrip("%")
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL),            # wq, wo
                                 (D_MODEL, KV * HEAD_DIM),       # wk, wv
                                 (D_MODEL, D_FF),                # gate, up
                                 (D_FF, D_MODEL),                # down
                                 (D_MODEL, VOCAB)])              # lm_head
def test_quant_matmul_compiles(one_chip, bits, k, n):
    cpb = packing.codes_per_byte(bits)
    w = [_shape(one_chip, (k // cpb, n), jnp.uint8),
         _shape(one_chip, (k // GS, n), jnp.float32),
         _shape(one_chip, (k // GS, n), jnp.float32)]
    for m in (SLOTS, BUCKET):
        c = _compile(lambda x, p, s, z: qm.quant_matmul(
            x, p, s, z, bits=bits, group_size=GS),
            _shape(one_chip, (m, k), jnp.bfloat16), *w)
        assert any(f"quant_matmul_b{bits}" in name for name in _kernels(c))


@pytest.mark.parametrize("bits,dequant", [(None, "auto"), (8, "affine"),
                                          (4, "lut"), (4, "affine")])
@pytest.mark.parametrize("lq", [1, 5])
def test_paged_attention_compiles(one_chip, bits, dequant, lq):
    n_pages, pps = 128, 10
    if bits is None:
        leaf = _shape(one_chip, (n_pages, PAGE, KV, HEAD_DIM), jnp.bfloat16)
    else:
        gr = HEAD_DIM // 16
        leaf = {"packed": _shape(one_chip, (n_pages, PAGE, KV,
                                            HEAD_DIM * bits // 8), jnp.uint8),
                "scale": _shape(one_chip, (n_pages, PAGE, KV, gr),
                                jnp.float32),
                "zmin": _shape(one_chip, (n_pages, PAGE, KV, gr),
                               jnp.float32)}
    c = _compile(lambda q, k, v, t, p: pa.paged_attention(
        q, k, v, t, p, dequant=dequant),
        _shape(one_chip, (SLOTS, lq, KV, G, HEAD_DIM), jnp.bfloat16),
        leaf, leaf, _shape(one_chip, (SLOTS, pps), jnp.int32),
        _shape(one_chip, (SLOTS,), jnp.int32))
    assert any("paged_attention_" in name for name in _kernels(c))


# mellum2-12b-a2.5b: 64 experts of width 896 over d_model 2304, top-8;
# 4 kv heads of 128, GQA group 8, a 1024-token window
M_D, M_FF, M_E, M_TOP = 2304, 896, 64, 8
M_KV, M_G, M_HD, M_WINDOW = 4, 8, 128, 1024


@pytest.mark.parametrize("tokens", [32, 2048], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(M_D, M_FF), (M_FF, M_D)],
                         ids=["gate_up", "down"])
def test_expert_matmul_compiles(one_chip, tokens, k, n):
    tm = em.row_tile(tokens * M_TOP, M_E)
    rows = -(-(tokens * M_TOP + M_E * (tm - 1)) // tm) * tm
    c = _compile(lambda x, p, s, z, te, nl: em.expert_matmul(
        x, p, s, z, te, nl, bits=4, group_size=GS, tm=tm),
        _shape(one_chip, (rows, k), jnp.bfloat16),
        _shape(one_chip, (M_E, k // 2, n), jnp.uint8),
        _shape(one_chip, (M_E, k // GS, n), jnp.float32),
        _shape(one_chip, (M_E, k // GS, n), jnp.float32),
        _shape(one_chip, (rows // tm,), jnp.int32),
        _shape(one_chip, (), jnp.int32))
    names = _kernels(c)
    assert any("expert_matmul_b4g128" in name for name in names)
    assert not any("quant_matmul_b" in name for name in names)


def _mellum_pages(one_chip, n_pages=256):
    gr = M_HD // 16
    return {"packed": _shape(one_chip, (n_pages, PAGE, M_KV, M_HD // 2),
                             jnp.uint8),
            "scale": _shape(one_chip, (n_pages, PAGE, M_KV, gr), jnp.float32),
            "zmin": _shape(one_chip, (n_pages, PAGE, M_KV, gr), jnp.float32)}


@pytest.mark.parametrize("lq", [1, 5])
def test_windowed_paged_attention_compiles(one_chip, lq):
    leaf = _mellum_pages(one_chip)
    c = _compile(lambda q, k, v, t, p: pa.paged_attention(
        q, k, v, t, p, window=M_WINDOW),
        _shape(one_chip, (32, lq, M_KV, M_G, M_HD), jnp.bfloat16),
        leaf, leaf, _shape(one_chip, (32, 128), jnp.int32),
        _shape(one_chip, (32,), jnp.int32))
    assert any("paged_attention_lut_b4" in name for name in _kernels(c))


def test_no_window_lowers_to_the_unwindowed_kernel(one_chip):
    """``window=None`` is the kernel without a window, HLO text and all:
    full-attention layers compile to the same program as before.  The
    serialized kernel body carries the tracing call stack's source
    locations, which differ between the two calls, so they are left
    out."""
    leaf = _mellum_pages(one_chip)
    args = (_shape(one_chip, (32, 1, M_KV, M_G, M_HD), jnp.bfloat16),
            leaf, leaf, _shape(one_chip, (32, 128), jnp.int32),
            _shape(one_chip, (32,), jnp.int32))

    def lower(**kw):
        return jax.jit(
            lambda q, k, v, t, p: pa.paged_attention(q, k, v, t, p, **kw)
        ).lower(*args).as_text(debug_info=False)

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.clear_caches()
    try:
        plain = lower()
        assert lower(window=None) == plain
        assert lower(window=M_WINDOW) != plain
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
        jax.clear_caches()


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k", [D_MODEL, D_FF,
                               896, 1280])      # K not a whole 128*cpb chunk
def test_act_quant_compiles(one_chip, bits, k):
    c = _compile(lambda x: aq.act_quant(x, bits=bits, group_size=GS),
                 _shape(one_chip, (BUCKET, k), jnp.bfloat16))
    assert any(f"act_quant_b{bits}" in name for name in _kernels(c))


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_FF, D_MODEL)])
def test_lut_matmul_compiles(one_chip, bits, k, n):
    cpb = packing.codes_per_byte(bits)
    c = _compile(lambda a, s, z, w: lm.lut_matmul(
        a, s, z, w, bits=bits, group_size=GS),
        _shape(one_chip, (SLOTS, k // cpb), jnp.uint8),
        _shape(one_chip, (SLOTS, k // GS), jnp.float32),
        _shape(one_chip, (SLOTS, k // GS), jnp.float32),
        _shape(one_chip, (k, n), jnp.bfloat16))
    assert any(f"lut_matmul_b{bits}" in name for name in _kernels(c))


def test_decode_attention_no_upcast_copy_on_v5e(one_chip):
    """The XLA decode path keeps bf16 caches in storage dtype
    (``preferred_element_type`` accumulation): compiled for a v5e, its
    temporaries stay well under one f32 copy of a cache, where an
    explicit ``.astype(f32)`` would hold two."""
    b, s, kvh, g, d = 2, 2048, 2, 2, 64
    c = _compile(attention.decode_attention,
                 _shape(one_chip, (b, 1, kvh, g, d), jnp.float32),
                 _shape(one_chip, (b, s, kvh, d), jnp.bfloat16),
                 _shape(one_chip, (b, s, kvh, d), jnp.bfloat16),
                 _shape(one_chip, (b,), jnp.int32))
    one_upcast_copy = b * s * kvh * d * 4
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < 0.5 * one_upcast_copy, \
        f"temps {temp}B vs one f32 cache copy {one_upcast_copy}B"


# ---------------------------------------------------------------------------
# the decode program's device scopes, as the chip's compiler leaves them
# ---------------------------------------------------------------------------

def _bench():
    """The benchmark's HLO reading (bench/hlo.py, bench/scopes.py), from
    the repository root."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import hlo, scopes
    return hlo, scopes


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "fused"])
def test_decode_copies_carry_a_scope(one_chip, fused):
    """In the decode program compiled for a v5e (granite widths, two
    layers, 4-bit weights and KV pages), every scatter and
    dynamic-update-slice, and every copy that has an op_name, lies under a
    layer-kind scope, and every copy has a consumer: a device trace splits
    the copies into the scatter's, the kernel's and the scan's.  Copies of
    a scalar (the scan's loop counter) are the loop's bookkeeping."""
    import dataclasses as dc
    from repro import configs
    from repro.core import schemes
    from repro.serve import pool as pool_mod
    from repro.serve.engine import EngineConfig, PagedConfig, PagedEngine
    from repro.models import transformer
    cfg = dc.replace(configs.get("granite-3-2b"), n_layers=2)
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.key(0), qcfg=schemes.get("lq4w")))
    ecfg = EngineConfig(max_len=256, kv_bits=4, kv_group=16,
                        weight_scheme="lq4w", backend="pallas",
                        fused_attention=fused)
    pcfg = PagedConfig(max_slots=SLOTS, page_size=PAGE, n_pages=65,
                       max_context=256)
    eng = PagedEngine(cfg, params, ecfg, pcfg)
    eng.fused_mode = "pallas" if fused else None   # compiled, not interpreted
    pages = jax.eval_shape(lambda: pool_mod.make_pool_pages(
        cfg, n_pages=pcfg.n_pages, page_size=PAGE, kv_bits=4, kv_group=16))
    put = lambda t: jax.tree.map(                  # noqa: E731
        lambda a: _shape(one_chip, a.shape, a.dtype), t)
    args = put((eng.params, pages, jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS, pcfg.pages_per_slot), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.int32)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    c = jax.jit(eng._step_paged_impl).lower(*args, key).compile()
    assert any(k.startswith("quant_matmul_b4") for k in _kernels(c))
    hlo, scopes = _bench()
    text = c.as_text()
    insts, to = hlo.parse(text), hlo.copy_consumers(text)
    moves = {n: i for n, i in insts.items()
             if i.opcode in ("copy", "scatter", "dynamic-update-slice")
             and not i.shape.split("{")[0].endswith("[]")}
    assert moves
    # a scatter or dynamic-update-slice, and a copy with an op_name, lies
    # under a scope; every copy the program runs has a consumer
    assert not [n for n, i in moves.items()
                if (i.path or i.opcode != "copy")
                and not scopes.scope_of(i.path)]
    assert not [n for n, i in moves.items() if n in to and not to[n]]
    assert {"kv_write", "layer_scan"} <= {scopes.scope_of(i.path)
                                          for i in moves.values()}
    # the page arrays go through three relayouts a layer: the scan's slice
    # for the scatter, the scatter's result for the kernel (fused path),
    # and the layer's pages for the scan's stacking
    goes = {to[n] for n, i in moves.items()
            if n in to and i.shape.count(",") >= 2}
    want = {"scatter@kv_write", "dynamic-update-slice@layer_scan"}
    if fused:
        want.add("paged_attention_lut_b4@attention")
    assert want <= goes, goes
