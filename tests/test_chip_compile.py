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
