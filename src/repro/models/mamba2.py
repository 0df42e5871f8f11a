"""Mamba-2 (SSD, state-space duality, arXiv:2405.21060) block.

Chunked SSD algorithm: within-chunk quadratic ("attention-like") term plus
an inter-chunk linear recurrence over chunk states -- O(L * chunk) compute,
O(L) memory, lax.scan across chunks.  Decode is the O(1) recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;   y_t = C_t h_t + D x_t

LQR applicability (DESIGN.md section 4): in/out/x projections quantize like
any Dense; there is no KV cache, so the serving-cache quantization feature
maps to the recurrent state (core/kvwire.py quantizes h with the same
per-region format).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers
from .layers import QuantPolicy, NO_QUANT
from repro.core import kvwire


def mamba2_init(key, *, d_model: int, d_state: int, head_dim: int = 64,
                expand: int = 2, n_groups: int = 1, conv_kernel: int = 4,
                dtype=jnp.float32):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * n_groups * d_state
    ks = jax.random.split(key, 4)
    in_dim = 2 * d_inner + 2 * n_groups * d_state + n_heads
    return {
        "in_proj": layers.dense_init(ks[0], d_model, in_dim, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_kernel, conv_dim),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "norm": layers.rmsnorm_init(d_inner, dtype),
        "out_proj": layers.dense_init(ks[3], d_inner, d_model, dtype=dtype),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv1d.  x (B, L, C), w (K, C) -> (B, L, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1]].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _ssd_chunked(xh, dt, a_head, bmat, cmat, chunk: int, h0=None):
    """Chunked SSD scan.

    xh (B,L,H,P); dt (B,L,H); a_head (H,) negative; bmat/cmat (B,L,G,N).
    Returns (y (B,L,H,P), h_final (B,H,P,N)).
    """
    b, l, h, p = xh.shape
    g, n = bmat.shape[2], bmat.shape[3]
    rep = h // g
    q = min(chunk, l)
    l_p = -(-l // q) * q
    if l_p != l:
        pad = l_p - l
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = l_p // q

    xb = (xh * dt[..., None]).astype(jnp.float32)               # dt-weighted
    a = (dt * a_head[None, None, :]).astype(jnp.float32)        # (B,L,H) <= 0
    ac = a.reshape(b, nc, q, h)
    cum = jnp.cumsum(ac, axis=2)                                # inclusive
    xc = xb.reshape(b, nc, q, h, p)
    bc = bmat.reshape(b, nc, q, g, n).astype(jnp.float32)
    cc = cmat.reshape(b, nc, q, g, n).astype(jnp.float32)

    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) x~_j
    cb = jnp.einsum("bnqgs,bnkgs->bnqkg", cc, bc)               # (B,nc,Q,Q,G)
    cb = jnp.repeat(cb, rep, axis=-1)                           # -> heads
    decay = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])
    mask = jnp.tril(jnp.ones((q, q), bool))
    scores = jnp.where(mask[None, None, :, :, None], cb * decay, 0.0)
    y_intra = jnp.einsum("bnqkh,bnkhp->bnqhp", scores, xc)

    # chunk states: S_n = sum_k exp(cum_last - cum_k) B_k (x)_k
    sdecay = jnp.exp(cum[:, :, -1:, :] - cum)                   # (B,nc,Q,H)
    s_chunk = jnp.einsum("bnkgs,bnkh,bnkhp->bnhps",
                         bc, sdecay, xc)                        # (B,nc,H,P,N)
    cdecay = jnp.exp(cum[:, :, -1, :])                          # (B,nc,H)

    def step(hprev, inp):
        cd, s = inp                                             # (B,H),(B,H,P,N)
        hnew = cd[..., None, None] * hprev + s
        return hnew, hprev

    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hfin, hprevs = jax.lax.scan(step, h0,
                                (jnp.moveaxis(cdecay, 1, 0),
                                 jnp.moveaxis(s_chunk, 1, 0)))
    hprevs = jnp.moveaxis(hprevs, 0, 1)                         # (B,nc,H,P,N)

    # inter-chunk: y_i += exp(cum_i) C_i . h_{chunk-1}
    cexp = jnp.repeat(cc, rep, axis=3) if g != h else cc
    y_inter = jnp.einsum("bnqhs,bnqh,bnhps->bnqhp",
                         cexp, jnp.exp(cum), hprevs)
    y = (y_intra + y_inter).reshape(b, l_p, h, p)[:, :l]
    return y, hfin


def mamba2_apply(p, x, *, d_state: int, head_dim: int = 64, expand: int = 2,
                 n_groups: int = 1, conv_kernel: int = 4, chunk: int = 256,
                 cache=None, policy: QuantPolicy = NO_QUANT):
    """x (B, L, d_model) -> (y, new_cache).

    cache (decode): {'conv': (B, K-1, conv_dim), 'ssm': (B, H, P, N)}.
    L == 1 when cache is active (single-token decode); otherwise full scan.
    """
    b, l, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    gdim = n_groups * d_state

    # LQ-quantized recurrent state (the attention-free arch's "KV cache",
    # DESIGN.md §4): dequantize on entry, requantize on exit.
    squant = cache is not None and kvwire.is_quant_state(cache.get("ssm"))
    if squant:
        sbits, sgroup = kvwire._infer(cache["ssm"]["packed"].shape[-1],
                                      d_state, cache["ssm"]["scale"].shape[-1])
        cache = dict(cache, ssm=kvwire.dequantize_state(cache["ssm"],
                                                        d_state))

    zxbcdt = layers.dense_apply(p["in_proj"], x, policy)
    z, xr, bmat, cmat, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + gdim,
                 2 * d_inner + 2 * gdim], axis=-1)

    conv_in = jnp.concatenate([xr, bmat, cmat], axis=-1)
    new_cache = cache
    if cache is None or l > 1:
        conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"],
                                            p["conv_b"]))
        if cache is not None:  # prefill into cache: keep conv tail
            k = p["conv_w"].shape[0]
            tail = jnp.pad(conv_in, ((0, 0), (k - 1, 0), (0, 0)))[:, -(k - 1):]
            new_conv = tail.astype(cache["conv"].dtype)
    else:
        hist = jnp.concatenate([cache["conv"], conv_in], axis=1)  # (B,K,C)
        conv_out = jax.nn.silu(
            (hist.astype(jnp.float32) * p["conv_w"].astype(jnp.float32)
             ).sum(axis=1, keepdims=True)
            + p["conv_b"].astype(jnp.float32)).astype(x.dtype)
        new_conv = hist[:, 1:]

    xr, bmat, cmat = jnp.split(conv_out, [d_inner, d_inner + gdim], axis=-1)
    xh = xr.reshape(b, l, n_heads, head_dim)
    bmat = bmat.reshape(b, l, n_groups, d_state)
    cmat = cmat.reshape(b, l, n_groups, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])          # (B,L,H)
    a_head = -jnp.exp(p["A_log"])                                # (H,) < 0

    if cache is None or l > 1:
        h0 = None if cache is None else cache["ssm"]
        y, hfin = _ssd_chunked(xh.astype(jnp.float32), dt, a_head,
                               bmat, cmat, chunk, h0=h0)
        new_cache = None if cache is None else {"conv": new_conv, "ssm": hfin}
    else:
        # O(1) decode recurrence
        h_prev = cache["ssm"]                                    # (B,H,P,N)
        rep = n_heads // n_groups
        b1 = jnp.repeat(bmat[:, 0], rep, axis=1)                 # (B,H,N)
        c1 = jnp.repeat(cmat[:, 0], rep, axis=1)
        dt1 = dt[:, 0]                                           # (B,H)
        decay = jnp.exp(dt1 * a_head[None, :])                   # (B,H)
        inject = (dt1[..., None, None]
                  * xh[:, 0].astype(jnp.float32)[..., None]
                  * b1[:, :, None, :].astype(jnp.float32))       # (B,H,P,N)
        h_new = decay[..., None, None] * h_prev + inject
        y = jnp.einsum("bhpn,bhn->bhp", h_new,
                       c1.astype(jnp.float32))[:, None]          # (B,1,H,P)
        hfin = h_new
        new_cache = {"conv": new_conv, "ssm": hfin}

    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, l, d_inner).astype(x.dtype)
    y = layers.rmsnorm_apply(p["norm"], y * jax.nn.silu(z))
    out = layers.dense_apply(p["out_proj"], y, policy)
    if cache is None:
        return out, None
    if squant:
        new_cache = dict(new_cache, ssm=kvwire.quantize_state(
            new_cache["ssm"], sbits, sgroup))
    return out, new_cache


def mamba2_init_cache(batch: int, *, d_model: int, d_state: int,
                      head_dim: int = 64, expand: int = 2, n_groups: int = 1,
                      conv_kernel: int = 4, dtype=jnp.float32,
                      state_quant=None):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * n_groups * d_state
    ssm_shape = (batch, n_heads, head_dim, d_state)
    if state_quant is not None:
        bits, gs = state_quant
        ssm = kvwire.make_quant_kv(ssm_shape, bits, min(gs, d_state))
    else:
        ssm = jnp.zeros(ssm_shape, jnp.float32)
    return {
        "conv": jnp.zeros((batch, conv_kernel - 1, conv_dim), dtype),
        "ssm": ssm,
    }
