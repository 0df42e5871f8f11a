"""Mixture-of-Experts FFN: drop-free grouped experts for serving, and a
capacity-based gather/scatter dispatch for training.

Serving (packed ``QWeight`` experts, ``_moe_dropfree``): no assignment is
ever dropped, so a token's output depends on that token alone, whoever
shares the batch and however much padding a prefill bucket holds.  The
(token, k) assignments are counted and sorted by expert, each expert's
group padded to the row tile, and each projection is one grouped
``expert_matmul`` over the sorted rows (``kernels/expert_matmul.py``):
every hit expert's packed weights stream once per row tile, and no
FLOP is spent on an expert's empty capacity.  The combine gathers each
token's k rows and sums them in top-k order.

Training (float experts), design (DESIGN.md section 6):

  * top-k routing with a dense router (kept fp32 -- tiny, numerically
    sensitive; DESIGN.md section 4);
  * **gather dispatch**: tokens are routed into per-expert buffers of
    static ``capacity`` via a cumsum rank -- a gather, NOT the GShard
    one-hot einsum (which costs O(T^2 d) and would swamp the roofline);
    overflow tokens are dropped (standard dropping MoE);
  * expert FFNs are SwiGLU computed as batched einsum over the expert
    axis; with expert-parallel sharding the (E, C, d) buffers shard over
    the 'model' mesh axis and GSPMD inserts the all-to-alls;
  * combine: weighted scatter-add back to token positions.

Auxiliary load-balancing loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers
from .layers import QuantPolicy, NO_QUANT
from repro.distributed.actshard import constrain


def moe_init(key, *, d_model: int, d_ff: int, n_experts: int,
             n_shared_ff: int = 0, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    std = d_model ** -0.5
    p = {
        "router": {"w": jax.random.normal(ks[0], (d_model, n_experts),
                                          jnp.float32) * std},
        "wi_gate": (jax.random.normal(ks[1], (n_experts, d_model, d_ff),
                                      jnp.float32) * std).astype(dtype),
        "wi_up": (jax.random.normal(ks[2], (n_experts, d_model, d_ff),
                                    jnp.float32) * std).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_experts, d_ff, d_model),
                                 jnp.float32) * (d_ff ** -0.5)).astype(dtype),
    }
    if n_shared_ff:
        from . import mlp
        p["shared"] = mlp.swiglu_init(ks[4], d_model, n_shared_ff, dtype)
    return p


def _expert_ffn(p, x):
    """x (E, C, d) -> (E, C, d) through per-expert SwiGLU (float experts;
    packed ones route drop-free)."""
    wg, wu, wo = p["wi_gate"], p["wi_up"], p["wo"]
    dt = x.dtype
    gate = jnp.einsum("ecd,edf->ecf", x, wg.astype(dt))
    up = jnp.einsum("ecd,edf->ecf", x, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, wo.astype(dt))


def _route(router_w, xt, top_k: int):
    """f32 router: softmax over the experts, top-k, renormalised.
    Returns (probs (T, E), gate (T, k), expert ids (T, k))."""
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_ids


def _balance_loss(probs, expert_ids, n_experts: int):
    """Switch-style load-balancing loss."""
    me = probs.mean(axis=0)                                     # (E,)
    ce = jax.nn.one_hot(expert_ids[:, 0], n_experts).mean(axis=0)
    return n_experts * jnp.sum(me * ce)


def _aux(loss, counts) -> dict:
    """The layer's auxiliary outputs: the balance loss and, from the rows
    each expert was given, the experts hit and the largest group."""
    return {"loss": loss, "experts_hit": jnp.sum(counts > 0),
            "expert_rows_max": jnp.max(counts)}


def moe_apply(p, x, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25,
              policy: QuantPolicy = NO_QUANT):
    """x (B, L, d) -> (out (B, L, d), aux): ``aux`` holds the balance
    ``loss`` and the routing counts ``experts_hit`` and
    ``expert_rows_max`` (zeros on the expert-parallel path).  Packed
    experts route drop-free; float experts through capacity buffers."""
    if isinstance(p["wi_gate"], layers.kops.QWeight):
        return _moe_dropfree(p, x, n_experts=n_experts, top_k=top_k,
                             policy=policy)
    from repro.distributed import actshard
    rules = actshard.current_rules()
    if rules and rules.get("moe_shard_map"):
        out, loss = _moe_apply_ep(p, x, n_experts=n_experts, top_k=top_k,
                                  capacity_factor=capacity_factor,
                                  mesh=rules["__mesh__"],
                                  dp_axes=tuple(rules["batch"]),
                                  ep_axis=rules.get("moe_ep_axis", "model"))
        return out, _aux(loss, jnp.zeros((n_experts,), jnp.int32))
    b, l, d = x.shape
    t = b * l
    xt = constrain(x.reshape(t, d), "flat_tokens", None)

    probs, gate_vals, expert_ids = _route(p["router"]["w"], xt, top_k)

    capacity = max(int(capacity_factor * t * top_k / n_experts), 1)

    # rank of each (token, k) assignment within its expert, via one-hot cumsum
    flat_ids = expert_ids.reshape(-1)                          # (T*K,)
    onehot = jax.nn.one_hot(flat_ids, n_experts, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - 1                      # (T*K, E)
    pos_in_expert = jnp.take_along_axis(
        rank, flat_ids[:, None], axis=1)[:, 0]                 # (T*K,)
    keep = pos_in_expert < capacity

    # scatter (token row, weight) into expert buffers
    slot = flat_ids * capacity + jnp.where(keep, pos_in_expert, 0)
    slot = jnp.where(keep, slot, n_experts * capacity)          # drop -> pad
    token_idx = jnp.repeat(jnp.arange(t), top_k)
    src = jnp.zeros((n_experts * capacity + 1,), jnp.int32)
    src = src.at[slot].set(token_idx, mode="drop")
    src_tok = src[:n_experts * capacity].reshape(n_experts, capacity)
    filled = jnp.zeros((n_experts * capacity + 1,), bool
                       ).at[slot].set(keep, mode="drop")
    filled = filled[:n_experts * capacity].reshape(n_experts, capacity)

    # gather dispatch (memory-bound, no O(T^2) einsum); expert buffers
    # pinned to the EP axis so GSPMD emits the all-to-all instead of
    # falling back to replicated scatter (§Perf)
    xe = jnp.take(xt, src_tok.reshape(-1), axis=0
                  ).reshape(n_experts, capacity, d)
    xe = jnp.where(filled[..., None], xe, 0)
    # 2-D shard the expert buffers: experts over the EP ("model") axis AND
    # capacity over dp — E alone divides the work by E, not by the mesh
    # (a capacity dim left replicated over 32 data ranks cost 32x expert
    # flops on the scout train cell; §Perf)
    xe = constrain(xe, "experts", "batch", None)

    ye = _expert_ffn(p, xe)                                     # (E, C, d)
    ye = constrain(ye, "experts", "batch", None)

    # combine: weighted scatter-add back to tokens
    w_flat = gate_vals.reshape(-1)                              # (T*K,)
    wbuf = jnp.zeros((n_experts * capacity + 1,), jnp.float32
                     ).at[slot].set(jnp.where(keep, w_flat, 0.0), mode="drop")
    wbuf = wbuf[:n_experts * capacity].reshape(n_experts, capacity)
    contrib = ye.astype(jnp.float32) * wbuf[..., None]
    out = jnp.zeros((t, d), jnp.float32).at[src_tok.reshape(-1)].add(
        jnp.where(filled[..., None], contrib, 0).reshape(-1, d))
    out = constrain(out, "flat_tokens", None)
    out = out.astype(x.dtype).reshape(b, l, d)

    if "shared" in p:
        from . import mlp
        out = out + mlp.swiglu_apply(p["shared"], x, policy)

    return out, _aux(_balance_loss(probs, expert_ids, n_experts),
                     onehot.sum(axis=0))


def _moe_dropfree(p, x, *, n_experts: int, top_k: int, policy: QuantPolicy):
    """Drop-free routed experts over packed weights (the serving path).

    The T*k assignments are sorted by expert (a stable argsort) and laid
    out in expert groups, each padded to the row tile ``tm``: at most
    ``T*k + E*(tm-1)`` rows, a static bound.  Gate, up and down are one
    grouped ``expert_matmul`` each; the combine gathers each token's k
    output rows and sums them, weighted, in top-k order, so a token's
    result is the same alone, in any batch, behind any padding."""
    b, l, d = x.shape
    t, e, k = b * l, n_experts, top_k
    a = t * k
    tm = layers.kops.expert_row_tile(a, e)
    rows = -(-(a + e * (tm - 1)) // tm) * tm
    xt = x.reshape(t, d)
    with jax.named_scope("router"):
        probs, gate_vals, expert_ids = _route(p["router"]["w"], xt, k)
    with jax.named_scope("expert_dispatch"):
        flat = expert_ids.reshape(-1)                           # (A,)
        counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        padded = -(-counts // tm) * tm
        pend = jnp.cumsum(padded)                               # group ends
        start = jnp.cumsum(counts) - counts
        order = jnp.argsort(flat, stable=True)                  # by expert
        e_sorted = flat[order]
        dest = jnp.zeros((a,), jnp.int32).at[order].set(
            pend[e_sorted] - padded[e_sorted]
            + jnp.arange(a, dtype=jnp.int32) - start[e_sorted])
        # each padded row's token; rows no assignment fills read a zero row
        src = jnp.full((rows,), t, jnp.int32).at[dest].set(
            jnp.arange(a, dtype=jnp.int32) // k)
        xs = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])[src]
        tiles = jnp.arange(rows // tm, dtype=jnp.int32) * tm
        tile_expert = jnp.minimum(
            jnp.sum(pend[None, :] <= tiles[:, None], axis=1), e - 1)
        n_live = pend[-1] // tm
    with jax.named_scope("experts"):
        def mm(h, w):
            return layers.kops.expert_matmul(h, w, tile_expert, n_live,
                                             tm=tm, backend=policy.backend)
        h = (jax.nn.silu(mm(xs, p["wi_gate"]).astype(jnp.float32))
             * mm(xs, p["wi_up"]).astype(jnp.float32)).astype(x.dtype)
        ys = mm(h, p["wo"])                                     # (rows, d)
    with jax.named_scope("expert_combine"):
        y = ys[dest].reshape(t, k, d).astype(jnp.float32)
        out = jnp.sum(y * gate_vals[..., None], axis=1)
        out = out.astype(x.dtype).reshape(b, l, d)
    if "shared" in p:
        from . import mlp
        out = out + mlp.swiglu_apply(p["shared"], x, policy)
    return out, _aux(_balance_loss(probs, expert_ids, n_experts), counts)


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (the production EP path; §Perf)
# ---------------------------------------------------------------------------
#
# GSPMD's best layout for the gather/scatter dispatch still all-gathers the
# token table across data ranks (~180 s/step collective on the 235B train
# cell after 2-D buffer sharding).  The structural fix: tokens never leave
# their data shard.  Activations are replicated over the model axis, so
# each (data_i, model_j) device dispatches its LOCAL tokens to its LOCAL
# e_loc = E/ep experts, runs them, scatters back locally, and a single
# psum over the model axis completes the combine — per-layer cross-chip
# traffic collapses to one (T_local, d) reduction.

def _moe_apply_ep(p, x, *, n_experts: int, top_k: int,
                  capacity_factor: float, mesh, dp_axes: tuple,
                  ep_axis: str):
    from jax.sharding import PartitionSpec as P

    b, l, d = x.shape
    ep = mesh.shape[ep_axis]
    if n_experts % ep:
        raise ValueError(f"E={n_experts} not divisible by |{ep_axis}|={ep}")
    e_loc = n_experts // ep
    has_shared = "shared" in p

    def body(xb, router_w, wg, wu, wo):
        b_loc = xb.shape[0]
        t = b_loc * l
        xt = xb.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router_w       # (T_loc, E) fp32
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        capacity = max(int(capacity_factor * t * top_k / n_experts), 1)
        my_lo = jax.lax.axis_index(ep_axis) * e_loc

        flat_ids = expert_ids.reshape(-1)                # (T_loc*K,)
        local = (flat_ids >= my_lo) & (flat_ids < my_lo + e_loc)
        loc_ids = jnp.where(local, flat_ids - my_lo, e_loc)
        onehot = jax.nn.one_hot(loc_ids, e_loc, dtype=jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - 1
        pos = jnp.take_along_axis(
            rank, jnp.clip(loc_ids, 0, e_loc - 1)[:, None], axis=1)[:, 0]
        keep = local & (pos < capacity)

        slot = jnp.where(keep, loc_ids * capacity + pos,
                         e_loc * capacity)
        token_idx = jnp.repeat(jnp.arange(t), top_k)
        src = jnp.zeros((e_loc * capacity + 1,), jnp.int32
                        ).at[slot].set(token_idx, mode="drop")
        src_tok = src[:e_loc * capacity].reshape(e_loc, capacity)
        filled = jnp.zeros((e_loc * capacity + 1,), bool
                           ).at[slot].set(keep, mode="drop")
        filled = filled[:e_loc * capacity].reshape(e_loc, capacity)

        xe = jnp.take(xt, src_tok.reshape(-1), axis=0
                      ).reshape(e_loc, capacity, d)
        xe = jnp.where(filled[..., None], xe, 0)

        dt = xe.dtype
        gate = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                        wo.astype(dt))

        w_flat = gate_vals.reshape(-1)
        wbuf = jnp.zeros((e_loc * capacity + 1,), jnp.float32
                         ).at[slot].set(jnp.where(keep, w_flat, 0.0),
                                        mode="drop")
        wbuf = wbuf[:e_loc * capacity].reshape(e_loc, capacity)
        contrib = ye.astype(jnp.float32) * wbuf[..., None]
        out = jnp.zeros((t, d), jnp.float32).at[src_tok.reshape(-1)].add(
            jnp.where(filled[..., None], contrib, 0).reshape(-1, d))
        # combine across expert shards: the ONLY cross-chip traffic
        out = jax.lax.psum(out.astype(xb.dtype), ep_axis)

        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(expert_ids[:, 0], n_experts).mean(axis=0)
        aux = n_experts * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp_axes)
        return out.reshape(b_loc, l, d), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False)
    out, aux = fn(x, p["router"]["w"].astype(jnp.float32),
                  p["wi_gate"], p["wi_up"], p["wo"])

    if has_shared:
        from . import mlp
        out = out + mlp.swiglu_apply(p["shared"], x, NO_QUANT)
    return out, aux
