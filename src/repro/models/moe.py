"""Mixture-of-Experts FFN: top-k routing with capacity-based scatter dispatch
(+ optional shared experts, Qwen-MoE or DeepSeek style).

TPU-native dispatch: fixed-shape scatter into an [E, C, d] buffer (tokens over
capacity are dropped, GShard-style), batched expert matmuls via einsum, and a
gather-combine.  Expert weights carry a leading E dim so expert parallelism is
just a sharding rule ("expert" -> "model"); the dispatch scatter/gather then
lowers to all-to-alls under pjit.

Two routers: ``scoring="softmax"`` (top-k of the softmax, optionally
renormalised; Mixtral / Qwen-MoE) and ``scoring="sigmoid"`` (DeepSeek-V3's
``noaux_tc`` with one group: top-k of sigmoid scores plus a selection-only
``score_bias``, gates = the chosen scores renormalised times
``routed_scale``).  ``dropless=True`` sets the capacity to the tokens of the
routing group, so no (token, expert) pair is ever dropped; a global-dispatch
call over more than ``DROPLESS_BLOCK`` tokens routes them in blocks of that
many, so its [E, C, d] buffers stay bounded however long the prefill.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models.layers import Params, init_linear, linear

# tokens per routing block of a dropless global dispatch (the capacity)
DROPLESS_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert ff
    capacity_factor: float = 1.25
    shared_ff: int = 0             # 0 = no shared expert branch
    norm_topk: bool = True
    router_aux_weight: float = 0.01
    dispatch: str = "global"       # global (one cross-device buffer) |
                                   # grouped (per-sequence groups)
    scoring: str = "softmax"       # softmax | sigmoid (+ score_bias leaf)
    routed_scale: float = 1.0      # gate multiplier (DeepSeek routed_scaling)
    shared_gate: bool = True       # sigmoid gate on the shared expert
    dropless: bool = False         # capacity = tokens in the group


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 6)
    E, F = cfg.n_experts, cfg.d_ff
    s = 1.0 / (d_model ** 0.5)
    p = {
        "router": init_linear(ks[0], d_model, E, dtype=dtype),
        "wi": jax.random.normal(ks[1], (E, d_model, F), dtype) * s,
        "wg": jax.random.normal(ks[2], (E, d_model, F), dtype) * s,
        "wo": jax.random.normal(ks[3], (E, F, d_model), dtype) * (1.0 / F ** 0.5),
    }
    if cfg.scoring == "sigmoid":
        p["score_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.shared_ff:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d_model, cfg.shared_ff, "swiglu", dtype)
        if cfg.shared_gate:
            p["shared_gate"] = init_linear(ks[5], d_model, 1, dtype=dtype)
    return p


def _expert_einsum(a: jax.Array, w, compute_dtype, out_contract: bool = False
                   ) -> jax.Array:
    """einsum('ecd,edf->ecf') for float weights or pre-quantized codes.

    The weight is cast to compute dtype and re-constrained to its TP-only
    layout *at the use site*: under FSDP the contracting dim is data-sharded,
    and letting XLA contract a sharded dim turns every expert matmul into a
    partial-sum all-reduce of the (huge) activation buffer — 6.3 TB/step on
    mixtral train_4k.  Re-gathering bf16 weights instead costs ~2 orders of
    magnitude less (§Perf iteration B4)."""
    if not isinstance(w, dict):
        # NOTE: an earlier iteration (§Perf B4) re-constrained the bf16 cast
        # to a TP-only layout here to force weight re-gather over the FSDP
        # axis; under GSPMD this regressed badly (XLA replicated the expert
        # compute). The identified follow-up is an explicit shard_map for the
        # expert block; the plain cast below at least keeps gathers in bf16.
        return jnp.einsum("ecd,edf->ecf", a, w.astype(compute_dtype))
    w_q, w_scale = w["w_q"], w["w_scale"]
    packed = w_q.dtype == jnp.uint8              # int4, two per byte along K
    qmax = 7 if packed else 127
    # per-row absmax codes in float32, as every projection of the serving
    # path takes them (kernels.lutmul.ops.quantize_activations)
    af = a.astype(jnp.float32)
    a_scale = jnp.maximum(jnp.max(jnp.abs(af), axis=-1, keepdims=True),
                          1e-8) / qmax
    a_q = jnp.clip(jnp.round(af / a_scale), -qmax - 1, qmax).astype(jnp.int8)
    if packed:
        # even k sit in the low nibbles, odd k in the high: two exact int32
        # contractions over the bank as stored (no unpacked or relaid-out
        # copy of the codes)
        def nibble(x):
            x = x.astype(jnp.int8)
            return jnp.where(x >= 8, x - 16, x)
        acc = (jnp.einsum("ecd,edf->ecf", a_q[..., 0::2], nibble(w_q & 0xF),
                          preferred_element_type=jnp.int32)
               + jnp.einsum("ecd,edf->ecf", a_q[..., 1::2], nibble(w_q >> 4),
                            preferred_element_type=jnp.int32))
    else:
        acc = jnp.einsum("ecd,edf->ecf", a_q, w_q,
                         preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * a_scale * w_scale
            ).astype(compute_dtype)


def moe_ffn(p: Params, x: jax.Array, cfg: MoEConfig, *, quant: str = "none",
            compute_dtype=jnp.bfloat16, deterministic_capacity: Optional[int] = None,
            with_stats: bool = False):
    """x: [B, S, d] -> (y, aux_loss), and with ``with_stats`` also
    int32[3] counters (experts that got a token, summed over routing groups;
    (token, expert) pairs computed; pairs dropped by capacity).

    ``dispatch="grouped"``: each batch row is its own routing group
    (GShard group_size = S).  Because the batch dim is data-sharded and groups
    never interact, the scatter/gather dispatch is **collective-free** — the
    global-buffer variant costs TBs of all-reduce per step at mixtral scale.
    Trade-off: capacity is enforced per-sequence, so unbalanced single
    sequences drop more tokens at equal capacity_factor.  ``cfg.dropless``
    sizes the capacity to the group's tokens instead (and ignores
    ``deterministic_capacity``); under global dispatch the groups are
    blocks of at most ``DROPLESS_BLOCK`` tokens.
    """
    if cfg.dispatch == "grouped":
        C = x.shape[1] if cfg.dropless else deterministic_capacity or max(
            cfg.top_k, int(x.shape[1] * cfg.top_k / cfg.n_experts
                           * cfg.capacity_factor))

        def one_group(xg):
            return _moe_dispatch_flat(p, xg, cfg, quant=quant,
                                      compute_dtype=compute_dtype,
                                      capacity=C, constrain_bufs=False)

        y, aux, stats = jax.vmap(one_group)(x)
        y = constrain(y, "batch", None, None)
        out = (y, jnp.mean(aux), jnp.sum(stats, axis=0))
        return out if with_stats else out[:2]
    B, S, d = x.shape
    T = B * S
    if cfg.dropless and T > DROPLESS_BLOCK:
        # blocks of DROPLESS_BLOCK tokens, capacity = the block; the tail
        # block's pad rows are neither counted nor kept
        blk = DROPLESS_BLOCK
        nb = -(-T // blk)
        xb = jnp.pad(x.reshape(T, d), ((0, nb * blk - T), (0, 0)))
        y, aux, stats = jax.lax.map(
            lambda a: _moe_dispatch_flat(
                p, a[0], cfg, quant=quant, compute_dtype=compute_dtype,
                capacity=blk, n_tokens=a[1]),
            (xb.reshape(nb, blk, d),
             jnp.minimum(T - blk * jnp.arange(nb), blk)))
        out = (y.reshape(nb * blk, d)[:T].reshape(B, S, d), jnp.mean(aux),
               jnp.sum(stats, axis=0))
        return out if with_stats else out[:2]
    y, aux, stats = _moe_dispatch_flat(
        p, x.reshape(T, d), cfg, quant=quant, compute_dtype=compute_dtype,
        capacity=T if cfg.dropless else deterministic_capacity)
    out = (y.reshape(B, S, d), aux, stats)
    return out if with_stats else out[:2]


def _route(p: Params, xf: jax.Array, cfg: MoEConfig, compute_dtype):
    """(gates f32 [T, k], expert ids [T, k], router probabilities [T, E]
    for the aux loss, or None)."""
    k = cfg.top_k
    if cfg.scoring == "sigmoid":
        # bf16 activations and router, f32 logits and sigmoid; the bias
        # only chooses, the gates are the chosen scores
        logits = jnp.dot(xf.astype(compute_dtype),
                         p["router"]["w"].astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits)
        _, expert_ids = jax.lax.top_k(scores + p["score_bias"], k)
        gate_vals = jnp.take_along_axis(scores, expert_ids, axis=1)
        if cfg.norm_topk:
            gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1,
                                             keepdims=True) + 1e-20)
        return gate_vals * cfg.routed_scale, expert_ids, None
    logits = linear(p["router"], xf.astype(jnp.float32), "none", jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # [T, k]
    if cfg.norm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return gate_vals * cfg.routed_scale, expert_ids, probs


def _moe_dispatch_flat(p: Params, xf: jax.Array, cfg: MoEConfig, *,
                       quant: str, compute_dtype, capacity: Optional[int],
                       constrain_bufs: bool = True, n_tokens=None):
    """Capacity-based dispatch over a flat token list [T, d]; returns
    (y, aux, int32[3] counters).  Rows from ``n_tokens`` (int32 scalar,
    default T) on are padding: routed to no expert and not counted."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity or max(1, int(T * k / E * cfg.capacity_factor))

    with jax.named_scope("moe_route"):
        gate_vals, expert_ids, probs = _route(p, xf, cfg, compute_dtype)
        if probs is None:                  # auxiliary-loss-free routing
            aux = jnp.zeros((), jnp.float32)
        else:
            # load-balancing aux loss (Switch): E * sum_e f_e * P_e
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                         dtype=jnp.float32), axis=0)
            aux = cfg.router_aux_weight * E * jnp.sum(me * ce)

        # position of each (token, slot) within its expert
        flat_e = expert_ids.reshape(-1)                          # [T*k]
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # [T*k, E]
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot           # exclusive
        pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
        token_of = jnp.repeat(jnp.arange(T), k)
        keep = pos < C
        if n_tokens is None:
            n_tokens = T
        else:
            keep = keep & (token_of < n_tokens)
        kept = jnp.sum(onehot * keep[:, None], axis=0)               # [E]
        pairs = jnp.sum(keep.astype(jnp.int32))
        stats = jnp.stack([jnp.sum((kept > 0).astype(jnp.int32)), pairs,
                           n_tokens * k - pairs])

        safe_pos = jnp.where(keep, pos, C - 1)
        buf = jnp.zeros((E, C, d), compute_dtype)
        contrib = jnp.where(keep[:, None],
                            xf[token_of].astype(compute_dtype), 0)
        buf = buf.at[flat_e, safe_pos].add(
            jnp.where(keep[:, None], contrib, 0))
        if constrain_bufs:
            buf = constrain(buf, "expert", "moe_capacity", None)

    # batched expert SwiGLU (weights may be pre-quantized serving codes).
    # Under expert-parallel tensor sharding (``tp_exp``-marked banks inside
    # a dist.tp context) each shard holds E/tp experts: the dispatch buffer
    # is sliced to the local experts, only they run, and an all_gather over
    # the expert axis rebuilds the full output buffer — every element of
    # which is computed by exactly one shard with the unsharded per-expert
    # math, so the (replicated) combine below stays bit-identical to the
    # single-device path.  Routing above ran on the replicated router, so
    # top-k choice, gates, and capacity positions are identical everywhere.
    from repro.dist import tp as tp_lib
    exp_axis = tp_lib.model_axis() if (isinstance(p["wi"], dict)
                                       and "tp_exp" in p["wi"]) else None
    if exp_axis is not None:
        E_local = p["wi"]["w_q"].shape[0]
        start = jax.lax.axis_index(exp_axis) * E_local
        buf = jax.lax.dynamic_slice_in_dim(buf, start, E_local, axis=0)
    with jax.named_scope("moe_experts"):
        h = _expert_einsum(buf, p["wi"], compute_dtype)
        g = _expert_einsum(buf, p["wg"], compute_dtype)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * h
        if exp_axis is None and constrain_bufs:
            h = constrain(h, "expert", "moe_capacity", "expert_mlp")
        out = _expert_einsum(h, p["wo"], compute_dtype, out_contract=True)
    if exp_axis is not None:
        out = jax.lax.all_gather(out, exp_axis, axis=0, tiled=True)
    elif constrain_bufs:
        out = constrain(out, "expert", "moe_capacity", None)

    with jax.named_scope("moe_combine"):
        gathered = out[flat_e, safe_pos]                         # [T*k, d]
        gathered = jnp.where(keep[:, None], gathered, 0)
        w = gate_vals.reshape(-1).astype(jnp.float32)
        # each token's k pairs sit together: sum them in slot order
        yf = jnp.sum((gathered.astype(jnp.float32) * w[:, None]
                      ).reshape(T, k, d), axis=1)
        y = yf.astype(compute_dtype)

    if "shared" in p:
        from repro.models.layers import mlp
        with jax.named_scope("moe_shared"):
            ys = mlp(p["shared"], xf, "swiglu", quant, compute_dtype)
            if cfg.shared_gate:
                sg = jax.nn.sigmoid(linear(
                    p["shared_gate"], xf.astype(jnp.float32), "none",
                    jnp.float32))
                y = y + (sg * ys.astype(jnp.float32)).astype(compute_dtype)
            else:
                y = y + ys.astype(compute_dtype)
    return y, aux, stats
