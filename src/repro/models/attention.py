"""Attention: GQA with blocked (flash-style) softmax, sliding windows,
Gemma-2 logit soft-capping, cross-attention, and KV-cache decode.

Blocked attention keeps the score tensor at [.., q_block, kv_block] so 32k
prefill fits in HBM; the online-softmax recurrence is the standard
FlashAttention algorithm expressed in lax.scan (XLA fuses it well on TPU; a
Pallas flash kernel would be a beyond-paper optimization).

Multi-head latent attention (DeepSeek-V2/V3 MLA, ``init_mla``,
``mla_attention``, ``mla_decode_attention``) caches one compressed latent
``c_kv`` and one shared RoPE key ``k_pe`` per token instead of per-head K/V:
prefill expands them into per-head keys and values, decode folds the
up-projection into the query (the absorbed form) and attends over the
latent cache directly.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models.layers import (Params, apply_mrope, apply_rope, init_linear,
                                 linear, stable_tanh)

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False, dtype=jnp.float32,
                   split_heads: bool = False) -> Params:
    """``split_heads=True`` stores projections as [d, H, dh] (3D) so the
    head axis is a real param dim — sharding then never straddles a reshape
    boundary (kills GSPMD's involuntary resharding permutes when
    H % mesh != 0)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    if not split_heads:
        return {
            "wq": init_linear(kq, d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
            "wk": init_linear(kk, d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
            "wv": init_linear(kv, d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
            "wo": init_linear(ko, n_heads * head_dim, d_model, dtype=dtype),
        }
    import math as _m
    s = 1.0 / _m.sqrt(d_model)
    p = {
        "wq3": {"w": jax.random.normal(kq, (d_model, n_heads, head_dim), dtype) * s},
        "wk3": {"w": jax.random.normal(kk, (d_model, n_kv, head_dim), dtype) * s},
        "wv3": {"w": jax.random.normal(kv, (d_model, n_kv, head_dim), dtype) * s},
        "wo3": {"w": jax.random.normal(ko, (n_heads, head_dim, d_model), dtype)
                * (1.0 / _m.sqrt(n_heads * head_dim))},
    }
    if qkv_bias:
        for k, h in (("wq3", n_heads), ("wk3", n_kv), ("wv3", n_kv)):
            p[k]["b"] = jnp.zeros((h, head_dim), dtype)
    return p


def _proj_qkv(p: Params, name: str, x: jax.Array, B: int, S: int, H: int,
              D: int, quant: str, cd) -> jax.Array:
    """Project to [B, S, h, D] through either the fused-2D or split-3D params.

    ``h`` is derived from the projection output, not the ``H`` argument:
    under head-sharded tensor parallelism (``tp_head``-marked leaves inside
    a ``dist.tp`` context) the projection emits only this shard's
    ``H / tp`` local heads and everything downstream (RoPE, cache writes,
    attention) is per-head math that works on the local slice unchanged.
    """
    if name + "3" in p:
        w = p[name + "3"]["w"].astype(cd)
        y = jnp.einsum("bsd,dhk->bshk", x.astype(cd), w)
        if "b" in p[name + "3"]:
            y = y + p[name + "3"]["b"].astype(cd)
        return y
    return linear(p[name], x, quant, cd).reshape(B, S, -1, D)


def _proj_out(p: Params, out: jax.Array, B: int, S: int, H: int, D: int,
              quant: str, cd) -> jax.Array:
    """Output projection.  ``out`` may hold only this shard's local heads:
    the 2D quantized ``wo`` is row-parallel (its K rows are head-major, so
    the local heads ARE its K slice — ops._row_parallel_prequant psums the
    exact int32 accumulator); the float ``wo3`` stays replicated, so local
    heads are all-gathered back to the full head axis in front of it."""
    if "wo3" in p:
        if out.shape[2] != H:               # head-sharded input
            from repro.dist import tp as tp_lib
            out = jax.lax.all_gather(out, tp_lib.model_axis(), axis=2,
                                     tiled=True)
        return jnp.einsum("bshk,hkd->bsd", out.astype(cd),
                          p["wo3"]["w"].astype(cd))
    return linear(p["wo"], out.reshape(B, S, -1).astype(cd), quant, cd)


def _mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """[..., q, k] boolean keep-mask from absolute positions.

    Negative key positions are sentinels for padding / unwritten cache slots
    and are always masked out.
    """
    m = (k_pos >= 0)[..., None, :]
    m = jnp.broadcast_to(m, q_pos.shape[:-1]
                         + (q_pos.shape[-1], k_pos.shape[-1]))
    d = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m = m & (d >= 0)
    if window is not None:
        m = m & (d < window)
    return m


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      logit_softcap: Optional[float] = None,
                      kv_block: int = 1024) -> jax.Array:
    """q: [B,S,Hq,D], k/v: [B,T,Hkv,D]; GQA via head grouping (no KV repeat).

    Scans over KV blocks with online softmax; score memory is
    O(B * Hq * S * kv_block).  K/V stay in their storage dtype — the score
    matmul accumulates in fp32 via ``preferred_element_type`` instead of
    materializing fp32 copies of the (possibly huge) K/V.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = (q.reshape(B, S, Hkv, G, D) * jnp.asarray(scale, q.dtype))

    nblk = -(-T // kv_block)
    pad = nblk * kv_block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-(10 ** 9))
    kb = k.reshape(B, nblk, kv_block, Hkv, D)
    vb = v.reshape(B, nblk, kv_block, Hkv, D)
    pb = k_pos.reshape(B, nblk, kv_block)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kj, vj, pj = blk                      # [B,kb,Hkv,D], [B,kb]
        s = jnp.einsum("bshgd,bkhd->bshgk", qg, kj,
                       preferred_element_type=jnp.float32)
        if logit_softcap is not None:
            s = logit_softcap * stable_tanh(s / logit_softcap)
        keep = _mask(q_pos, pj, causal, window)   # [B, S, kb]
        s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bshgk,bkhd->bshgd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.moveaxis(pb, 1, 0)))
    out = acc / jnp.maximum(l_f[..., None], 1e-30)
    return out.reshape(B, S, Hq, D)


def full_attention(q, k, v, q_pos, k_pos, *, causal=True, window=None,
                   logit_softcap=None, bias=None) -> jax.Array:
    """Unblocked reference path (tests + short sequences + decode).

    K/V stay in storage dtype (fp32 accumulation via preferred_element_type)
    — for a 32k decode cache this avoids a 2x fp32 materialization.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D) * jnp.asarray(1.0 / math.sqrt(D), q.dtype)
    s = jnp.einsum("bshgd,bkhd->bshgk", qg, k,
                   preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * stable_tanh(s / logit_softcap)
    if bias is not None:
        s = s + bias
    keep = _mask(q_pos, k_pos, causal, window)
    s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgk,bkhd->bshgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hq, v.shape[-1])


def attention(p: Params, x: jax.Array, positions: jax.Array, *,
              n_heads: int, n_kv: int, head_dim: int,
              causal: bool = True, window: Optional[int] = None,
              logit_softcap: Optional[float] = None,
              rope_theta: float = 10000.0, rope_mode: str = "rope",
              mrope_sections: tuple[int, ...] = (),
              mrope_positions: Optional[jax.Array] = None,
              kv_block: int = 1024, quant: str = "none",
              compute_dtype=jnp.bfloat16,
              return_kv: bool = False):
    """Self-attention over a full sequence (train / prefill)."""
    B, S, _ = x.shape
    q = _proj_qkv(p, "wq", x, B, S, n_heads, head_dim, quant, compute_dtype)
    k = _proj_qkv(p, "wk", x, B, S, n_kv, head_dim, quant, compute_dtype)
    v = _proj_qkv(p, "wv", x, B, S, n_kv, head_dim, quant, compute_dtype)
    if rope_mode == "mrope":
        mpos = mrope_positions
        if mpos is None:
            mpos = jnp.broadcast_to(positions[..., None], positions.shape + (3,))
        q = apply_mrope(q, mpos, mrope_sections, rope_theta)
        k = apply_mrope(k, mpos, mrope_sections, rope_theta)
    elif rope_mode == "rope":
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    if S <= 2 * kv_block:
        out = full_attention(q, k, v, positions, positions, causal=causal,
                             window=window, logit_softcap=logit_softcap)
    else:
        out = blocked_attention(q, k, v, positions, positions, causal=causal,
                                window=window, logit_softcap=logit_softcap,
                                kv_block=kv_block)
    out = constrain(out.astype(compute_dtype), "batch", None, "heads", None)
    y = _proj_out(p, out, B, S, n_heads, head_dim, quant, compute_dtype)
    if return_kv:
        return y, (k, v)
    return y


def _pos_vec(pos: jax.Array, B: int) -> jax.Array:
    """Normalize a decode position argument to per-sequence [B] int32.

    Scalar positions (the legacy lock-step schedule) broadcast; [B] vectors
    (continuous batching: every slot at its own depth) pass through.  Negative
    positions are the free-slot sentinel — their keys never unmask.
    """
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(jnp.atleast_1d(pos), (B,))


def _write_kv_slot(cache: jax.Array, new: jax.Array,
                   slot: jax.Array) -> jax.Array:
    """Per-sequence cache write: cache [B,T,...], new [B,1,...], slot [B]."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    )(cache, new.astype(cache.dtype), slot)


# ---------------------------------------------------------------------------
# paged KV cache: ordered gather / per-row page-table writes.  The pool is a
# shared [num_pages, page_size, ...] block store; each batch row owns a
# fixed-shape [E] int32 page-table row.  Gathering the pages in table order
# reconstructs the row's dense [T = E*page_size, ...] buffer with values
# bit-identical to the dense cache (unmapped entries read the reserved null
# page 0, whose junk stays behind the position mask), so the attention math
# downstream is byte-for-byte the dense path.
# ---------------------------------------------------------------------------

def paged_gather(pool: jax.Array, table: jax.Array) -> jax.Array:
    """pool [P, ps, ...], table [B, E] -> dense [B, E*ps, ...] in logical
    order (page j's rows land at positions [j*ps, (j+1)*ps))."""
    B, E = table.shape
    g = jnp.take(pool, table, axis=0)            # [B, E, ps, ...]
    return g.reshape((B, E * pool.shape[1]) + pool.shape[2:])


def paged_write(pool: jax.Array, table: jax.Array, slot: jax.Array,
                new: jax.Array) -> jax.Array:
    """One decode-token write through the page table.

    pool [P, ps, ...]; table [B, E]; slot [B] int32 (the token's slot in the
    row's logical buffer — absolute position, or ``pos % window`` for SWA
    rings); new [B, 1, ...].  A free slot's table row is all zeros, so its
    idempotent write lands in the null page; active rows own their current
    page exclusively, so the scatter never collides across rows.
    """
    ps = pool.shape[1]
    page = jnp.take_along_axis(table, (slot // ps)[:, None], axis=1)[:, 0]
    return pool.at[page, slot % ps].set(new[:, 0].astype(pool.dtype))


def _read_kv(cache_k: jax.Array, cache_v: jax.Array,
             table: Optional[jax.Array]) -> tuple:
    """The dense [B, T, ...] K and V buffers attention reads: the caches
    themselves, or their ordered page gather under a page table."""
    if table is None:
        return cache_k, cache_v
    return paged_gather(cache_k, table), paged_gather(cache_v, table)


def _write_kv_block(cache: jax.Array, new: jax.Array,
                    start: jax.Array) -> jax.Array:
    """Contiguous S-token cache write: cache [B,T,...], new [B,S,...],
    start [B] (dynamic_update_slice clamps starts into [0, T-S])."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    )(cache, new.astype(cache.dtype), start)


def decode_kv_positions(pos: jax.Array, T: int, rolling: bool) -> jax.Array:
    """Absolute positions of cache slots for per-sequence decode.

    pos: [B] int32 (position being written this step).  Returns [B, T] with
    the negative sentinel on unwritten / out-of-ring slots.
    """
    idx = jnp.arange(T)[None]                                  # [1, T]
    posb = pos[:, None]
    if rolling:
        # slot i holds absolute position: the largest p <= pos with p % T == i
        k_pos = posb - ((posb - idx) % T)
        return jnp.where(k_pos < 0, -(10 ** 9), k_pos)
    return jnp.where((idx <= posb) & (posb >= 0), idx, -(10 ** 9))


def decode_attention(p: Params, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, pos: jax.Array, *,
                     n_heads: int, n_kv: int, head_dim: int,
                     window: Optional[int] = None,
                     logit_softcap: Optional[float] = None,
                     rope_theta: float = 10000.0, rope_mode: str = "rope",
                     mrope_sections: tuple[int, ...] = (),
                     rolling: bool = False,
                     quant: str = "none", compute_dtype=jnp.bfloat16,
                     table: Optional[jax.Array] = None):
    """One decode step. x: [B, 1, d]; cache: [B, T, Hkv, D]; pos: scalar or
    per-sequence [B] int32 (continuous batching: slots at different depths).

    Returns (y, new_cache_k, new_cache_v).  With ``rolling=True`` the cache is
    a ring buffer of size ``window`` (SWA serving — bounded memory, the
    Mistral/Mixtral rolling cache); slot addressing is per-sequence
    ``pos[b] % T``.  A negative ``pos[b]`` marks a free slot: its write lands
    inside its own (free) row and every key stays masked.

    ``table`` ([B, E] int32) switches the cache arguments to paged pools
    ([P, page_size, Hkv, D]): the token write scatters through the row's
    page table and attention runs over the ordered page gather — the dense
    [B, T, Hkv, D] buffer reconstructed value-for-value, so the output is
    bit-identical to the dense path.
    """
    B = x.shape[0]
    paged = table is not None
    T = table.shape[1] * cache_k.shape[1] if paged else cache_k.shape[1]
    q = _proj_qkv(p, "wq", x, B, 1, n_heads, head_dim, quant, compute_dtype)
    k = _proj_qkv(p, "wk", x, B, 1, n_kv, head_dim, quant, compute_dtype)
    v = _proj_qkv(p, "wv", x, B, 1, n_kv, head_dim, quant, compute_dtype)
    posv = _pos_vec(pos, B)
    posb = posv[:, None]                                       # [B,1]
    if rope_mode == "mrope":
        mpos = jnp.broadcast_to(posb[..., None], (B, 1, 3))
        q = apply_mrope(q, mpos, mrope_sections, rope_theta)
        k = apply_mrope(k, mpos, mrope_sections, rope_theta)
    elif rope_mode == "rope":
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    slot = posv % T if rolling else jnp.clip(posv, 0, T - 1)
    with jax.named_scope("kv_write"):
        if paged:
            cache_k = paged_write(cache_k, table, slot, k)
            cache_v = paged_write(cache_v, table, slot, v)
        else:
            cache_k = _write_kv_slot(cache_k, k, slot)
            cache_v = _write_kv_slot(cache_v, v, slot)
    with jax.named_scope("attend"):
        dense_k, dense_v = _read_kv(cache_k, cache_v, table)
        k_pos = decode_kv_positions(posv, T, rolling)
        out = full_attention(q, dense_k, dense_v, posb, k_pos, causal=True,
                             window=window, logit_softcap=logit_softcap)
    y = _proj_out(p, out.astype(compute_dtype), B, 1, n_heads, head_dim,
                  quant, compute_dtype)
    return y, cache_k, cache_v


def decode_attention_multi(p: Params, x: jax.Array, cache_k: jax.Array,
                           cache_v: jax.Array, pos: jax.Array, *,
                           n_heads: int, n_kv: int, head_dim: int,
                           logit_softcap: Optional[float] = None,
                           rope_theta: float = 10000.0, rope_mode: str = "rope",
                           mrope_sections: tuple[int, ...] = (),
                           quant: str = "none", compute_dtype=jnp.bfloat16,
                           table: Optional[jax.Array] = None):
    """A contiguous S-token decode block in one call (speculative verify).

    x: [B, S, d]; pos: [B] int32 start positions — token i of a row sits at
    ``pos + i``.  All S writes land *before* attention, and the causal mask
    hides keys past ``pos + i`` from query i, so output position i is
    bit-identical to what S sequential :func:`decode_attention` calls would
    produce (same einsum contractions, per-row independent reductions —
    the chunked-prefill differentials' invariant).

    Only the full-length (non-rolling) cache layout: SWA rings are excluded
    from speculative rounds by the engine's eligibility check.  Negative
    ``pos`` rows (free slots) clamp their writes into their own row / the
    null page and keep every key masked, exactly like single-token decode.
    """
    B, S = x.shape[:2]
    paged = table is not None
    T = table.shape[1] * cache_k.shape[1] if paged else cache_k.shape[1]
    q = _proj_qkv(p, "wq", x, B, S, n_heads, head_dim, quant, compute_dtype)
    k = _proj_qkv(p, "wk", x, B, S, n_kv, head_dim, quant, compute_dtype)
    v = _proj_qkv(p, "wv", x, B, S, n_kv, head_dim, quant, compute_dtype)
    posv = _pos_vec(pos, B)
    q_pos = posv[:, None] + jnp.arange(S, dtype=jnp.int32)[None]   # [B,S]
    if rope_mode == "mrope":
        mpos = jnp.broadcast_to(q_pos[..., None], (B, S, 3))
        q = apply_mrope(q, mpos, mrope_sections, rope_theta)
        k = apply_mrope(k, mpos, mrope_sections, rope_theta)
    elif rope_mode == "rope":
        q = apply_rope(q, q_pos, rope_theta)
        k = apply_rope(k, q_pos, rope_theta)
    with jax.named_scope("kv_write"):
        if paged:
            # S sequential table writes (deterministic, and unmapped/free
            # rows collapse into the null page exactly like single-token
            # decode)
            for i in range(S):
                slot = jnp.clip(posv + i, 0, T - 1)
                cache_k = paged_write(cache_k, table, slot, k[:, i:i + 1])
                cache_v = paged_write(cache_v, table, slot, v[:, i:i + 1])
        else:
            cache_k = _write_kv_block(cache_k, k, posv)
            cache_v = _write_kv_block(cache_v, v, posv)
    with jax.named_scope("attend"):
        dense_k, dense_v = _read_kv(cache_k, cache_v, table)
        # free rows keep posv < 0 so every key stays masked for them
        k_pos = decode_kv_positions(
            jnp.where(posv >= 0, posv + (S - 1), posv), T, rolling=False)
        out = full_attention(q, dense_k, dense_v, q_pos, k_pos, causal=True,
                             window=None, logit_softcap=logit_softcap)
    y = _proj_out(p, out.astype(compute_dtype), B, S, n_heads, head_dim,
                  quant, compute_dtype)
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# int8-quantized KV cache decode (beyond-paper: the paper's integer-MAC idea
# applied to the decode bottleneck — KV bytes halve vs bf16, QK^T and PV run
# as int8 MACs with fp32 rescale; per-token-per-head scales)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array):
    """x: [B, T, H, D] -> (int8 codes, scales [B, T, H])."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                        1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def int8_kv_attention(q: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                      v_q: jax.Array, v_scale: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array, *,
                      window: Optional[int] = None,
                      logit_softcap: Optional[float] = None) -> jax.Array:
    """Decode attention over an int8 cache. q: [B,1,Hq,D] float."""
    B, S, Hq, D = q.shape
    Hkv = k_q.shape[2]
    G = Hq // Hkv
    # integer QK^T: quantize q per (b, head) row
    qg = q.reshape(B, S, Hkv, G, D)
    q_scale = jnp.maximum(jnp.max(jnp.abs(qg.astype(jnp.float32)), axis=-1),
                          1e-8) / 127.0
    q_int = jnp.clip(jnp.round(qg.astype(jnp.float32) / q_scale[..., None]),
                     -127, 127).astype(jnp.int8)
    s_int = jnp.einsum("bshgd,bkhd->bshgk", q_int, k_q,
                       preferred_element_type=jnp.int32)
    # scale[b,s,h,g,t] = q_scale[b,s,h,g] * k_scale[b,t,h]
    scale = q_scale[..., None] \
        * jnp.moveaxis(k_scale, 1, -1)[:, None, :, None, :]
    s = s_int.astype(jnp.float32) * scale / math.sqrt(D)
    if logit_softcap is not None:
        s = logit_softcap * stable_tanh(s / logit_softcap)
    keep = _mask(q_pos, k_pos, True, window)
    s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # integer PV: fold the per-key v_scale into p (exact), then quantize the
    # effective probabilities to int8 rows
    vs = jnp.moveaxis(v_scale, 1, -1)[:, None, :, None, :]   # [B,1,Hkv,1,T]
    p_eff = p * vs
    p_scale = jnp.maximum(jnp.max(jnp.abs(p_eff), axis=-1), 1e-12) / 127.0
    p_int = jnp.round(p_eff / p_scale[..., None]).astype(jnp.int8)
    o_int = jnp.einsum("bshgk,bkhd->bshgd", p_int, v_q,
                       preferred_element_type=jnp.int32)
    o = o_int.astype(jnp.float32) * p_scale[..., None]
    return o.reshape(B, S, Hq, D)


def decode_attention_int8(p: Params, x: jax.Array, cache: dict,
                          pos: jax.Array, *, n_heads: int, n_kv: int,
                          head_dim: int, window: Optional[int] = None,
                          logit_softcap: Optional[float] = None,
                          rope_theta: float = 10000.0, rope_mode: str = "rope",
                          mrope_sections: tuple[int, ...] = (),
                          quant: str = "none", compute_dtype=jnp.bfloat16,
                          table: Optional[jax.Array] = None):
    """One decode step over an int8-quantized cache.

    cache: {"k": s8[B,T,Hkv,D], "v": s8, "k_scale": f32[B,T,Hkv],
            "v_scale": f32[B,T,Hkv]}.  pos: scalar or per-sequence [B].
    ``table`` switches the four cache leaves to paged pools
    ([P, page_size, ...] — int8 codes AND their per-token-per-head scales
    page together, so every page carries its own scales).
    """
    B = x.shape[0]
    paged = table is not None
    T = table.shape[1] * cache["k"].shape[1] if paged else cache["k"].shape[1]
    q = _proj_qkv(p, "wq", x, B, 1, n_heads, head_dim, quant, compute_dtype)
    k = _proj_qkv(p, "wk", x, B, 1, n_kv, head_dim, quant, compute_dtype)
    v = _proj_qkv(p, "wv", x, B, 1, n_kv, head_dim, quant, compute_dtype)
    posv = _pos_vec(pos, B)
    posb = posv[:, None]
    if rope_mode == "rope":
        q = apply_rope(q, posb, rope_theta)
        k = apply_rope(k, posb, rope_theta)
    elif rope_mode == "mrope":
        mpos = jnp.broadcast_to(posb[..., None], (B, 1, 3))
        q = apply_mrope(q, mpos, mrope_sections, rope_theta)
        k = apply_mrope(k, mpos, mrope_sections, rope_theta)
    k_new, ks_new = quantize_kv(k)
    v_new, vs_new = quantize_kv(v)
    slot = jnp.clip(posv, 0, T - 1)
    cache = dict(cache)
    write = ((lambda c, n: paged_write(c, table, slot, n)) if paged
             else (lambda c, n: _write_kv_slot(c, n, slot)))
    with jax.named_scope("kv_write"):
        cache["k"] = write(cache["k"], k_new)
        cache["v"] = write(cache["v"], v_new)
        cache["k_scale"] = write(cache["k_scale"], ks_new)
        cache["v_scale"] = write(cache["v_scale"], vs_new)
    dense = ((lambda c: paged_gather(c, table)) if paged else (lambda c: c))
    with jax.named_scope("attend"):
        k_pos = decode_kv_positions(posv, T, rolling=False)
        out = int8_kv_attention(q, dense(cache["k"]), dense(cache["k_scale"]),
                                dense(cache["v"]), dense(cache["v_scale"]),
                                posb, k_pos, window=window,
                                logit_softcap=logit_softcap)
    y = _proj_out(p, out.astype(compute_dtype), B, 1, n_heads, head_dim,
                  quant, compute_dtype)
    return y, cache


def cross_attention(p: Params, x: jax.Array, enc: jax.Array, *,
                    n_heads: int, n_kv: int, head_dim: int,
                    quant: str = "none", compute_dtype=jnp.bfloat16):
    """Encoder-decoder cross attention (Whisper decoder)."""
    B, S, _ = x.shape
    T = enc.shape[1]
    q = linear(p["wq"], x, quant, compute_dtype).reshape(B, S, -1, head_dim)
    k = linear(p["wk"], enc, quant, compute_dtype).reshape(B, T, -1, head_dim)
    v = linear(p["wv"], enc, quant, compute_dtype).reshape(B, T, -1, head_dim)
    q_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    k_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    out = full_attention(q, k, v, q_pos, k_pos, causal=False)
    return linear(p["wo"], out.reshape(B, S, -1).astype(compute_dtype),
                  quant, compute_dtype)


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3 MLA).  Per token the cache
# holds c_kv (the kv_a projection's first kv_lora_rank outputs after their
# RMSNorm) and k_pe (its last qk_rope_dim outputs after RoPE, one key shared
# by every head).  wkv_b maps c_kv to each head's [nope key | value]; it is
# a float leaf, so the expanded form (prefill) and the absorbed form
# (decode) are the same function of c_kv.
# ---------------------------------------------------------------------------

def init_mla(key, d_model: int, n_heads: int, mla, dtype=jnp.float32
             ) -> Params:
    """``mla``: a ``transformer.MLAConfig`` (kv_lora_rank, qk_nope_dim,
    qk_rope_dim, v_head_dim).  ``wq`` has no low-rank factorisation
    (``q_lora_rank`` null) and no projection has a bias."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    r, nope, rope, dv = (mla.kv_lora_rank, mla.qk_nope_dim, mla.qk_rope_dim,
                         mla.v_head_dim)
    return {
        "wq": init_linear(kq, d_model, n_heads * (nope + rope), dtype=dtype),
        "wkv_a": init_linear(ka, d_model, r + rope, dtype=dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": init_linear(kb, r, n_heads * (nope + dv), dtype=dtype),
        "wo": init_linear(ko, n_heads * dv, d_model, dtype=dtype),
    }


def deinterleave(x: jax.Array) -> jax.Array:
    """[..., D] pairs (x0, x1), (x2, x3), ... -> [evens | odds]: the order
    DeepSeek's ``apply_rotary_pos_emb`` puts the rope dims in before its
    rotate-half RoPE."""
    D = x.shape[-1]
    return jnp.swapaxes(x.reshape(x.shape[:-1] + (D // 2, 2)), -1, -2
                        ).reshape(x.shape)


def mla_project(p: Params, x: jax.Array, positions: jax.Array, *,
                n_heads: int, mla, rope_theta: float, norm_eps: float,
                quant: str, compute_dtype):
    """x [B, S, d] -> (q_nope [B,S,H,nope], q_pe [B,S,H,rope] after RoPE,
    c_kv [B,S,r] after its norm, k_pe [B,S,rope] after RoPE)."""
    from repro.models.layers import rms_norm
    B, S, _ = x.shape
    r, nope = mla.kv_lora_rank, mla.qk_nope_dim
    q = linear(p["wq"], x, quant, compute_dtype).reshape(B, S, n_heads, -1)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = linear(p["wkv_a"], x, quant, compute_dtype)
    c_kv = rms_norm(p["kv_norm"], kv[..., :r], norm_eps)
    q_pe = apply_rope(deinterleave(q_pe), positions, rope_theta)
    k_pe = apply_rope(deinterleave(kv[..., r:])[:, :, None], positions,
                      rope_theta)[:, :, 0]
    return q_nope, q_pe, c_kv, k_pe


def _kv_b(p: Params, mla, n_heads: int, cd):
    """wkv_b split per head: (W_UK [r, H, nope], W_UV [r, H, v])."""
    w = p["wkv_b"]["w"].astype(cd).reshape(mla.kv_lora_rank, n_heads, -1)
    return w[..., :mla.qk_nope_dim], w[..., mla.qk_nope_dim:]


def mla_attention(p: Params, x: jax.Array, positions: jax.Array, *,
                  n_heads: int, mla, rope_theta: float, norm_eps: float,
                  quant: str = "none", compute_dtype=jnp.bfloat16,
                  return_cache: bool = False):
    """Causal MLA over a full sequence (train / prefill), expanded form:
    per-head keys [k_nope | k_pe] (qk dim nope + rope) and values from
    ``wkv_b``, softmax scale 1/sqrt(nope + rope).  The batch rows attend
    one at a time (each row's sums are the same), so a long batched
    prefill holds one row's keys, values and scores.  ``return_cache``
    also returns the latent rows {"c_kv", "k_pe"} the decode cache
    holds."""
    cd = compute_dtype
    B, S, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = mla_project(
        p, x, positions, n_heads=n_heads, mla=mla, rope_theta=rope_theta,
        norm_eps=norm_eps, quant=quant, compute_dtype=cd)
    w_uk, w_uv = _kv_b(p, mla, n_heads, cd)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)

    def rows(q, c, kp, pos):
        k_nope = jnp.einsum("bsr,rhd->bshd", c, w_uk,
                            preferred_element_type=jnp.float32).astype(cd)
        v = jnp.einsum("bsr,rhd->bshd", c, w_uv,
                       preferred_element_type=jnp.float32).astype(cd)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            kp[:, :, None], k_nope.shape[:3] + kp.shape[-1:])], axis=-1)
        return full_attention(q, k, v, pos, pos, causal=True)

    out = jax.lax.map(lambda a: rows(*(t[None] for t in a))[0],
                      (q, c_kv, k_pe, positions))
    y = linear(p["wo"], out.astype(cd).reshape(B, S, -1), quant, cd)
    if return_cache:
        return y, {"c_kv": c_kv.astype(cd), "k_pe": k_pe.astype(cd)}
    return y


def mla_decode_attention(p: Params, x: jax.Array, cache: dict,
                         pos: jax.Array, *, n_heads: int, mla,
                         rope_theta: float, norm_eps: float,
                         quant: str = "none", compute_dtype=jnp.bfloat16):
    """One decode step over the latent cache, absorbed form.  x [B, 1, d];
    cache {"c_kv": [B, T, r], "k_pe": [B, T, rope]}; pos scalar or [B]
    (negative: a free slot, every key masked).

    W_UK folds into the query (q_lat = q_nope W_UK^T, [B, H, r]); scores
    are q_lat . c_kv + q_pe . k_pe times 1/sqrt(nope + rope); the softmax
    reads c_kv back (the latent readout, [B, H, r]), then W_UV and wo.
    Returns (y, cache)."""
    cd = compute_dtype
    B = x.shape[0]
    T = cache["c_kv"].shape[1]
    posv = _pos_vec(pos, B)
    posb = posv[:, None]
    q_nope, q_pe, c_kv, k_pe = mla_project(
        p, x, posb, n_heads=n_heads, mla=mla, rope_theta=rope_theta,
        norm_eps=norm_eps, quant=quant, compute_dtype=cd)
    slot = jnp.clip(posv, 0, T - 1)
    with jax.named_scope("kv_write"):
        cache = {"c_kv": _write_kv_slot(cache["c_kv"], c_kv, slot),
                 "k_pe": _write_kv_slot(cache["k_pe"], k_pe, slot)}
    with jax.named_scope("mla"):
        w_uk, w_uv = _kv_b(p, mla, n_heads, cd)
        scale = 1.0 / math.sqrt(mla.qk_nope_dim + mla.qk_rope_dim)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk,
                           preferred_element_type=jnp.float32).astype(cd)
        s = (jnp.einsum("bhr,btr->bht", q_lat, cache["c_kv"],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhe,bte->bht", q_pe[:, 0], cache["k_pe"],
                          preferred_element_type=jnp.float32)) * scale
        keep = _mask(posb, decode_kv_positions(posv, T, False), True, None)
        s = jnp.where(keep, s, NEG_INF)          # keep [B, 1, T]
        prob = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bht,btr->bhr", prob.astype(cd), cache["c_kv"],
                           preferred_element_type=jnp.float32).astype(cd)
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv,
                         preferred_element_type=jnp.float32).astype(cd)
    y = linear(p["wo"], out.reshape(B, 1, -1), quant, cd)
    return y, cache
