"""Decoder-LM assembly: pattern-based blocks, scanned over repeated groups.

A model is ``embed -> lead -> scan(groups) -> final_norm -> lm_head`` where
one group is one repetition of ``cfg.pattern`` (e.g. Gemma-2: (local, global)
x 13; Zamba2: (mamba x 6 + shared attn at position 0) x 9; RWKV6: (rwkv,) x
24).  Scanning over groups keeps the HLO small (critical for 512-device
dry-run compiles) and makes remat policies uniform.  ``cfg.lead`` blocks
(DeepSeek-V3's leading dense layers) run once each before the scan, from
``params["lead"]["layer<i>"]``.

All block params for one pattern position are stacked along a leading G axis.
A decode cache is a tuple with one entry per leading block, then one per
pattern position (``layer_specs``); every entry's leaves lead with their
layer axis (1 for a leading block, G in the scan).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (init_embedding, init_linear, init_mlp,
                                 init_norm, layer_norm, mlp, rms_norm,
                                 softcap)
from repro.models.moe import MoEConfig, init_moe, moe_ffn


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockSpec:
    kind: str = "attn"              # attn | mamba2 | rwkv6
    attn_type: str = "global"       # global | local | mla
    mlp: str = "swiglu"             # swiglu | geglu | gelu | moe | rwkv_cm | none
    shared_attn: bool = False       # prepend the shared attention block (zamba2)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention sizes (DeepSeek-V2/V3)."""
    kv_lora_rank: int               # latent c_kv width
    qk_nope_dim: int                # per-head key/query dims without RoPE
    qk_rope_dim: int                # RoPE dims, one key shared by all heads
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: tuple[BlockSpec, ...] = (BlockSpec(),)
    window: Optional[int] = None
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    rope_mode: str = "rope"         # rope | mrope | none
    mrope_sections: tuple[int, ...] = ()
    qkv_bias: bool = False
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    gemma_norms: bool = False       # zero-centered scale + post-block norms
    tie_embeddings: bool = False
    embed_scale: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None  # sizes of attn_type="mla" blocks
    lead: tuple[BlockSpec, ...] = ()  # blocks before the scanned groups
    norm_eps: float = 1e-6          # every RMSNorm's eps
    # ssm / rwkv
    d_inner: int = 0
    d_state: int = 0
    ssm_heads: int = 0
    rwkv_heads: int = 0
    # enc-dec (whisper)
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500
    frontend: str = "none"          # none | audio | vision (stubs)
    # execution
    quant: str = "none"             # none|qat|w4a4_lut|w4a4_mxu|w8a8|
                                    # w{1,2,3,4}a{4,8}[_tmac]|
                                    # ternary_a{4,8}[_tmac] (tmac bitplanes)
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "full"             # full | dots | none
    kv_block: int = 1024
    split_head_params: bool = False  # store QKV/O as [d,H,dh] (3D) — head
                                     # sharding without reshape straddling
    rwkv_chunk: int = 32            # WKV chunk length (memory-term lever)
    kv_quant: str = "none"          # none | int8 — quantized decode KV cache
    unroll_groups: bool = False     # dry-run: unroll the group scan so
                                    # cost_analysis counts every layer
    long_context_ok: bool = False   # sub-quadratic family -> long_500k runs

    @property
    def n_groups(self) -> int:
        body = self.n_layers - len(self.lead)
        assert body % len(self.pattern) == 0
        return body // len(self.pattern)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, spec: BlockSpec) -> dict:
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    dt = cfg.pdtype
    if spec.kind == "attn" and spec.attn_type == "mla":
        p["ln1"] = init_norm(cfg.d_model, dt)
        p["attn"] = attn_lib.init_mla(ks[0], cfg.d_model, cfg.n_heads,
                                      cfg.mla, dt)
    elif spec.kind == "attn":
        p["ln1"] = init_norm(cfg.d_model, dt)
        p["attn"] = attn_lib.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                            cfg.n_kv, cfg.head_dim,
                                            cfg.qkv_bias, dt,
                                            split_heads=cfg.split_head_params)
        if cfg.gemma_norms:
            p["post_attn_ln"] = init_norm(cfg.d_model, dt)
    elif spec.kind == "mamba2":
        p["ln1"] = init_norm(cfg.d_model, dt)
        p["mamba"] = ssm_lib.init_mamba2(ks[0], cfg.d_model, cfg.d_inner,
                                         cfg.d_state, cfg.ssm_heads, dtype=dt)
    elif spec.kind == "rwkv6":
        p["ln1"] = init_norm(cfg.d_model, dt)
        p["tmix"] = ssm_lib.init_rwkv6(ks[0], cfg.d_model, cfg.rwkv_heads,
                                       dtype=dt)
    else:
        raise ValueError(spec.kind)
    if spec.mlp == "moe":
        p["ln2"] = init_norm(cfg.d_model, dt)
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.moe, dt)
    elif spec.mlp == "rwkv_cm":
        p["ln2"] = init_norm(cfg.d_model, dt)
        p["cmix"] = ssm_lib.init_rwkv6_chanmix(ks[1], cfg.d_model, cfg.d_ff, dt)
    elif spec.mlp != "none":
        p["ln2"] = init_norm(cfg.d_model, dt)
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, spec.mlp, dt)
        if cfg.gemma_norms:
            p["post_mlp_ln"] = init_norm(cfg.d_model, dt)
    return p


def group_keys(keys: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Per-block init keys of ``init_params``' split, shaped [G, P, ...]:
    block (group g, pattern position pi) uses ``keys[g * P + pi]``."""
    G, P = cfg.n_groups, len(cfg.pattern)
    return keys[:G * P].reshape((G, P) + keys.shape[1:])


def init_group(gkeys: jax.Array, cfg: ModelConfig) -> tuple:
    """One group's block params, one dict per pattern position."""
    return tuple(_init_block(gkeys[pi], cfg, spec)
                 for pi, spec in enumerate(cfg.pattern))


def layer_specs(cfg) -> tuple:
    """The block spec of each decode-cache entry: the leading blocks, then
    the pattern positions."""
    return tuple(getattr(cfg, "lead", ())) + tuple(cfg.pattern)


def init_outer(keys: jax.Array, cfg: ModelConfig) -> dict:
    """Everything outside the scanned blocks: embedding, final norm, LM head,
    the shared attention block and the leading blocks (``init_params``'
    keys after the scanned groups' and at its tail)."""
    params = {
        "embed": init_embedding(keys[-1], cfg.vocab, cfg.d_model, cfg.pdtype),
        "final_norm": init_norm(cfg.d_model, cfg.pdtype),
    }
    if cfg.lead:
        first = cfg.n_groups * len(cfg.pattern)
        params["lead"] = {f"layer{i}": _init_block(keys[first + i], cfg, spec)
                          for i, spec in enumerate(cfg.lead)}
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(keys[-2], cfg.d_model, cfg.vocab,
                                        dtype=cfg.pdtype)
    if any(s.shared_attn for s in cfg.pattern):
        params["shared_attn"] = {
            "ln": init_norm(cfg.d_model, cfg.pdtype),
            "attn": attn_lib.init_attention(keys[-3], cfg.d_model, cfg.n_heads,
                                            cfg.n_kv, cfg.head_dim,
                                            cfg.qkv_bias, cfg.pdtype),
            "mlp_ln": init_norm(cfg.d_model, cfg.pdtype),
            "mlp": init_mlp(keys[-4], cfg.d_model, cfg.d_ff, "swiglu",
                            cfg.pdtype),
        }
    return params


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, cfg.n_layers + 4)
    gk = group_keys(keys, cfg)
    # stack per pattern-position
    per_group = [init_group(gk[g], cfg) for g in range(cfg.n_groups)]
    blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_group)
    return {**init_outer(keys, cfg), "blocks": blocks}


# ---------------------------------------------------------------------------
# forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------

def _norm(pnorm, x, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return layer_norm(pnorm, x)
    return rms_norm(pnorm, x, cfg.norm_eps, zero_centered=cfg.gemma_norms)


def _mla_kw(cfg: ModelConfig) -> dict:
    return dict(n_heads=cfg.n_heads, mla=cfg.mla, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps, quant=_infer_quant(cfg),
                compute_dtype=cfg.cdtype)


def _moe(bp: dict, cfg: ModelConfig, h: jax.Array, decode: bool = False):
    """The block's MoE over its normed input ``h`` [B, S, d]: (y, aux loss,
    int32[3] counters).  A decode step's global-dispatch MoE takes a
    capacity from the batch size alone."""
    det_cap = None
    if decode and cfg.moe.dispatch == "global":
        det_cap = max(1, int(h.shape[0] * cfg.moe.top_k / cfg.moe.n_experts
                             * cfg.moe.capacity_factor) + 1)
    return moe_ffn(bp["moe"], h, cfg.moe, quant=_infer_quant(cfg),
                   compute_dtype=cfg.cdtype, deterministic_capacity=det_cap,
                   with_stats=True)


def _block_fwd(bp: dict, spec: BlockSpec, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array, shared_p: Optional[dict],
               mrope_positions=None, aux_acc=None):
    cd = cfg.cdtype
    if spec.shared_attn and shared_p is not None:
        h = _norm(shared_p["ln"], x, cfg)
        x = x + attn_lib.attention(
            shared_p["attn"], h, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim, causal=True,
            rope_theta=cfg.rope_theta, rope_mode=cfg.rope_mode,
            kv_block=cfg.kv_block, quant=_infer_quant(cfg),
            compute_dtype=cd)
        h = _norm(shared_p["mlp_ln"], x, cfg)
        x = x + mlp(shared_p["mlp"], h, "swiglu", _infer_quant(cfg), cd)
    h = _norm(bp["ln1"], x, cfg)
    if spec.kind == "attn" and spec.attn_type == "mla":
        x = x + attn_lib.mla_attention(bp["attn"], h, positions,
                                       **_mla_kw(cfg))
    elif spec.kind == "attn":
        window = cfg.window if spec.attn_type == "local" else None
        y = attn_lib.attention(
            bp["attn"], h, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, causal=True, window=window,
            logit_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            rope_mode=cfg.rope_mode, mrope_sections=cfg.mrope_sections,
            mrope_positions=mrope_positions, kv_block=cfg.kv_block,
            quant=_infer_quant(cfg), compute_dtype=cd)
        if cfg.gemma_norms:
            y = _norm(bp["post_attn_ln"], y, cfg)
        x = x + y
    elif spec.kind == "mamba2":
        x = x + ssm_lib.mamba2(bp["mamba"], h, d_inner=cfg.d_inner,
                               d_state=cfg.d_state, n_heads=cfg.ssm_heads,
                               quant=_infer_quant(cfg), compute_dtype=cd)
    elif spec.kind == "rwkv6":
        x = x + ssm_lib.rwkv6_timemix(bp["tmix"], h, n_heads=cfg.rwkv_heads,
                                      chunk=cfg.rwkv_chunk,
                                      quant=_infer_quant(cfg), compute_dtype=cd)
    if spec.mlp == "moe":
        h = _norm(bp["ln2"], x, cfg)
        y, aux, _ = _moe(bp, cfg, h)
        x = x + y
        if aux_acc is not None:
            aux_acc = aux_acc + aux
    elif spec.mlp == "rwkv_cm":
        h = _norm(bp["ln2"], x, cfg)
        h_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        x = x + ssm_lib.rwkv6_chanmix(bp["cmix"], h, h_prev,
                                      quant=_infer_quant(cfg), compute_dtype=cd)
    elif spec.mlp != "none":
        h = _norm(bp["ln2"], x, cfg)
        y = mlp(bp["mlp"], h, spec.mlp, quant=_infer_quant(cfg),
                compute_dtype=cd)
        if cfg.gemma_norms:
            y = _norm(bp["post_mlp_ln"], y, cfg)
        x = x + y
    x = constrain(x, "batch", "seq", None)
    return x, aux_acc


def _infer_quant(cfg: ModelConfig) -> str:
    return cfg.quant


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def maybe_scan(body, carry, xs, unroll: bool):
    """lax.scan, or an unrolled python loop (dry-run cost accounting)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    G = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for g in range(G):
        xg = jax.tree_util.tree_map(lambda a: a[g], xs)
        carry, y = body(carry, xg)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


def _lm_head(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final projection; handles tied embeddings and pre-quantized heads."""
    if cfg.tie_embeddings:
        return x @ params["embed"]["emb"].T.astype(x.dtype)
    lh = params["lm_head"]
    if "w_q" in lh:
        from repro.dist.tp import leaf_tp_mode
        from repro.kernels.lutmul import ops as lut_ops
        return lut_ops.prequant_matmul(x, lh["w_q"], lh["w_scale"],
                                       mode=cfg.quant, compute_dtype=x.dtype,
                                       tp=leaf_tp_mode(lh))
    return x @ lh["w"].astype(x.dtype)


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            embeddings: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss).

    ``embeddings`` (if given) bypasses the token embed — the stub modality
    frontend path for [audio]/[vlm] archs.
    """
    cd = cfg.cdtype
    if embeddings is not None:
        x = embeddings.astype(cd)
        B, S = x.shape[:2]
    else:
        B, S = tokens.shape
        x = params["embed"]["emb"].astype(cd)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = constrain(x, "batch", "seq", None)
    shared_p = params.get("shared_attn")
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(cfg.lead):
        x, aux = _block_fwd(params["lead"][f"layer{i}"], spec, cfg, x,
                            positions, shared_p, mrope_positions, aux)

    def group_body(carry, group_params):
        x, aux = carry
        for bp, spec in zip(group_params, cfg.pattern):
            x, aux = _block_fwd(bp, spec, cfg, x, positions, shared_p,
                                mrope_positions, aux)
        return (x, aux), None

    body = group_body
    if cfg.remat != "none":
        body = jax.checkpoint(group_body, policy=_remat_policy(cfg),
                              prevent_cse=False)
    (x, aux), _ = maybe_scan(body, (x, aux), params["blocks"],
                             cfg.unroll_groups)
    x = _norm(params["final_norm"], x, cfg)
    logits = _lm_head(params, cfg, x.astype(cd))
    logits = constrain(logits, "batch", "seq", "vocab")
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits, aux


def loss_fn(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Causal LM loss (mean NLL) + MoE aux. batch: tokens [B,S+1] or
    (tokens, labels)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    embeddings = batch.get("embeddings")
    logits, aux = forward(params, cfg, tokens, embeddings=embeddings,
                          mrope_positions=batch.get("mrope_positions"))
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + aux


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with typed caches
# ---------------------------------------------------------------------------

def _roll_local(k: jax.Array, S: int, W: int) -> jax.Array:
    """Last-W slice arranged so slot i holds the token with abs_pos % W == i
    (matches decode_attention's ring-buffer addressing)."""
    tail = k[:, max(0, S - W):]
    if S < W:
        tail = jnp.pad(tail, ((0, 0), (0, W - S)) + ((0, 0),) * (k.ndim - 2))
        return tail
    return jnp.roll(tail, S % W, axis=1)


def _block_prefill(bp, cache_tmpl, spec: BlockSpec, cfg: ModelConfig,
                   x, positions, shared_p, mrope_positions=None,
                   full_kv: bool = False):
    """Like _block_fwd but also emits the cache entry for decode handoff.

    ``full_kv=True`` keeps local/SWA layers' K/V at full sequence length
    instead of rolling them into a window-size ring — the serving scheduler
    stitches the ring itself from the true (traced) prompt length, so padded
    prompt buckets never leak junk into ring slots.
    """
    cd = cfg.cdtype
    q = _infer_quant(cfg)
    S = x.shape[1]
    cache = {}
    if spec.shared_attn and shared_p is not None:
        h = _norm(shared_p["ln"], x, cfg)
        y, (sk, sv) = attn_lib.attention(
            shared_p["attn"], h, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim, causal=True,
            rope_theta=cfg.rope_theta, rope_mode=cfg.rope_mode,
            kv_block=cfg.kv_block, quant=q, compute_dtype=cd, return_kv=True)
        x = x + y
        h = _norm(shared_p["mlp_ln"], x, cfg)
        x = x + mlp(shared_p["mlp"], h, "swiglu", q, cd)
        cache["shared_k"], cache["shared_v"] = sk.astype(cd), sv.astype(cd)
    h = _norm(bp["ln1"], x, cfg)
    if spec.kind == "attn" and spec.attn_type == "mla":
        y, latent = attn_lib.mla_attention(bp["attn"], h, positions,
                                           return_cache=True, **_mla_kw(cfg))
        x = x + y
        cache.update(latent)
    elif spec.kind == "attn":
        window = cfg.window if spec.attn_type == "local" else None
        y, (k, v) = attn_lib.attention(
            bp["attn"], h, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.head_dim, causal=True, window=window,
            logit_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
            rope_mode=cfg.rope_mode, mrope_sections=cfg.mrope_sections,
            mrope_positions=mrope_positions, kv_block=cfg.kv_block,
            quant=q, compute_dtype=cd, return_kv=True)
        if cfg.gemma_norms:
            y = _norm(bp["post_attn_ln"], y, cfg)
        x = x + y
        if (spec.attn_type == "local" and cfg.window and cfg.window < S
                and not full_kv):
            cache["k"] = _roll_local(k.astype(cd), S, cfg.window)
            cache["v"] = _roll_local(v.astype(cd), S, cfg.window)
        else:
            cache["k"], cache["v"] = k.astype(cd), v.astype(cd)
    elif spec.kind == "mamba2":
        y, st = ssm_lib.mamba2(bp["mamba"], h, d_inner=cfg.d_inner,
                               d_state=cfg.d_state, n_heads=cfg.ssm_heads,
                               quant=q, compute_dtype=cd, return_state=True)
        x = x + y
        cache["h"], cache["conv"] = st.h, st.conv.astype(cd)
    elif spec.kind == "rwkv6":
        y, (Sf, xlast) = ssm_lib.rwkv6_timemix(
            bp["tmix"], h, n_heads=cfg.rwkv_heads, chunk=cfg.rwkv_chunk,
            quant=q, compute_dtype=cd, return_state=True)
        x = x + y
        cache["S"], cache["xt"] = Sf, xlast.astype(cd)
    if spec.mlp == "moe":
        h = _norm(bp["ln2"], x, cfg)
        y, _, _ = _moe(bp, cfg, h)
        x = x + y
    elif spec.mlp == "rwkv_cm":
        h = _norm(bp["ln2"], x, cfg)
        h_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        x = x + ssm_lib.rwkv6_chanmix(bp["cmix"], h, h_prev, quant=q,
                                      compute_dtype=cd)
        cache["xc"] = h[:, -1:].astype(cd)
    elif spec.mlp != "none":
        h = _norm(bp["ln2"], x, cfg)
        y = mlp(bp["mlp"], h, spec.mlp, quant=q, compute_dtype=cd)
        if cfg.gemma_norms:
            y = _norm(bp["post_mlp_ln"], y, cfg)
        x = x + y
    x = constrain(x, "batch", "seq", None)
    return x, cache


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            embeddings: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None,
            full_kv: bool = False, length: Optional[jax.Array] = None):
    """Full-sequence forward that also returns the decode cache.

    Returns (last_token_logits [B, V], cache) — cache layout matches
    ``init_cache`` per pattern position (attn K/V sized S, or window for
    local/rolling layers; SSM/RWKV final states).

    ``full_kv=True`` keeps local-layer K/V at full length (the serving
    scheduler arranges the ring at stitch time).  ``length`` ([B] or scalar
    int32) selects the logits position for right-padded prompt buckets:
    logits are taken at ``length - 1`` instead of the last position (pad
    tokens sit after the prompt, so causal masking keeps them out of every
    real token's attention).
    """
    cd = cfg.cdtype
    if embeddings is not None:
        x = embeddings.astype(cd)
        B, S = x.shape[:2]
    else:
        B, S = tokens.shape
        x = params["embed"]["emb"].astype(cd)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = constrain(x, "batch", "seq", None)
    shared_p = params.get("shared_attn")
    lead = []
    for i, spec in enumerate(cfg.lead):
        x, c = _block_prefill(params["lead"][f"layer{i}"], None, spec, cfg, x,
                              positions, shared_p, mrope_positions,
                              full_kv=full_kv)
        lead.append(jax.tree_util.tree_map(lambda a: a[None], c))

    def group_body(x, group_params):
        caches = []
        for bp, spec in zip(group_params, cfg.pattern):
            x, c = _block_prefill(bp, None, spec, cfg, x, positions, shared_p,
                                  mrope_positions, full_kv=full_kv)
            caches.append(c)
        return x, tuple(caches)

    body = group_body
    if cfg.remat != "none":
        body = jax.checkpoint(group_body, policy=_remat_policy(cfg),
                              prevent_cse=False)
    x, cache = maybe_scan(body, x, params["blocks"], cfg.unroll_groups)
    cache = tuple(lead) + tuple(cache)
    x = _norm(params["final_norm"], x, cfg)
    if length is None:
        xl = x[:, -1]
    else:
        last = jnp.clip(jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(length, jnp.int32)), (B,)) - 1,
            0, S - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = _lm_head(params, cfg, xl.astype(cd)).astype(jnp.float32)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits, cache

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> tuple:
    """Decode caches, one entry per ``layer_specs`` position: a leading
    block's leaves lead with a layer axis of 1, a pattern position's with
    the G groups it is stacked over."""
    return tuple(_block_cache(cfg, spec, 1 if i < len(cfg.lead)
                              else cfg.n_groups, batch, max_len)
                 for i, spec in enumerate(layer_specs(cfg)))


def _block_cache(cfg: ModelConfig, spec: BlockSpec, G: int, batch: int,
                 max_len: int) -> dict:
    cd = cfg.cdtype
    if spec.kind == "attn" and spec.attn_type == "mla":
        # the latent rows: normed c_kv and the roped shared key
        c = {"c_kv": jnp.zeros((G, batch, max_len, cfg.mla.kv_lora_rank), cd),
             "k_pe": jnp.zeros((G, batch, max_len, cfg.mla.qk_rope_dim), cd)}
    elif spec.kind == "attn":
        is_local = spec.attn_type == "local" and cfg.window
        T = min(max_len, cfg.window) if is_local else max_len
        if cfg.kv_quant == "int8" and not is_local:
            c = {"k": jnp.zeros((G, batch, T, cfg.n_kv, cfg.head_dim),
                                jnp.int8),
                 "v": jnp.zeros((G, batch, T, cfg.n_kv, cfg.head_dim),
                                jnp.int8),
                 "k_scale": jnp.zeros((G, batch, T, cfg.n_kv),
                                      jnp.float32),
                 "v_scale": jnp.zeros((G, batch, T, cfg.n_kv),
                                      jnp.float32)}
        else:
            c = {"k": jnp.zeros((G, batch, T, cfg.n_kv, cfg.head_dim), cd),
                 "v": jnp.zeros((G, batch, T, cfg.n_kv, cfg.head_dim), cd)}
        if spec.shared_attn:
            c["shared_k"] = jnp.zeros((G, batch, max_len, cfg.n_kv,
                                       cfg.head_dim), cd)
            c["shared_v"] = jnp.zeros((G, batch, max_len, cfg.n_kv,
                                       cfg.head_dim), cd)
    elif spec.kind == "mamba2":
        P = cfg.d_inner // cfg.ssm_heads
        c = {"h": jnp.zeros((G, batch, cfg.ssm_heads, cfg.d_state, P),
                            jnp.float32),
             "conv": jnp.zeros((G, batch, 3, cfg.d_inner + 2 * cfg.d_state),
                               cd)}
        if spec.shared_attn:
            c["shared_k"] = jnp.zeros((G, batch, max_len, cfg.n_kv,
                                       cfg.head_dim), cd)
            c["shared_v"] = jnp.zeros((G, batch, max_len, cfg.n_kv,
                                       cfg.head_dim), cd)
    elif spec.kind == "rwkv6":
        K = cfg.d_model // cfg.rwkv_heads
        c = {"S": jnp.zeros((G, batch, cfg.rwkv_heads, K, K), jnp.float32),
             "xt": jnp.zeros((G, batch, 1, cfg.d_model), cd),
             "xc": jnp.zeros((G, batch, 1, cfg.d_model), cd)}
    return c


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     num_pages: int, page_size: int) -> tuple:
    """Paged form of :func:`init_cache`: every attention K/V leaf (incl.
    int8-KV scale planes and zamba2's shared-attention K/V) becomes a shared
    ``[G, num_pages, page_size, ...]`` page pool — per-slot addressing lives
    in the scheduler's page tables, not here.  SWA ring layers use the same
    pool shape (their pages are addressed through the ring table).
    Recurrent (mamba2 / rwkv6) states have no sequence axis and stay dense
    per-slot buffers of ``batch`` rows.  MLA's latent cache has no paged
    form."""
    if any(s.attn_type == "mla" for s in layer_specs(cfg)):
        raise NotImplementedError("the latent (MLA) cache has no paged form: "
                                  "serve MLA models with paged=False")
    sds = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    caches = []
    for c in sds:
        out = {}
        for key, leaf in c.items():
            G = leaf.shape[0]
            if key in ("k", "v", "shared_k", "shared_v"):
                out[key] = jnp.zeros(
                    (G, num_pages, page_size) + leaf.shape[3:], leaf.dtype)
            elif key in ("k_scale", "v_scale"):
                out[key] = jnp.zeros((G, num_pages, page_size, cfg.n_kv),
                                     jnp.float32)
            else:
                out[key] = jnp.zeros(leaf.shape, leaf.dtype)
        caches.append(out)
    return tuple(caches)


def _block_decode(bp: dict, cache: dict, spec: BlockSpec, cfg: ModelConfig,
                  x: jax.Array, pos: jax.Array, shared_p: Optional[dict],
                  tables=None):
    cd = cfg.cdtype
    q = _infer_quant(cfg)
    # paged decode: attn cache leaves are [pages, page_size, ...] pools;
    # full-length layers index through tables[0], SWA rings through
    # tables[1] (exclusively-owned page-aligned windows)
    full_t = tables[0] if tables is not None else None
    if spec.shared_attn and shared_p is not None:
        h = _norm(shared_p["ln"], x, cfg)
        y, ck, cv = attn_lib.decode_attention(
            shared_p["attn"], h, cache["shared_k"], cache["shared_v"], pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, rope_mode=cfg.rope_mode,
            quant=q, compute_dtype=cd, table=full_t)
        x = x + y
        h = _norm(shared_p["mlp_ln"], x, cfg)
        x = x + mlp(shared_p["mlp"], h, "swiglu", q, cd)
        cache = {**cache, "shared_k": ck, "shared_v": cv}
    h = _norm(bp["ln1"], x, cfg)
    if spec.kind == "attn" and spec.attn_type == "mla":
        if tables is not None:
            raise NotImplementedError("the latent (MLA) cache has no paged "
                                      "form")
        y, latent = attn_lib.mla_decode_attention(
            bp["attn"], h, {k: cache[k] for k in ("c_kv", "k_pe")}, pos,
            **_mla_kw(cfg))
        x = x + y
        cache = {**cache, **latent}
    elif spec.kind == "attn":
        window = cfg.window if spec.attn_type == "local" else None
        is_local = spec.attn_type == "local" and cfg.window is not None
        if tables is not None:
            rolling = is_local
            attn_t = tables[1] if is_local else full_t
        else:
            rolling = is_local and cache["k"].shape[1] <= cfg.window
            attn_t = None
        if "k_scale" in cache:
            y, c8 = attn_lib.decode_attention_int8(
                bp["attn"], h, cache, pos, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv, head_dim=cfg.head_dim, window=window,
                logit_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
                rope_mode=cfg.rope_mode, mrope_sections=cfg.mrope_sections,
                quant=q, compute_dtype=cd, table=attn_t)
            if cfg.gemma_norms:
                y = _norm(bp["post_attn_ln"], y, cfg)
            x = x + y
            cache = {**cache, **{kk: c8[kk] for kk in
                                 ("k", "v", "k_scale", "v_scale")}}
            return _finish_block_decode(bp, cache, spec, cfg, x, q, cd)
        y, ck, cv = attn_lib.decode_attention(
            bp["attn"], h, cache["k"], cache["v"], pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            window=window, logit_softcap=cfg.attn_softcap,
            rope_theta=cfg.rope_theta, rope_mode=cfg.rope_mode,
            mrope_sections=cfg.mrope_sections, rolling=rolling,
            quant=q, compute_dtype=cd, table=attn_t)
        if cfg.gemma_norms:
            y = _norm(bp["post_attn_ln"], y, cfg)
        x = x + y
        cache = {**cache, "k": ck, "v": cv}
    elif spec.kind == "mamba2":
        st = ssm_lib.Mamba2State(h=cache["h"], conv=cache["conv"])
        y, st = ssm_lib.mamba2_decode(bp["mamba"], h, st, d_inner=cfg.d_inner,
                                      d_state=cfg.d_state,
                                      n_heads=cfg.ssm_heads, quant=q,
                                      compute_dtype=cd)
        x = x + y
        cache = {**cache, "h": st.h, "conv": st.conv}
    elif spec.kind == "rwkv6":
        st = ssm_lib.RWKVState(S=cache["S"], x_prev_t=cache["xt"],
                               x_prev_c=cache["xc"])
        y, st = ssm_lib.rwkv6_timemix_decode(bp["tmix"], h, st,
                                             n_heads=cfg.rwkv_heads, quant=q,
                                             compute_dtype=cd)
        x = x + y
        cache = {**cache, "S": st.S, "xt": st.x_prev_t}
    return _finish_block_decode(bp, cache, spec, cfg, x, q, cd)


def _finish_block_decode(bp, cache, spec, cfg, x, q, cd):
    """MLP / MoE / channel-mix tail of a decode block: (x, cache, int32[3]
    MoE counters, zeros without MoE)."""
    counts = jnp.zeros((3,), jnp.int32)
    if spec.mlp == "moe":
        h = _norm(bp["ln2"], x, cfg)
        y, _, counts = _moe(bp, cfg, h, decode=True)
        x = x + y
    elif spec.mlp == "rwkv_cm":
        h = _norm(bp["ln2"], x, cfg)
        x = x + ssm_lib.rwkv6_chanmix(bp["cmix"], h, cache["xc"], quant=q,
                                      compute_dtype=cd)
        cache = {**cache, "xc": h}
    elif spec.mlp != "none":
        h = _norm(bp["ln2"], x, cfg)
        y = mlp(bp["mlp"], h, spec.mlp, quant=q, compute_dtype=cd)
        if cfg.gemma_norms:
            y = _norm(bp["post_mlp_ln"], y, cfg)
        x = x + y
    return x, cache, counts


def _block_verify(bp: dict, cache: dict, spec: BlockSpec, cfg: ModelConfig,
                  x: jax.Array, pos: jax.Array, tables=None):
    """S-token decode block for the speculative verify forward.

    Only chunk-eligible attention stacks reach here (the engine's
    spec_decode eligibility raises for recurrent / MoE / int8-KV / SWA /
    shared-attention patterns at construction)."""
    cd = cfg.cdtype
    q = _infer_quant(cfg)
    if (spec.kind != "attn" or spec.shared_attn or spec.attn_type == "mla"
            or (spec.attn_type == "local" and cfg.window)
            or spec.mlp in ("moe", "rwkv_cm") or "k_scale" in cache):
        raise ValueError(
            f"verify_step cannot run block spec {spec} (kv_quant="
            f"{cfg.kv_quant!r}): speculative decoding supports plain "
            "full-length attention blocks only")
    full_t = tables[0] if tables is not None else None
    h = _norm(bp["ln1"], x, cfg)
    y, ck, cv = attn_lib.decode_attention_multi(
        bp["attn"], h, cache["k"], cache["v"], pos,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
        logit_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
        rope_mode=cfg.rope_mode, mrope_sections=cfg.mrope_sections,
        quant=q, compute_dtype=cd, table=full_t)
    if cfg.gemma_norms:
        y = _norm(bp["post_attn_ln"], y, cfg)
    x = x + y
    cache = {**cache, "k": ck, "v": cv}
    return _finish_block_decode(bp, cache, spec, cfg, x, q, cd)[:2]


def verify_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                cache: tuple, pos: jax.Array,
                tables=None) -> tuple[jax.Array, tuple]:
    """S tokens for the whole batch in ONE forward (speculative verify).

    tokens: [B, S] int32 — token i of a row logically sits at ``pos + i``;
    pos: [B] int32 start positions (negative marks a free slot).  Returns
    (logits [B, S, V], cache): ``logits[:, i]`` conditions on
    ``tokens[:, :i+1]`` plus the cache history, bit-identical to S
    sequential :func:`decode_step` calls, because every KV write lands
    before attention and the causal mask hides keys past ``pos + i`` from
    query i.  The batched [B*S] matmuls are where the verify step beats S
    sequential target steps."""
    if cfg.lead:
        raise ValueError("verify_step runs the scanned groups only: models "
                         "with leading blocks cannot run speculative rounds")
    cd = cfg.cdtype
    x = params["embed"]["emb"].astype(cd)[tokens]               # [B,S,d]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)

    def group_body(carry, scanned):
        x, = carry
        gp, gc = scanned
        out_caches = []
        for bp, c, spec in zip(gp, gc, cfg.pattern):
            x, c = _block_verify(bp, c, spec, cfg, x, pos, tables=tables)
            out_caches.append(c)
        return (x,), tuple(out_caches)

    (x,), cache = maybe_scan(group_body, (x,),
                             (params["blocks"], cache), cfg.unroll_groups)
    x = _norm(params["final_norm"], x, cfg)
    logits = _lm_head(params, cfg, x.astype(cd)).astype(jnp.float32)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits, cache


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                cache: tuple, pos: jax.Array, tables=None,
                counters: bool = False) -> tuple:
    """One token for the whole batch. token: [B] int32; pos: scalar int32 or
    per-sequence [B] int32 (continuous batching — each slot at its own depth;
    negative marks a free slot whose keys stay masked).

    ``tables`` (paged serving): a ``(full_table [B, E], ring_table [B, Er])``
    pair of int32 page tables — the attention cache leaves are then shared
    page pools instead of per-slot dense buffers (see ``serve.paged``).

    Returns (logits, cache), and with ``counters`` also the MoE layers'
    int32[3] counters summed over layers (experts that got a token, pairs
    computed, pairs dropped)."""
    cd = cfg.cdtype
    # gather the rows, then cast: no compute-dtype copy of the whole table
    x = params["embed"]["emb"][token].astype(cd)[:, None, :]    # [B,1,d]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    shared_p = params.get("shared_attn")
    counts = jnp.zeros((3,), jnp.int32)
    n_lead = len(cfg.lead)
    lead = []
    for i, spec in enumerate(cfg.lead):
        c = jax.tree_util.tree_map(lambda a: a[0], cache[i])
        x, c, n = _block_decode(params["lead"][f"layer{i}"], c, spec, cfg, x,
                                pos, shared_p, tables=tables)
        counts = counts + n
        lead.append(jax.tree_util.tree_map(lambda a: a[None], c))

    # scan over groups per pattern position jointly (tables are
    # scan-invariant: every group indexes the same per-slot page rows)
    def group_body(carry, scanned):
        x, counts = carry
        gp, gc = scanned                 # tuple(params), tuple(cache)
        out_caches = []
        for bp, c, spec in zip(gp, gc, cfg.pattern):
            x, c, n = _block_decode(bp, c, spec, cfg, x, pos, shared_p,
                                    tables=tables)
            counts = counts + n
            out_caches.append(c)
        return (x, counts), tuple(out_caches)

    (x, counts), body = maybe_scan(group_body, (x, counts),
                                   (params["blocks"], tuple(cache[n_lead:])),
                                   cfg.unroll_groups)
    cache = tuple(lead) + tuple(body)
    x = _norm(params["final_norm"], x, cfg)
    logits = _lm_head(params, cfg, x[:, 0].astype(cd)).astype(jnp.float32)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    if counters:
        return logits, cache, counts
    return logits, cache
