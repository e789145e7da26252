"""Plain reference of the served model, independent of the program.

A LLaMA/Qwen2-family decoder written out in ``jax.numpy``: RMSNorm, GQA
attention with rotate-half RoPE, SwiGLU MLP, every projection an integer
matmul of per-token absmax activation codes with the weight codes, then the
per-token and per-channel scales.  It imports nothing of the program and
takes nothing the program made: the weight codes are regenerated layer by
layer from the seed (``bench.weights``).

It computes at the precision the configuration file states: activations
and the KV cache in bfloat16, norms, RoPE and softmax in float32, integer
accumulation exact, logits in bfloat16.  Rounding at exactly those points
matters because every projection re-quantizes its input to 4 (or 8) bits:
a one-ulp difference flips an activation code, and random layers amplify a
flipped code to O(1) within a few layers.

Attention is evaluated in the two forms the server uses, chosen per
position: positions admitted by the batched prefill (``mono`` leading
positions) attend over exactly the prompt's keys; every later position
attends, as a decode step does, one position at a time over a ``max_len``
key buffer masked past its own position.  (Batched over positions, the
same sums ran in another order on the chip and a qwen2-7b seed read a gap
of 0.03125; one position at a time it reads 0.)

The control is the same model one precision step below what the
configuration states (``lower``): ``"kv_fp8"`` rounds keys and values to
float8 (e4m3) where bfloat16 is stated; ``"act_int4"`` quantizes the body
projections' activations to int4 where int8 is stated.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

NEG_INF = -1e30
COMPILE = {"xla_allow_excess_precision": False}


# -- layers --------------------------------------------------------------

def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def act_codes(x2, bits: int):
    qmax = 2 ** (bits - 1) - 1
    a_scale = jnp.maximum(jnp.max(jnp.abs(x2), axis=1, keepdims=True),
                          1e-8) / qmax
    return jnp.clip(jnp.round(x2 / a_scale), -qmax - 1, qmax).astype(
        jnp.int8), a_scale


def linear(x, leaf: dict, abits: int, cd, fmt):
    """``fmt``: the leaf's weight format module (``bench/formats``)."""
    lead, K = x.shape[:-1], x.shape[-1]
    w = fmt.codes(leaf)
    a_q, a_scale = act_codes(x.reshape(-1, K).astype(jnp.float32), abits)
    acc = jax.lax.dot(a_q, w, preferred_element_type=jnp.int32)
    y = (acc.astype(jnp.float32) * a_scale
         * leaf["w_scale"].reshape(1, -1)).reshape(*lead, -1).astype(cd)
    if "b" in leaf:
        y = y + leaf["b"].astype(y.dtype)
    return y


def rope(x, positions, theta):
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def attend(q, k, v, q_pos, k_pos):
    """Softmax attention of q [B,S,Hq,D] over k/v [B,T,Hkv,D]; key t is
    seen by query s where k_pos[t] <= q_pos[s]."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, D) * jnp.asarray(
        1.0 / math.sqrt(D), q.dtype)
    s = jnp.einsum("bshgd,bkhd->bshgk", qg, k,
                   preferred_element_type=jnp.float32)
    keep = k_pos[:, None, :] <= q_pos[:, :, None]
    s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgk,bkhd->bshgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hq, D)


@functools.partial(jax.jit, static_argnames=("cfg_key", "mono", "T",
                                             "lower"),
                   compiler_options=COMPILE)
def _layer(x, g, key, *, cfg_key: str, mono: int, T: int, lower: str):
    cfg = json.loads(cfg_key)
    m = weights.dims(cfg)
    cd = x.dtype
    abits = 4 if lower == "act_int4" else m["abits"]
    fmt = weights.weight_format(cfg, m["format"])
    eps = float(cfg["rms_norm_eps"])
    p = weights.layer_params(cfg, key, g)
    B, L, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))

    h = rms_norm(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = linear(h, a["wq"], abits, cd, fmt).reshape(B, L, m["h"], m["hd"])
    k = linear(h, a["wk"], abits, cd, fmt).reshape(B, L, m["kv"], m["hd"])
    v = linear(h, a["wv"], abits, cd, fmt).reshape(B, L, m["kv"], m["hd"])
    q = rope(q, pos, m["theta"])
    k = rope(k, pos, m["theta"])
    if lower == "kv_fp8":
        k = k.astype(jnp.float8_e4m3fn).astype(cd)
        v = v.astype(jnp.float8_e4m3fn).astype(cd)
    outs = []
    if mono:
        outs.append(attend(q[:, :mono], k[:, :mono], v[:, :mono],
                           pos[:, :mono], pos[:, :mono]))
    if L > mono:
        pad = ((0, 0), (0, T - L), (0, 0), (0, 0))
        kT, vT = jnp.pad(k, pad), jnp.pad(v, pad)
        tpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

        def one(_, p):               # one query position, as a decode step
            qp = jax.lax.dynamic_slice_in_dim(q, p, 1, axis=1)
            return None, attend(qp, kT, vT, jnp.full((B, 1), p), tpos)[:, 0]

        _, o = jax.lax.scan(one, None, jnp.arange(mono, L))
        outs.append(jnp.moveaxis(o, 0, 1))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    x = x + linear(out.astype(cd).reshape(B, L, -1), a["wo"], abits, cd,
                   fmt)

    h = rms_norm(x, p["ln2"]["scale"], eps)
    f = p["mlp"]
    y = jax.nn.silu(linear(h, f["wg"], abits, cd, fmt)) * linear(
        h, f["wi"], abits, cd, fmt)
    x = x + linear(y, f["wo"], abits, cd, fmt)
    return x, k, v


@functools.partial(jax.jit, static_argnames=("cfg_key",),
                   compiler_options=COMPILE)
def _embed(tokens, key, *, cfg_key: str):
    cfg = json.loads(cfg_key)
    emb = weights.outer_params(cfg, key)["embed"]["emb"]
    return emb.astype(jnp.dtype(cfg["serving"]["compute_dtype"]))[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_key",),
                   compiler_options=COMPILE)
def _head(x, at, score, key, *, cfg_key: str):
    """Logits at positions ``at`` [B, P]; returns (best logit, logit of each
    ``score`` token, argmax), each [B, P]."""
    cfg = json.loads(cfg_key)
    outer = weights.outer_params(cfg, key)
    xl = jnp.take_along_axis(x, at[..., None], axis=1)
    xl = rms_norm(xl, outer["final_norm"]["scale"],
                  float(cfg["rms_norm_eps"]))
    head = weights.weight_format(cfg, weights.HEAD_FORMAT)
    logits = linear(xl.astype(x.dtype), outer["lm_head"], 8, x.dtype,
                    head).astype(jnp.float32)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, score[..., None], axis=-1)[..., 0]
    return best, got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def run(cfg: dict, seed: int, tokens: np.ndarray, *, mono: int, T: int,
        at: np.ndarray, score: np.ndarray, lower: str = "",
        kv_rows: int = 0) -> dict:
    """Teacher-forced forward over ``tokens`` [B, L] (right-padded).

    Returns host arrays: ``best``, ``got`` (logit of ``score`` [B, P] at
    positions ``at`` [B, P]) and ``argmax``; with ``kv_rows`` > 0 also
    ``k``/``v`` [n_layers, B, kv_rows, Hkv, D] of the first positions, on
    the device."""
    ck = json.dumps(cfg, sort_keys=True)
    m = weights.dims(cfg)
    key = weights.seed_key(seed)
    x = _embed(jnp.asarray(tokens, jnp.int32), key, cfg_key=ck)
    ks, vs = [], []
    for g in range(m["L"]):
        x, k, v = _layer(x, jnp.uint32(g), key, cfg_key=ck, mono=mono, T=T,
                         lower=lower)
        if kv_rows:
            ks.append(k[:, :kv_rows])
            vs.append(v[:, :kv_rows])
    best, got, arg = _head(x, jnp.asarray(at, jnp.int32),
                           jnp.asarray(score, jnp.int32), key, cfg_key=ck)
    out = {"best": np.asarray(best), "got": np.asarray(got),
           "argmax": np.asarray(arg)}
    if kv_rows:
        out["k"], out["v"] = jnp.stack(ks), jnp.stack(vs)
    return out
