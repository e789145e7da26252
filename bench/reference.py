"""Plain reference of the served model, independent of the program.

The model written out in ``jax.numpy``: the embedding, each layer by its
architecture's step (``layer`` of ``bench/archs/<arch>.py``, built from
the pieces here), the final RMSNorm and the int8 head.  Every projection
is an integer matmul of per-token absmax activation codes with the weight
codes, then the per-token and per-channel scales (``linear``).  It imports
nothing of the program and takes nothing the program made: the weight
codes are regenerated layer by layer from the seed (``bench.weights``).

It computes at the precision the configuration file states: activations
and the KV cache in bfloat16, norms, RoPE and softmax in float32, integer
accumulation exact, logits in bfloat16.  Rounding at exactly those points
matters because every projection re-quantizes its input to 4 (or 8) bits:
a one-ulp difference flips an activation code, and random layers amplify a
flipped code to O(1) within a few layers.

The control is the same model one precision step below what the
configuration states (``lower``, a step the architecture's layer takes,
such as ``"kv_fp8"`` or ``"act_int4"``).  ``mono`` (the leading positions
a batched prefill admitted) and ``T`` (``max_len``) tell the step which
form of attention the server ran at each position
(``bench.archs.dense_decoder.attention``).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights

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


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "mono",
                                             "T", "lower"),
                   compiler_options=COMPILE)
def _layer(x, g, key, *, cfg_key: str, kind: str, mono: int, T: int,
           lower: str):
    """Layer ``g`` (a layer of ``kind``): the architecture's step over the
    leaves regenerated from the seed."""
    cfg = json.loads(cfg_key)
    p = weights.layer_params(cfg, key, g, kind)
    return spec.arch(cfg).layer(cfg, kind, p, x, mono=mono, T=T,
                                lower=lower)


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
    ``rows``, the cache rows the architecture's step names, each
    [n_layers, B, kv_rows, ...] of the first positions, on the device."""
    ck = json.dumps(cfg, sort_keys=True)
    key = weights.seed_key(seed)
    x = _embed(jnp.asarray(tokens, jnp.int32), key, cfg_key=ck)
    rows: dict = {}
    for g, kind in enumerate(spec.arch(cfg).layout(cfg).kinds()):
        x, r = _layer(x, jnp.uint32(g), key, cfg_key=ck, kind=kind,
                      mono=mono, T=T, lower=lower)
        if kv_rows:
            for name, a in r.items():
                rows.setdefault(name, []).append(a[:, :kv_rows])
    best, got, arg = _head(x, jnp.asarray(at, jnp.int32),
                           jnp.asarray(score, jnp.int32), key, cfg_key=ck)
    out = {"best": np.asarray(best), "got": np.asarray(got),
           "argmax": np.asarray(arg)}
    if kv_rows:
        out["rows"] = {name: jnp.stack(a) for name, a in rows.items()}
    return out
