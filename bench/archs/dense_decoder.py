"""A LLaMA/Qwen2-family dense decoder: every layer pre-norm RMSNorm, GQA
(or MHA) attention with rotate-half RoPE and optional QKV biases, SwiGLU
MLP; one pattern position, no layer outside the scan.

Configuration keys (Hugging Face names): ``hidden_size``,
``intermediate_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (default hidden / heads), ``num_hidden_layers``,
``vocab_size``, ``rope_theta``, ``rms_norm_eps``, ``hidden_act``,
``architecture.qkv_bias``, and ``serving`` (``quant``, ``format``,
``compute_dtype``).
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

from bench.cost import Work
from bench.reference import attend, linear, rms_norm, rope
from bench.weights import HEAD_FORMAT, Layout, weight_format

KIND = "layer"                  # the one kind of layer


def dims(cfg: dict) -> dict:
    """The sizes the layout needs, from a configuration file's keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim", d // h))
    return {"d": d, "ff": int(cfg["intermediate_size"]), "h": h,
            "kv": int(cfg["num_key_value_heads"]), "hd": hd,
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
            "qkv_bias": bool(cfg["architecture"]["qkv_bias"]),
            "quant": cfg["serving"]["quant"],
            "format": cfg["serving"]["format"],
            "abits": int(re.search(r"a(\d+)", cfg["serving"]["quant"])[1]),
            "theta": float(cfg["rope_theta"])}


# -- the program's configuration -----------------------------------------

def model_config(cfg: dict):
    """The program's ModelConfig.  The program runs SwiGLU here and a fixed
    RMSNorm eps of 1e-6: a file asking for other values is refused."""
    from repro.models.transformer import BlockSpec, ModelConfig
    m = dims(cfg)
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (silu) MLPs are "
                         f"wired here, got {cfg['hidden_act']!r}")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm runs "
                         f"eps=1e-6, the file says {cfg['rms_norm_eps']}")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["h"], n_kv=m["kv"], head_dim=m["hd"], d_ff=m["ff"],
        vocab=m["V"], qkv_bias=m["qkv_bias"],
        pattern=(BlockSpec(kind="attn", attn_type="global", mlp="swiglu"),),
        rope_theta=m["theta"], quant=m["quant"],
        compute_dtype=cfg["serving"]["compute_dtype"])


# -- served leaves ---------------------------------------------------------

def layout(cfg: dict) -> Layout:
    return Layout(pattern=(KIND,), groups=dims(cfg)["L"])


def projections(cfg: dict) -> list:
    """(group, name, K, N, residual_out) of every body projection."""
    m = dims(cfg)
    d, q, kv, ff = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    return [("attn", "wq", d, q, False), ("attn", "wk", d, kv, False),
            ("attn", "wv", d, kv, False), ("attn", "wo", q, d, True),
            ("mlp", "wi", d, ff, False), ("mlp", "wg", d, ff, False),
            ("mlp", "wo", ff, d, True)]


def layer_leaves(cfg: dict, kind: str) -> list:
    """(path, rule, shape, dtype, extra) of one layer's leaves; ``path``
    is relative to the layer's block dict."""
    m = dims(cfg)
    out = [(("ln1", "scale"), "norm", (m["d"],), jnp.float32, {}),
           (("ln2", "scale"), "norm", (m["d"],), jnp.float32, {})]
    for group, name, K, N, resid in projections(cfg):
        gain = 1.0 / math.sqrt(2 * m["L"]) if resid else 1.0
        base = (group, name)
        out.append((base, "codes", (K, N), None,
                    {"fmt": weight_format(cfg, m["format"])}))
        out.append((base + ("w_scale",), "scale", (1, N), jnp.float32,
                    {"K": K, "gain": gain,
                     "fmt": weight_format(cfg, m["format"])}))
        if m["qkv_bias"] and name in ("wq", "wk", "wv"):
            out.append((base + ("b",), "bias", (N,), jnp.float32, {}))
    return out


def outer_leaves(cfg: dict) -> list:
    m = dims(cfg)
    head = weight_format(cfg, HEAD_FORMAT)
    return [(("embed", "emb"), "emb", (m["V"], m["d"]), jnp.float32, {}),
            (("final_norm", "scale"), "norm", (m["d"],), jnp.float32, {}),
            (("lm_head",), "codes", (m["d"], m["V"]), None,
             {"fmt": head}),
            (("lm_head", "w_scale"), "scale", (1, m["V"]), jnp.float32,
             {"K": m["d"], "gain": 1.0, "fmt": head})]


# -- the reference step ----------------------------------------------------

def _abits(cfg: dict, lower: str) -> int:
    """Activation bits of the body projections (control ``act_int4``: 4)."""
    return 4 if lower == "act_int4" else dims(cfg)["abits"]


def attention(cfg: dict, p: dict, x, *, mono: int, T: int, lower: str):
    """The attention half of a layer: ``x`` plus its attention output, and
    the layer's K/V rows {"k", "v"} [B, L, Hkv, D] as the cache holds them.

    Attention is evaluated in the two forms the server uses, chosen per
    position: positions admitted by the batched prefill (``mono`` leading
    positions) attend over exactly the prompt's keys; every later position
    attends, as a decode step does, one position at a time over a ``T``
    (``max_len``) key buffer masked past its own position.  (Batched over
    positions, the same sums ran in another order on the chip and a
    qwen2-7b seed read a gap of 0.03125; one position at a time it reads
    0.)  Control ``kv_fp8`` rounds keys and values to float8 (e4m3) where
    bfloat16 is stated; ``act_int4`` quantizes the projections'
    activations to int4 where int8 is stated."""
    m = dims(cfg)
    cd = x.dtype
    abits = _abits(cfg, lower)
    fmt = weight_format(cfg, m["format"])
    B, L, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))

    h = rms_norm(x, p["ln1"]["scale"], float(cfg["rms_norm_eps"]))
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
    return x, {"k": k, "v": v}


def gated_mlp(cfg: dict, p: dict, x, *, lower: str, act):
    """The MLP half's output (to add to ``x``): ``act(x Wg) * (x Wi)``,
    then ``Wo``, over the layer's second norm."""
    abits = _abits(cfg, lower)
    cd = x.dtype
    fmt = weight_format(cfg, cfg["serving"]["format"])
    h = rms_norm(x, p["ln2"]["scale"], float(cfg["rms_norm_eps"]))
    f = p["mlp"]
    y = act(linear(h, f["wg"], abits, cd, fmt)) * linear(
        h, f["wi"], abits, cd, fmt)
    return linear(y, f["wo"], abits, cd, fmt)


def layer(cfg: dict, kind: str, p: dict, x, *, mono: int, T: int,
          lower: str):
    """One layer over ``x`` [B, L, d]: (its output, its K/V rows)."""
    x, rows = attention(cfg, p, x, mono=mono, T=T, lower=lower)
    return x + gated_mlp(cfg, p, x, lower=lower, act=jax.nn.silu), rows


# -- what the check reads from the program ----------------------------------

def cache_rows(cache, slots, P: int) -> dict:
    """The program's K and V rows of ``slots``, the first ``P`` positions:
    {"k", "v"} [n_layers, len(slots), P, Hkv, D] in the cache's dtype."""
    stacks = cache[0]            # the one pattern position, [n_layers, ...]
    return {name: stacks[name][:, slots, :P] for name in ("k", "v")}


# -- work of a forward (bench/cost.py) ---------------------------------------

def kernel_calls(cfg: dict, kernel: str) -> list:
    """(K, N, code bits) of each call of ``kernel`` in one forward: the
    body projections run on their format's ``KERNEL``, the head on the
    head format's."""
    m = dims(cfg)
    body = weight_format(cfg, m["format"])
    head = weight_format(cfg, HEAD_FORMAT)
    out = []
    if body.KERNEL == kernel:
        out += [(K, N, body.BITS)
                for _, _, K, N, _ in projections(cfg)] * m["L"]
    if head.KERNEL == kernel:
        out.append((m["d"], m["V"], head.BITS))
    return out


def weight_bytes(cfg: dict) -> float:
    """Bytes of the served weights one forward reads: body codes and
    scales, norms, biases, the head (the embedding is gathered by row)."""
    m = dims(cfg)
    bits = weight_format(cfg, m["format"]).BITS
    body = sum(K * N * bits / 8 + 4 * N
               for _, _, K, N, _ in projections(cfg))
    body += 2 * 4 * m["d"]
    if m["qkv_bias"]:
        body += 4 * (m["h"] + 2 * m["kv"]) * m["hd"]
    head = weight_format(cfg, HEAD_FORMAT).BITS
    return (m["L"] * body + m["d"] * m["V"] * head / 8
            + 4 * m["V"] + 4 * m["d"])


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 K and V of one token over all layers."""
    m = dims(cfg)
    return m["L"] * 2 * m["kv"] * m["hd"] * 2


def forward(cfg: dict, ctx: list) -> Work:
    """One forward of the rows whose context lengths (the position each
    row attends from, plus one) are ``ctx``: every weight read once, each
    row's projections and head, its attention over its own context."""
    m = dims(cfg)
    rows = len(ctx)
    if not rows:
        return Work()
    per_row = 2.0 * (m["L"] * sum(K * N for _, _, K, N, _ in
                                  projections(cfg)) + m["d"] * m["V"])
    attn = sum(4.0 * m["L"] * m["h"] * m["hd"] * c for c in ctx)
    return Work(int_ops=rows * per_row, bf16_ops=attn,
                bytes=weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(ctx)
                + rows * 4 * m["d"])           # + embedding rows gathered


# -- a CPU-size copy (bench/tests) --------------------------------------------

def tiny(cfg: dict, layers: int) -> dict:
    """The configuration at width 64, 4 heads (2 KV heads where the source
    groups them), d_ff 128, vocab 512 and ``layers`` layers."""
    gqa = cfg["num_key_value_heads"] < cfg["num_attention_heads"]
    return {**cfg, "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4,
            "num_key_value_heads": 2 if gqa else 4,
            "num_hidden_layers": layers, "vocab_size": 512}
