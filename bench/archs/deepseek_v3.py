"""The DeepSeek-V3 block (``model_type: deepseek_v3``; Moonlight-16B-A3B):
pre-norm RMSNorm layers with multi-head latent attention (MLA), the first
``first_k_dense_replace`` layers with a dense SwiGLU MLP and outside the
program's layer scan, every later layer a mixture of experts: sigmoid
scores, top-k on score plus a selection-only bias (``noaux_tc`` with one
group), gates the chosen scores renormalised times
``routed_scaling_factor``, and ungated shared experts.

MLA (``modeling_deepseek.DeepseekV3Attention``, ``q_lora_rank`` null):
``q_proj`` gives each head [nope | rope] dims; ``kv_a_proj_with_mqa``
gives the latent ``c_kv`` (then ``kv_a_layernorm``) and one RoPE key
``k_pe`` shared by the heads; ``kv_b_proj`` maps ``c_kv`` to each head's
[nope key | value].  RoPE de-interleaves the rope dims' pairs, then
rotates halves.  The softmax scale is 1/sqrt(nope + rope).  The cache
holds ``c_kv`` and ``k_pe`` per token.

Configuration keys (Hugging Face names): ``hidden_size``,
``intermediate_size``, ``moe_intermediate_size``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``num_hidden_layers``, ``first_k_dense_replace``,
``n_routed_experts``, ``num_experts_per_tok``, ``n_shared_experts``,
``routed_scaling_factor``, ``norm_topk_prob``, ``vocab_size``,
``rope_theta``, ``rms_norm_eps``, and ``serving`` (``quant``, ``format``,
``compute_dtype``).

Precision as served: every projection but ``kv_b_proj`` and the router is
w4 codes with a4 per-token activations (the routed experts on XLA's integer
einsum, the rest on the one-hot LUT kernel); ``kv_b_proj`` is bfloat16 with
its input unquantized; the router multiplies bfloat16 activations by a
bfloat16 weight into float32 logits, with a float32 sigmoid.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

from bench.cost import Work, matmul
from bench.reference import act_codes, linear, rms_norm, rope
from bench.weights import HEAD_FORMAT, Layout, weight_format

NEG_INF = -1e30
FLOAT = "normal"                  # the format of the float leaves
EXPERTS = "nibble_experts"        # the format of the routed expert banks
MOE_BLOCK = 4096                  # tokens per block of the reference's MoE


def dims(cfg: dict) -> dict:
    """The sizes the layout needs, from a configuration file's keys."""
    ser = cfg["serving"]
    return {"d": int(cfg["hidden_size"]),
            "ff": int(cfg["intermediate_size"]),
            "eff": int(cfg["moe_intermediate_size"]),
            "h": int(cfg["num_attention_heads"]),
            "r": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "dv": int(cfg["v_head_dim"]),
            "L": int(cfg["num_hidden_layers"]),
            "lead": int(cfg["first_k_dense_replace"]),
            "E": int(cfg["n_routed_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "V": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "quant": ser["quant"], "format": ser["format"],
            "abits": int(re.search(r"a(\d+)", ser["quant"])[1])}


# -- the program's configuration -----------------------------------------

def model_config(cfg: dict):
    """The program's ModelConfig; refuses what the program does not run."""
    from repro.models.moe import MoEConfig
    from repro.models.transformer import BlockSpec, MLAConfig, ModelConfig
    m = dims(cfg)
    wants = {"hidden_act": "silu", "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "q_lora_rank": None, "moe_layer_freq": 1,
             "attention_bias": False, "norm_topk_prob": True}
    for key, want in wants.items():
        if cfg.get(key) != want:
            raise ValueError(f"{cfg['name']}: the program runs {key} = "
                             f"{want!r}, the file says {cfg.get(key)!r}")
    if cfg.get("rope_scaling"):
        raise ValueError(f"{cfg['name']}: no rope_scaling is wired")
    mla = BlockSpec(kind="attn", attn_type="mla", mlp="swiglu")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["h"], n_kv=m["h"], head_dim=m["nope"] + m["rope"],
        d_ff=m["ff"], vocab=m["V"],
        lead=(mla,) * m["lead"],
        pattern=(BlockSpec(kind="attn", attn_type="mla", mlp="moe"),),
        mla=MLAConfig(m["r"], m["nope"], m["rope"], m["dv"]),
        moe=MoEConfig(n_experts=m["E"], top_k=m["k"], d_ff=m["eff"],
                      shared_ff=m["shared"] * m["eff"], norm_topk=True,
                      scoring="sigmoid", routed_scale=m["scale"],
                      shared_gate=False, dropless=True),
        rope_theta=m["theta"], norm_eps=m["eps"], quant=m["quant"],
        compute_dtype=cfg["serving"]["compute_dtype"])


# -- served leaves ---------------------------------------------------------

def layout(cfg: dict) -> Layout:
    m = dims(cfg)
    return Layout(pattern=("moe",), groups=m["L"] - m["lead"],
                  lead=tuple((("lead", f"layer{i}"), "dense")
                             for i in range(m["lead"])))


def projections(cfg: dict, kind: str) -> list:
    """(group path, K, N, residual_out) of a layer's w4 projections on the
    LUT kernel (not the routed experts)."""
    m = dims(cfg)
    d = m["d"]
    ff = m["ff"] if kind == "dense" else m["shared"] * m["eff"]
    mlp = ("mlp",) if kind == "dense" else ("moe", "shared")
    return [(("attn", "wq"), d, m["h"] * (m["nope"] + m["rope"]), False),
            (("attn", "wkv_a"), d, m["r"] + m["rope"], False),
            (("attn", "wo"), m["h"] * m["dv"], d, True),
            (mlp + ("wi",), d, ff, False), (mlp + ("wg",), d, ff, False),
            (mlp + ("wo",), ff, d, True)]


def _codes(out: list, cfg: dict, path, shape, gain: float, fmt) -> None:
    """A w4 leaf: its codes and its per-channel scale."""
    K, N = shape[-2:]
    out.append((path, "codes", shape, None, {"fmt": fmt}))
    out.append((path + ("w_scale",), "scale", shape[:-2] + (1, N),
                jnp.float32, {"K": K, "gain": gain, "fmt": fmt}))


def layer_leaves(cfg: dict, kind: str) -> list:
    """(path, rule, shape, dtype, extra) of one layer's leaves."""
    m = dims(cfg)
    resid = 1.0 / math.sqrt(2 * m["L"])
    body = weight_format(cfg, m["format"])
    flt = weight_format(cfg, FLOAT)
    out = [(("ln1", "scale"), "norm", (m["d"],), jnp.float32, {}),
           (("ln2", "scale"), "norm", (m["d"],), jnp.float32, {}),
           (("attn", "kv_norm", "scale"), "norm", (m["r"],), jnp.float32,
            {}),
           (("attn", "wkv_b"), "codes",
            (m["r"], m["h"] * (m["nope"] + m["dv"])), None, {"fmt": flt})]
    for path, K, N, res in projections(cfg, kind):
        _codes(out, cfg, path, (K, N), resid if res else 1.0, body)
    if kind == "moe":
        E, d, eff = m["E"], m["d"], m["eff"]
        exp = weight_format(cfg, EXPERTS)
        out += [(("moe", "router"), "codes", (d, E), None, {"fmt": flt}),
                (("moe", "score_bias"), "bias", (E,), jnp.float32, {})]
        _codes(out, cfg, ("moe", "wi"), (E, d, eff), 1.0, exp)
        _codes(out, cfg, ("moe", "wg"), (E, d, eff), 1.0, exp)
        _codes(out, cfg, ("moe", "wo"), (E, eff, d), resid, exp)
    return out


def outer_leaves(cfg: dict) -> list:
    m = dims(cfg)
    head = weight_format(cfg, HEAD_FORMAT)
    return [(("embed", "emb"), "emb", (m["V"], m["d"]), jnp.float32, {}),
            (("final_norm", "scale"), "norm", (m["d"],), jnp.float32, {}),
            (("lm_head",), "codes", (m["d"], m["V"]), None, {"fmt": head}),
            (("lm_head", "w_scale"), "scale", (1, m["V"]), jnp.float32,
             {"K": m["d"], "gain": 1.0, "fmt": head})]


# -- the reference step ----------------------------------------------------

def deinterleave(x):
    """Pairs (x0, x1), (x2, x3), ... to [evens | odds], as
    ``apply_rotary_pos_emb`` orders the rope dims before rotating halves."""
    D = x.shape[-1]
    return jnp.swapaxes(x.reshape(x.shape[:-1] + (D // 2, 2)), -1, -2
                        ).reshape(x.shape)


def attend(q, k, v, q_pos, k_pos):
    """Softmax attention of q [B,S,H,Dq] over k [B,T,H,Dq] and v
    [B,T,H,Dv], scale 1/sqrt(Dq); key t is seen by query s where
    k_pos[t] <= q_pos[s]."""
    D = q.shape[-1]
    q = q * jnp.asarray(1.0 / math.sqrt(D), q.dtype)
    s = jnp.einsum("bshd,bkhd->bshk", q, k,
                   preferred_element_type=jnp.float32)
    keep = k_pos[:, None, :] <= q_pos[:, :, None]
    s = jnp.where(keep[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bshk,bkhd->bshd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attention(cfg: dict, p: dict, x, *, mono: int, T: int, lower: str):
    """The attention half of a layer: ``x`` plus its MLA output, and the
    layer's latent rows {"c_kv" [B, L, r], "k_pe" [B, L, rope]} as the
    cache holds them.

    As the server runs it: positions admitted by the batched prefill
    (``mono`` leading positions) take the expanded form over the prompt's
    keys; every later position takes the absorbed form one position at a
    time over a ``T`` (``max_len``) latent buffer masked past its own
    position, as a decode step does.  Control ``kv_fp8`` rounds the latent
    rows to float8 (e4m3) where bfloat16 is stated."""
    m = dims(cfg)
    cd = x.dtype
    fmt = weight_format(cfg, m["format"])
    B, L, _ = x.shape
    H, r, nope = m["h"], m["r"], m["nope"]
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))

    h = rms_norm(x, p["ln1"]["scale"], m["eps"])
    a = p["attn"]
    q = linear(h, a["wq"], m["abits"], cd, fmt).reshape(B, L, H, -1)
    kv = linear(h, a["wkv_a"], m["abits"], cd, fmt)
    c = rms_norm(kv[..., :r], a["kv_norm"]["scale"], m["eps"])
    q_nope = q[..., :nope]
    q_pe = rope(deinterleave(q[..., nope:]), pos, m["theta"])
    k_pe = rope(deinterleave(kv[..., r:])[:, :, None], pos,
                m["theta"])[:, :, 0]
    if lower == "kv_fp8":
        c = c.astype(jnp.float8_e4m3fn).astype(cd)
        k_pe = k_pe.astype(jnp.float8_e4m3fn).astype(cd)
    w = a["wkv_b"]["w"].astype(cd).reshape(r, H, -1)
    w_uk, w_uv = w[..., :nope], w[..., nope:]
    outs = []
    if mono:
        def expanded(qm, cm, kp, pm):
            k_nope = jnp.einsum("bsr,rhd->bshd", cm, w_uk,
                                preferred_element_type=jnp.float32
                                ).astype(cd)
            v = jnp.einsum("bsr,rhd->bshd", cm, w_uv,
                           preferred_element_type=jnp.float32).astype(cd)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                kp[:, :, None], k_nope.shape[:3] + kp.shape[-1:])], -1)
            return attend(qm, k, v, pm, pm)

        args = (jnp.concatenate([q_nope[:, :mono], q_pe[:, :mono]], -1),
                c[:, :mono], k_pe[:, :mono], pos[:, :mono])
        # one row's scores at a time: [H, mono, mono] each
        outs.append(jax.lax.map(
            lambda a: expanded(*(t[None] for t in a))[0], args))
    if L > mono:
        cT = jnp.pad(c, ((0, 0), (0, T - L), (0, 0)))
        kT = jnp.pad(k_pe, ((0, 0), (0, T - L), (0, 0)))
        tpos = jnp.arange(T, dtype=jnp.int32)
        scale = 1.0 / math.sqrt(nope + m["rope"])

        def one(_, t):               # one query position, as a decode step
            qn = jax.lax.dynamic_index_in_dim(q_nope, t, 1, False)
            qp = jax.lax.dynamic_index_in_dim(q_pe, t, 1, False)
            q_lat = jnp.einsum("bhd,rhd->bhr", qn, w_uk,
                               preferred_element_type=jnp.float32
                               ).astype(cd)
            s = (jnp.einsum("bhr,btr->bht", q_lat, cT,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bhe,bte->bht", qp, kT,
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where((tpos <= t)[None, None], s, NEG_INF)
            prob = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bht,btr->bhr", prob.astype(cd), cT,
                               preferred_element_type=jnp.float32
                               ).astype(cd)
            return None, jnp.einsum("bhr,rhd->bhd", o_lat, w_uv,
                                    preferred_element_type=jnp.float32)

        _, o = jax.lax.scan(one, None, jnp.arange(mono, L))
        outs.append(jnp.moveaxis(o, 0, 1))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    x = x + linear(out.astype(cd).reshape(B, L, -1), a["wo"], m["abits"],
                   cd, fmt)
    return x, {"c_kv": c, "k_pe": k_pe}


def swiglu(cfg: dict, f: dict, h):
    """``silu(h Wg) * (h Wi)``, then ``Wo``: w4 projections, the gate's
    silu on its bfloat16 output (the program's ``layers.mlp``)."""
    m = dims(cfg)
    cd = h.dtype
    fmt = weight_format(cfg, m["format"])
    y = jax.nn.silu(linear(h, f["wg"], m["abits"], cd, fmt)) * linear(
        h, f["wi"], m["abits"], cd, fmt)
    return linear(y, f["wo"], m["abits"], cd, fmt)


def route(cfg: dict, f: dict, h, *, lower: str):
    """(gates f32 [T, k], expert ids [T, k]) of tokens ``h`` [T, d]:
    compute-dtype (bfloat16) activations and router weight, logits in
    float32 and a float32 sigmoid (control
    ``router_bf16``: both in bfloat16), top-k on score + bias, gates the
    chosen scores renormalised times the routed scale."""
    m = dims(cfg)
    w = f["router"]["w"].astype(h.dtype)
    if lower == "router_bf16":
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
        ).astype(jnp.float32)
    else:
        scores = jax.nn.sigmoid(jnp.dot(
            h, w, preferred_element_type=jnp.float32))
    _, ids = jax.lax.top_k(scores + f["score_bias"], m["k"])
    g = jnp.take_along_axis(scores, ids, axis=1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * m["scale"], ids


def routed(cfg: dict, f: dict, h, ids, gates):
    """The routed experts' part of the layer over tokens ``h`` [T, d]:
    each expert runs over the (token, expert) pairs routed to it
    (``jax.lax.ragged_dot`` over the pairs sorted by expert).  Codes are
    int4 times int4 and at most K = 2048 of them add up, so the products
    summed in float32 are exact integers, as the program's int32 sums."""
    m = dims(cfg)
    cd = h.dtype
    k, E = m["k"], m["E"]
    exp = weight_format(cfg, EXPERTS)
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    e_of = flat[order]

    def proj(x, leaf):
        a_q, a_s = act_codes(x.astype(jnp.float32), m["abits"])
        acc = jax.lax.ragged_dot(a_q.astype(jnp.bfloat16),
                                 exp.codes(leaf).astype(jnp.bfloat16), sizes,
                                 preferred_element_type=jnp.float32)
        return (acc * a_s * leaf["w_scale"][e_of, 0]).astype(cd)

    xs = h[tok]
    y = jax.nn.silu(proj(xs, f["wg"]).astype(jnp.float32)).astype(cd) * \
        proj(xs, f["wi"])
    out = jnp.zeros_like(xs).at[order].set(proj(y, f["wo"]))
    T = h.shape[0]
    return jnp.sum((out.astype(jnp.float32) * gates.reshape(-1, 1)
                    ).reshape(T, k, -1), axis=1).astype(cd)


def moe(cfg: dict, p: dict, x, *, lower: str):
    """The MoE half's output (to add to ``x``) over the layer's second
    norm: routed experts plus the ungated shared experts, over blocks of
    ``MOE_BLOCK`` tokens (each token's sums are its own)."""
    B, L, d = x.shape
    f = p["moe"]
    h = rms_norm(x, p["ln2"]["scale"], dims(cfg)["eps"]).reshape(-1, d)
    T = h.shape[0]
    n = -(-T // MOE_BLOCK)
    hb = jnp.pad(h, ((0, n * MOE_BLOCK - T), (0, 0))).reshape(
        n, MOE_BLOCK, d)

    def block(hb):
        gates, ids = route(cfg, f, hb, lower=lower)
        return routed(cfg, f, hb, ids, gates) + swiglu(cfg, f["shared"], hb)

    y = jax.lax.map(block, hb) if n > 1 else block(hb[0])[None]
    return y.reshape(-1, d)[:T].reshape(B, L, d)


def layer(cfg: dict, kind: str, p: dict, x, *, mono: int, T: int,
          lower: str):
    """One layer over ``x`` [B, L, d]: (its output, its latent rows).
    Controls: ``kv_fp8`` (latent cache in float8), ``router_bf16``
    (router logits and sigmoid in bfloat16)."""
    x, rows = attention(cfg, p, x, mono=mono, T=T, lower=lower)
    if kind == "dense":
        h = rms_norm(x, p["ln2"]["scale"], dims(cfg)["eps"])
        return x + swiglu(cfg, p["mlp"], h), rows
    return x + moe(cfg, p, x, lower=lower), rows


# -- what the check reads from the program ----------------------------------

def cache_rows(cache, slots, P: int) -> dict:
    """The program's latent rows of ``slots``, the first ``P`` positions:
    {"c_kv" [n_layers, len(slots), P, r], "k_pe" [.., rope]}, the leading
    layers' entries first, as the layers run."""
    return {name: jnp.concatenate([c[name][:, slots, :P] for c in cache])
            for name in ("c_kv", "k_pe")}


# -- work of a forward (bench/cost.py) ---------------------------------------

def kernel_calls(cfg: dict, kernel: str) -> list:
    """(K, N, code bits) of each call of ``kernel`` in one forward: the
    projections outside the routed experts on the body format's kernel,
    the head on the head format's."""
    m = dims(cfg)
    body = weight_format(cfg, m["format"])
    head = weight_format(cfg, HEAD_FORMAT)
    out = []
    if body.KERNEL == kernel:
        for kind in layout(cfg).kinds():
            out += [(K, N, body.BITS) for _, K, N, _ in
                    projections(cfg, kind)]
    if head.KERNEL == kernel:
        out.append((m["d"], m["V"], head.BITS))
    return out


def expert_bytes(cfg: dict) -> float:
    """One routed expert's codes and scales."""
    m = dims(cfg)
    return 3 * m["d"] * m["eff"] * 4 / 8 + 4 * (2 * m["eff"] + m["d"])


def expert_work(cfg: dict, pairs: float, hit: float) -> Work:
    """The routed experts' work: ``pairs`` (token, expert) pairs through
    three int4 projections each, the codes of ``hit`` experts read once,
    and each pair's activations in and out."""
    m = dims(cfg)
    w = Work()
    for K, N in ((m["d"], m["eff"]), (m["d"], m["eff"]), (m["eff"], m["d"])):
        one = matmul(pairs, K, N, 4)
        w = w + Work(int_ops=one.int_ops,
                     bytes=one.bytes - K * N * 4 / 8 - 4 * N)
    return w + Work(bytes=hit * expert_bytes(cfg))


def forward(cfg: dict, ctx: list) -> Work:
    """One forward of the rows whose context lengths are ``ctx``, decode
    form: every weight outside the routed experts read once, the experts
    a uniform router is expected to hit, E (1 - (1 - k/E)^rows) a layer,
    read once; each row's projections, routed experts, router and head;
    its absorbed MLA over its own latent rows."""
    m = dims(cfg)
    rows = len(ctx)
    if not rows:
        return Work()
    moe_layers = m["L"] - m["lead"]
    kinds = layout(cfg).kinds()
    proj = sum(K * N for kind in kinds for _, K, N, _ in
               projections(cfg, kind))
    per_row = 2.0 * (proj + moe_layers * m["k"] * 3 * m["d"] * m["eff"]
                     + m["d"] * m["V"])
    kv_b = m["r"] * m["h"] * (m["nope"] + m["dv"])
    lat = m["r"] + m["rope"]
    attn = sum(m["L"] * (2.0 * kv_b + 2.0 * m["h"] * (lat + m["r"]) * c)
               for c in ctx)
    router = 2.0 * rows * moe_layers * m["d"] * m["E"]
    hit = m["E"] * (1 - (1 - m["k"] / m["E"]) ** rows)
    body = weight_format(cfg, m["format"]).BITS
    weights = (sum(K * N * body / 8 + 4 * N for kind in kinds
                   for _, K, N, _ in projections(cfg, kind))
               + m["L"] * (2 * kv_b + 4 * (2 * m["d"] + m["r"]))
               + moe_layers * (hit * expert_bytes(cfg) + 2 * m["d"] * m["E"]
                               + 4 * m["E"])
               + m["d"] * m["V"] + 4 * m["V"] + 4 * m["d"])
    return Work(int_ops=rows * per_row, bf16_ops=attn + router,
                bytes=weights + m["L"] * lat * 2 * sum(ctx)
                + rows * 4 * m["d"])


# -- a CPU-size copy (bench/tests) --------------------------------------------

def tiny(cfg: dict, layers: int) -> dict:
    """The configuration at width 64: 4 heads, kv_lora_rank 32, rope 8,
    nope 16, v 16, d_ff 128, 8 experts of width 32 (top-2, 1 shared),
    vocab 512, one leading dense layer and ``layers - 1`` MoE layers."""
    return {**cfg, "hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 4, "kv_lora_rank": 32,
            "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
            "n_routed_experts": 8, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "num_hidden_layers": layers, "vocab_size": 512}
