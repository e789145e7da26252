"""DeepSeek-V3 pieces of the program at a tiny size: multi-head latent
attention (expanded prefill form vs absorbed decode form), sigmoid routing
with a selection-only bias, dropless dispatch and its counters, a leading
layer outside the scan with its own cache entry, and the RMSNorm eps taken
from the configuration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn_lib
from repro.models import layers as layers_lib
from repro.models import moe as moe_lib
from repro.models import transformer
from repro.models.moe import MoEConfig, init_moe, moe_ffn
from repro.models.transformer import BlockSpec, MLAConfig, ModelConfig

MLA = MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                v_head_dim=16)
MOE = MoEConfig(n_experts=8, top_k=2, d_ff=32, shared_ff=32,
                scoring="sigmoid", routed_scale=2.446, shared_gate=False,
                dropless=True)
CFG = ModelConfig(
    name="tiny-dsv3", family="moe", n_layers=5, d_model=64, n_heads=4,
    n_kv=4, head_dim=24, d_ff=128, vocab=256,
    lead=(BlockSpec(kind="attn", attn_type="mla", mlp="swiglu"),),
    pattern=(BlockSpec(kind="attn", attn_type="mla", mlp="moe"),),
    mla=MLA, moe=MOE, rope_theta=50000.0, norm_eps=1e-5,
    compute_dtype="float32")


def _mla_params(seed=0):
    p = attn_lib.init_mla(jax.random.PRNGKey(seed), 64, 4, MLA)
    # norm scales away from 1 so the latent norm matters
    p["kv_norm"]["scale"] = jax.random.uniform(
        jax.random.PRNGKey(seed + 1), (MLA.kv_lora_rank,), jnp.float32,
        0.8, 1.2)
    return p


def test_absorbed_decode_is_the_expanded_attention():
    """In float32 the absorbed form (W_UK folded into the query, the
    readout over c_kv) equals the expanded form token by token."""
    B, S, T = 2, 12, 16
    p = _mla_params()
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, 64))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kw = dict(n_heads=4, mla=MLA, rope_theta=50000.0, norm_eps=1e-5,
              compute_dtype=jnp.float32)
    want, latent = jax.jit(lambda p, x: attn_lib.mla_attention(
        p, x, pos, return_cache=True, **kw))(p, x)
    cache = {k: jnp.zeros((B, T) + v.shape[2:]) for k, v in latent.items()}

    def step(cache, t):
        y, cache = attn_lib.mla_decode_attention(
            p, jax.lax.dynamic_slice_in_dim(x, t, 1, 1), cache,
            jnp.full((B,), t), **kw)
        return cache, y[:, 0]

    cache, got = jax.jit(lambda c: jax.lax.scan(step, c, jnp.arange(S)))(
        cache)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 0, 1)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    for k in latent:                     # the cache holds the same rows
        np.testing.assert_allclose(np.asarray(cache[k][:, :S]),
                                   np.asarray(latent[k]), rtol=1e-6,
                                   atol=1e-6)


def test_mla_rope_deinterleaves_pairs_then_rotates_halves():
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(attn_lib.deinterleave(x)),
                                  [0, 2, 4, 6, 1, 3, 5, 7])


def _moe(seed=0, bias=None):
    p = init_moe(jax.random.PRNGKey(seed), 16, MOE)
    if bias is not None:
        p["score_bias"] = bias
    return p


def test_sigmoid_bias_chooses_experts_but_not_gates():
    """The bias moves the choice; the gates stay the chosen scores,
    renormalised and scaled, whatever the bias."""
    p = _moe()
    xf = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    scores = jax.nn.sigmoid(xf @ p["router"]["w"])
    g0, ids0, _ = moe_lib._route(p, xf, MOE, jnp.float32)
    big = jnp.zeros((8,)).at[5].set(10.0)     # expert 5 always chosen
    g1, ids1, _ = moe_lib._route({**p, "score_bias": big}, xf, MOE,
                                 jnp.float32)
    assert (np.asarray(ids1) == 5).any(axis=1).all()
    assert not np.array_equal(np.asarray(ids0), np.asarray(ids1))
    for g, ids in ((g0, ids0), (g1, ids1)):
        chosen = jnp.take_along_axis(scores, ids, axis=1)
        want = chosen / chosen.sum(-1, keepdims=True) * 2.446
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-6)


def test_dropless_keeps_every_pair_when_all_tokens_pick_one_expert():
    E = MOE.n_experts
    bias = jnp.zeros((E,)).at[jnp.array([2, 6])].set(100.0)
    p = _moe(bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, 16))
    y, _, stats = moe_ffn(p, x, MOE, compute_dtype=jnp.float32,
                          with_stats=True)
    assert np.asarray(stats).tolist() == [2, 4 * 6 * 2, 0]
    # the same as each token through its two experts densely
    xf = x.reshape(-1, 16)
    scores = jax.nn.sigmoid(xf @ p["router"]["w"])
    g = scores[:, jnp.array([2, 6])]
    g = g / g.sum(-1, keepdims=True) * 2.446
    want = 0.0
    for j, e in enumerate((2, 6)):
        h = jax.nn.silu(xf @ p["wg"][e]) * (xf @ p["wi"][e])
        want = want + g[:, j:j + 1] * (h @ p["wo"][e])
    want = want + layers_lib.mlp(p["shared"], xf, "swiglu", "none",
                                 jnp.float32)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 16)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    # a capacity-bound router drops there
    capped = dataclasses.replace(MOE, dropless=False, capacity_factor=1.0)
    _, _, stats = moe_ffn(p, x, capped, compute_dtype=jnp.float32,
                          with_stats=True)
    assert np.asarray(stats)[2] > 0


def test_dropless_token_blocks_are_one_group(monkeypatch):
    """Past DROPLESS_BLOCK tokens the global dispatch routes in blocks
    (the tail block padded): the same outputs, and counters summed over
    the blocks with no pad row counted."""
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7, 16))   # 21 tokens
    want, _, n_want = moe_ffn(p, x, MOE, compute_dtype=jnp.float32,
                              with_stats=True)
    monkeypatch.setattr(moe_lib, "DROPLESS_BLOCK", 8)
    got, _, n_got = moe_ffn(p, x, MOE, compute_dtype=jnp.float32,
                            with_stats=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert np.asarray(n_got).tolist()[1:] == [21 * 2, 0]
    # experts hit are summed over the three blocks
    assert np.asarray(n_want)[0] <= np.asarray(n_got)[0] <= 3 * 8


def test_packed_expert_codes_contract_exactly():
    """Even k in the low nibbles, odd in the high: the split contraction
    equals the integer einsum over the unpacked codes."""
    from repro.serve.quantize import quantize_leaf
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 24))
    leaf = quantize_leaf(w, 4)
    a = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64))
    got = jax.jit(moe_lib._expert_einsum, static_argnums=2)(a, leaf,
                                                            jnp.float32)
    from repro.core.lut import unpack_int4
    codes = jnp.swapaxes(unpack_int4(jnp.swapaxes(leaf["w_q"], -1, -2)),
                         -1, -2)
    s = jnp.maximum(jnp.abs(a).max(-1, keepdims=True), 1e-8) / 7
    a_q = jnp.clip(jnp.round(a / s), -8, 7).astype(jnp.int8)
    acc = jnp.einsum("ecd,edf->ecf", a_q, codes,
                     preferred_element_type=jnp.int32)
    # the integer sums agree exactly; only the float rescale rounds (a
    # nibble read in the wrong order would be off by whole codes)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(acc * s * leaf["w_scale"]), rtol=1e-6)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(jax.random.PRNGKey(0), CFG)


def test_leading_layer_has_its_own_params_and_cache_entry(params):
    assert set(params["lead"]) == {"layer0"}
    assert "mlp" in params["lead"]["layer0"]
    assert params["blocks"][0]["moe"]["wi"].shape[0] == CFG.n_groups == 4
    cache = transformer.init_cache(CFG, 2, 16)
    assert [c["c_kv"].shape[0] for c in cache] == [1, 4]


def test_prefill_then_decode_is_the_forward_and_reads_the_lead_row(
        params, monkeypatch):
    monkeypatch.setattr(moe_lib, "DROPLESS_BLOCK", 16)  # prefill in blocks
    B, S = 2, 20
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, 256)
    full, _ = jax.jit(lambda p, t: transformer.forward(p, CFG, t))(
        params, tok)
    logits, pc = jax.jit(lambda p, t: transformer.prefill(p, CFG, t))(
        params, tok[:, :S])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, S - 1]),
                               rtol=1e-4, atol=1e-4)
    T = 32
    cache = tuple(jax.tree_util.tree_map(
        lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, T - S), (0, 0))), c)
        for c in pc)
    decode = jax.jit(lambda p, t, c: transformer.decode_step(
        p, CFG, t, c, jnp.full((B,), S), counters=True))
    step, new, n = decode(params, tok[:, S], cache)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full[:, S]),
                               rtol=1e-4, atol=1e-4)
    # the leading layer wrote its row at position S ...
    assert float(jnp.abs(new[0]["c_kv"][0, :, S]).max()) > 0
    assert float(jnp.abs(cache[0]["c_kv"][0, :, S]).max()) == 0
    # ... and reads its rows: without them the step differs
    blank = ({k: jnp.zeros_like(v) for k, v in cache[0].items()},) + \
        cache[1:]
    other, _, _ = decode(params, tok[:, S], blank)
    assert float(jnp.abs(other - step).max()) > 1e-3
    # every MoE layer's pairs, none dropped
    assert np.asarray(n).tolist()[1:] == [4 * B * 2, 0]


def test_norm_eps_reaches_every_norm(params, monkeypatch):
    seen = []
    real = layers_lib.rms_norm

    def spy(p, x, eps=1e-6, zero_centered=False):
        seen.append(eps)
        return real(p, x, eps, zero_centered)

    monkeypatch.setattr(transformer, "rms_norm", spy)
    monkeypatch.setattr(layers_lib, "rms_norm", spy)
    tok = jnp.zeros((1, 4), jnp.int32)
    jax.eval_shape(lambda p: transformer.forward(p, CFG, tok), params)
    # per layer ln1, ln2 and the latent norm, then the final norm; the
    # scan traces its body once
    assert len(seen) == 3 + 3 + 1 and set(seen) == {1e-5}
    seen.clear()
    jax.eval_shape(lambda p: transformer.decode_step(
        p, CFG, tok[:, 0], transformer.init_cache(CFG, 1, 8),
        jnp.zeros((1,), jnp.int32)), params)
    assert len(seen) == 7 and set(seen) == {1e-5}


def test_paged_serving_refuses_the_latent_cache(params):
    from repro.serve import ServeConfig, make_engine
    with pytest.raises(NotImplementedError, match="latent"):
        make_engine(params, CFG, ServeConfig(max_len=16, paged=True))
