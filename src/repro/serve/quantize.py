"""Offline weight quantization for serving — the deployment-side of the
paper's flow: weights leave the QAT checkpoint as *integer codes* (packed
int4 nibbles or int8) + per-output-channel scales, exactly what the LUT
kernel consumes.  At decode, weight HBM traffic drops 4x (w4) / 2x (w8) vs
bf16 — the memory-roofline move that is LUTMUL's claim transposed to TPU.

A quantized projection leaf looks like::

    {"w_q": uint8[.., K//2, N]   (packed int4)   or  int8[.., K, N],
     "w_scale": f32[.., 1, N]}

or, for the T-MAC bitplane family (w1/w2/w3/w4/ternary weights)::

    {"w_q": uint8[P, K//8, N]    (packed bitplanes, P = plane count),
     "w_scale": f32[1, N],
     "w_tmac": uint8[0],          # zero-size formulation marker
     "w_tern": uint8[0]}          # present iff ternary (P=2 is ambiguous)

The markers are zero-size arrays so the choice is *static pytree
structure* (same idiom as the dist.tp ``tp_*`` markers) — ``jit`` sees the
bit width without tracing on values.  ``models.layers.linear`` dispatches
on the presence of ``w_q`` and on its rank (3D = tmac).
Embedding and lm_head follow the paper's first/last-layer rule (8-bit).
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lut import pack_int4

# projection leaves eligible for low-bit quantization (trailing ['w'])
_INNER_W = re.compile(
    r"\['(wq|wk|wv|wo|wi|wg|wr|in_proj|out_proj)'\]\['w'\]$")
_MOE_W = re.compile(r"\['moe'\]\['w[igo]'\]$")
_HEAD_W = re.compile(r"\['lm_head'\]\['w'\]$")


def quantize_leaf(w: jax.Array, bits: int):
    """Float weight [..., K, N] -> {"w_q", "w_scale"} serving codes.

    Every weight-quantization event in the codebase funnels through here or
    ``kernels.lutmul.ops.quantize_weights`` — both bump
    ``ops.WEIGHT_QUANT_COUNT`` so tests can assert that cached layers
    quantize once at load, never per forward call.
    """
    from repro.kernels.lutmul import ops as lut_ops
    lut_ops.WEIGHT_QUANT_COUNT += 1
    codes, scale = lut_ops.absmax_codes(w, 2 ** (bits - 1) - 1)
    q = codes.astype(jnp.int8)
    if bits == 4:
        q = jnp.swapaxes(pack_int4(jnp.swapaxes(q, -1, -2)), -1, -2)
    return {"w_q": q, "w_scale": scale}


_quantize_leaf = quantize_leaf          # backwards-compat alias


def quantize_leaf_mode(w: jax.Array, mode: str):
    """Mode-aware leaf quantizer: float weight -> serving codes dict.

    Legacy modes ("w4a4_lut"/"w4a4_mxu"/"w8a8") produce the nibble/int8
    leaf; tmac-family modes produce the bitplane leaf with markers (leading
    stack dims — the scanned per-group block axis — pass through).  A
    suffix-free sub-4-bit mode ("w2a4") lets :func:`ops.pick_formulation`
    A/B tmac vs one-hot per (bits, shape) and stores the winner's format —
    the stored leaf IS the formulation choice.  MoE expert banks must use
    legacy modes (``quantize_params_for_serving`` coerces them): tmac
    targets the dense projections; ``moe._expert_einsum`` consumes
    nibble/int8 stacks.
    """
    from repro.kernels.lutmul import ops as lut_ops
    form, wspec, abits = lut_ops.parse_mode(mode)
    if form == "int":
        return quantize_leaf(w, 8 if abits >= 8 else 4)
    if form == "auto":
        form = lut_ops.pick_formulation(wspec, abits, w.shape[-2],
                                        w.shape[-1])
    if form == "onehot":
        # sub-4-bit codes are valid 4-bit codes: quantize at the leaf's own
        # width, store in the nibble format the one-hot kernel consumes
        if lut_ops.weight_bits(wspec) < 4:
            planes, scale = lut_ops.quantize_weights_planes(w, wspec)
            from repro.core.lut import decode_planes, unpack_bitplanes
            q = decode_planes(unpack_bitplanes(planes), wspec).astype(jnp.int8)
            q = jnp.swapaxes(pack_int4(jnp.swapaxes(q, -1, -2)), -1, -2)
            return {"w_q": q, "w_scale": scale.astype(jnp.float32)}
        return quantize_leaf(w, 4)
    planes, scale = lut_ops.quantize_weights_planes(w, wspec)
    # markers shaped leading_stack_dims + (0,) so they scan like any leaf
    marker = jnp.zeros(planes.shape[:-3] + (0,), jnp.uint8)
    leaf = {"w_q": planes, "w_scale": scale.astype(jnp.float32),
            "w_tmac": marker}
    if wspec == "ternary":
        leaf["w_tern"] = marker
    return leaf


def quantize_params_for_serving(params, mode: str = "w4a4_mxu",
                                bits_plan: Optional[dict] = None):
    """Replace eligible projection weights with integer codes + scales.

    mode: w4a4_lut | w4a4_mxu -> int4 inner, int8 head; w8a8 -> int8 all;
    tmac family (``w{1,2,3,4}a{4,8}[_tmac]``, ``ternary_a{4,8}[_tmac]``) ->
    bitplane leaves (suffix-free = formulation auto-picked per shape).

    ``bits_plan``: optional {path -> mode string} per-leaf override (the
    output of ``roofline.analysis.plan_mixed_bits``) keyed by the same
    ``"...['wq']['w']"`` path strings this walk builds — lets the roofline
    model choose mixed per-layer bit widths while everything else follows
    ``mode``.

    Every eligible leaf is converted through ``models.layers.QuantizedLinear``
    — THE weight-code cache: quantize + pack exactly once here, zero
    weight-quantization events afterwards (serving decode and the QAT eval
    path in ``train.loop`` both ride this invariant).
    """
    from repro.kernels.lutmul import ops as lut_ops
    from repro.models.layers import QuantizedLinear

    plan = bits_plan or {}

    def codes(leaf: dict, leaf_mode: str) -> dict:
        return QuantizedLinear(leaf, mode=leaf_mode).params

    def legacy(leaf_mode: str) -> str:
        # MoE expert banks stay on the nibble/int8 stack format
        # (moe._expert_einsum consumes it); coerce tmac modes down
        form, _, abits = lut_ops.parse_mode(leaf_mode)
        if form in ("int", "onehot"):
            return leaf_mode
        return "w8a8" if abits >= 8 else "w4a4_mxu"

    def walk(tree, path=""):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                sub = f"{path}['{k}']"
                if isinstance(v, dict) and "w" in v and _INNER_W.search(
                        sub + "['w']") and v["w"].ndim >= 2:
                    out[k] = codes(v, plan.get(sub + "['w']", mode))
                elif _MOE_W.search(sub) and not isinstance(v, dict):
                    out[k] = codes({"w": v}, legacy(plan.get(sub, mode)))
                elif isinstance(v, dict) and "w" in v and _HEAD_W.search(
                        sub + "['w']"):
                    out[k] = codes(v, "w8a8")     # paper: last layer 8-bit
                else:
                    out[k] = walk(v, sub)
            return out
        if isinstance(tree, (tuple, list)):
            return type(tree)(walk(v, f"{path}[{i}]")
                              for i, v in enumerate(tree))
        return tree

    return walk(params)


def init_quantized_params(key, cfg, mode: str):
    """Random serving params of a decoder LM, quantized as they are built.

    Bit-identical to ``quantize_params_for_serving(init_params(key, cfg),
    mode)`` and with the same ``WEIGHT_QUANT_COUNT``, but the
    float model never exists: the embedding and head come first, then each
    layer group is initialised from ``init_params``' own keys, quantized
    by one jitted walk (traced once, so each leaf counts once) and written
    into the stacked code buffers in place.  Peak memory is the quantized
    model plus one group's float weights — how a full-width model reaches
    a device that cannot hold it in float.
    """
    from repro.models import transformer

    keys = jax.random.split(key, cfg.n_layers + 4)
    # donated: the float head is freed once its codes exist, and the
    # embedding passes through without a copy
    outer = jax.jit(lambda p: quantize_params_for_serving(p, mode),
                    donate_argnums=0)(transformer.init_outer(keys, cfg))
    gkeys = transformer.group_keys(keys, cfg)
    quant = jax.jit(lambda blocks: quantize_params_for_serving(
        {"blocks": blocks}, mode)["blocks"])
    put = jax.jit(lambda buf, piece, g: jax.tree_util.tree_map(
        lambda b, p: b.at[g].set(p), buf, piece), donate_argnums=0)
    blocks = None
    for g in range(cfg.n_groups):
        piece = quant(transformer.init_group(gkeys[g], cfg))
        if blocks is None:
            blocks = jax.tree_util.tree_map(
                lambda p: jnp.zeros((cfg.n_groups,) + p.shape, p.dtype),
                piece)
        blocks = put(blocks, piece, g)
    return {**outer, "blocks": blocks}


def _draftable(leaf, draft_planes: int) -> bool:
    """True for tmac leaves whose plane stack truncates to ``draft_planes``.

    Positional int planes only: ternary's two planes are (+1, -1) masks, not
    powers of two, so it (and w1) pass through undrafted — as do leaves
    already at or below the draft width, one-hot nibble leaves, the w8a8
    head, and MoE banks (legacy stack format).
    """
    return (isinstance(leaf, dict) and "w_tmac" in leaf
            and "w_tern" not in leaf and leaf["w_q"].ndim >= 3
            and leaf["w_q"].shape[-3] > draft_planes >= 2)


def draft_params_view(params, draft_planes: int):
    """Truncated-plane drafter view of quantized serving params.

    For every draftable tmac leaf, slice the top ``draft_planes`` bitplanes
    (plane axis -3 — leading scanned stack dims pass through) and fold the
    ``2^(B-p)`` coefficient factor into ``w_scale``; every other leaf is the
    *same object* as the target's.  The view is a pure tree walk over slices
    — zero extra weight memory, safe to build inside ``jit`` (XLA hoists it
    as loop-invariant), and it preserves the ``w_tmac``/tp markers so
    formulation dispatch and the row-parallel int32 psum work unchanged.
    """
    from repro.kernels.lutmul import ops as lut_ops

    def walk(tree):
        if isinstance(tree, dict):
            if _draftable(tree, draft_planes):
                wbits = int(tree["w_q"].shape[-3])
                sliced, _, mult = lut_ops.truncate_planes(
                    tree["w_q"], wbits, draft_planes)
                out = dict(tree)
                out["w_q"] = sliced
                out["w_scale"] = tree["w_scale"] * jnp.float32(mult)
                return out
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, (tuple, list)):
            return type(tree)(walk(v) for v in tree)
        return tree

    return walk(params)


def count_draftable_leaves(params, draft_planes: int) -> int:
    """How many leaves :func:`draft_params_view` would actually truncate."""
    n = 0

    def walk(tree):
        nonlocal n
        if isinstance(tree, dict):
            if _draftable(tree, draft_planes):
                n += 1
            else:
                for v in tree.values():
                    walk(v)
        elif isinstance(tree, (tuple, list)):
            for v in tree:
                walk(v)

    walk(params)
    return n


def dequantize_weight(p: dict, dtype=jnp.bfloat16) -> jax.Array:
    """Reassemble a float weight from codes (tests / fallbacks)."""
    from repro.core.lut import decode_planes, unpack_bitplanes, unpack_int4
    q = p["w_q"]
    if "w_tmac" in p:             # packed bitplanes (plane axis is -3:
        # leading stack dims — the scanned block axis — pass through)
        spec = "ternary" if "w_tern" in p else int(q.shape[-3])
        q = decode_planes(unpack_bitplanes(q), spec)
    elif q.dtype == jnp.uint8:    # packed int4
        q = jnp.swapaxes(unpack_int4(jnp.swapaxes(q, -1, -2), signed=True),
                         -1, -2)
    return (q.astype(jnp.float32) * p["w_scale"]).astype(dtype)
