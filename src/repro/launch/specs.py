"""Per-cell lowering specs: the function to lower, ShapeDtypeStruct inputs,
and in/out shardings for every (arch x shape x mesh) combination.

Nothing here allocates device memory — params/state/caches are eval_shape'd
(the shannon/kernels ShapeDtypeStruct pattern).
"""
from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.dist import partitioning
from repro.dist.sharding import Rules
from repro.models import encdec, transformer
from repro.train.step import TrainConfig, init_state, make_train_step

SDS = jax.ShapeDtypeStruct


def _named(mesh: Mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def validate_specs(sds_tree, spec_tree, mesh: Mesh):
    """Drop spec axes whose dimension is not divisible by the mesh axes.

    pjit in/out shardings require exact divisibility (unlike internal
    with_sharding_constraint, which GSPMD pads).  Non-divisible cases —
    GQA KV heads (4/8/10/20 over model=16), MiniCPM's 122753 vocab — fall
    back to replication on that dim; DESIGN.md notes the cost.
    """
    def fix(sds, spec):
        if not isinstance(spec, P):
            return spec
        parts = list(spec) + [None] * (len(sds.shape) - len(spec))
        out = []
        for dim, entry in zip(sds.shape, parts):
            if entry is not None and dim % _axis_size(mesh, entry) != 0:
                entry = None
            out.append(entry)
        return P(*out)

    return jax.tree_util.tree_map(fix, sds_tree, spec_tree,
                                  is_leaf=lambda x: isinstance(
                                      x, jax.ShapeDtypeStruct))


def _batch_axes(rules: Rules):
    return rules.get("batch")


def cache_specs(cache_sds, rules: Rules, mesh: Mesh | None = None) -> Any:
    """PartitionSpec tree for a decode cache (by leaf name/rank).

    KV leaves prefer head sharding; when the arch's kv-head count does not
    divide the model axis (GQA: 4/8/10/20/36 vs 16), the cache falls back to
    *sequence-over-model* sharding — attention then contracts over a sharded
    T axis (partial-softmax + small all-reduce), which is the right serving
    layout for kv-head-poor models (fixes e.g. minicpm decode_32k going from
    a replicated 388 GB/device cache to a fully sharded one).
    """
    b = rules.get("batch")
    kvh = rules.get("kv_heads")
    h = rules.get("heads")
    m = rules.get("mlp")
    seq_kv = rules.get("seq_kv")

    def _kv_spec(x):
        T_dim, H_dim = x.shape[-3], x.shape[-2]
        kv_ok = (mesh is None or kvh is None
                 or (H_dim % _axis_size(mesh, kvh) == 0))
        if kv_ok:
            return (b, seq_kv, kvh, None)
        # fall back: shard T over the model axis (plus any seq_kv axes)
        model_ax = kvh
        seq_axes = []
        for ax in (seq_kv, model_ax):
            if ax is None:
                continue
            seq_axes.extend(ax if isinstance(ax, (tuple, list)) else (ax,))
        seq_entry = tuple(seq_axes) if seq_axes else None
        if seq_entry is not None and mesh is not None \
                and T_dim % _axis_size(mesh, seq_entry) != 0:
            seq_entry = None
        return (b, seq_entry, None, None)

    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        nd = x.ndim
        if re.search(r"'(k_scale|v_scale)'", name) and nd >= 3:
            # int8-KV scales [..., B, T, Hkv] — shard like the cache minus D
            fake = jax.ShapeDtypeStruct(x.shape + (1,), x.dtype)
            spec = _kv_spec(fake)[:-1]
        elif re.search(r"(shared_k|shared_v|'k'|'v'|xk|xv)", name) and nd >= 4:
            # [..., B, T, Hkv, D]
            spec = _kv_spec(x)
        elif re.search(r"'h'", name) and nd >= 4:        # mamba [.., B,H,N,P]
            spec = (b, h, None, None)
        elif re.search(r"'S'", name) and nd >= 4:        # rwkv  [.., B,H,K,V]
            spec = (b, h, None, None)
        elif re.search(r"'conv'", name):                 # [.., B, 3, C]
            spec = (b, None, m)
        elif re.search(r"'(xt|xc)'", name):              # [.., B, 1, d]
            spec = (b, None, None)
        else:
            spec = (None,) * nd
        pad = (None,) * (nd - len(spec))
        return P(*(pad + tuple(spec)))

    return jax.tree_util.tree_map_with_path(leaf, cache_sds)


# decode-cache leaves that hold per-head KV state: [.., B, T, H, D] tensors
# and their int8-KV [.., B, T, H] scale companions (exact key names — mamba
# "h" / rwkv "S" recurrent states must NOT match)
KV_CACHE_LEAVES = frozenset({"k", "v", "shared_k", "shared_v", "xk", "xv"})
KV_SCALE_LEAVES = frozenset({"k_scale", "v_scale"})
# MLA's per-token latent rows [.., B, T, r]: no head axis to shard
LATENT_CACHE_LEAVES = frozenset({"c_kv", "k_pe"})


def _leaf_key(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", ""))


def serving_cache_specs(cache_sds, data_axis: str | None,
                        model_axis: str | None):
    """PartitionSpec tree for the serving engine's decode cache.

    Every per-slot buffer splits its batch axis over ``data_axis`` (each
    data shard runs an independent slot pool).  When ``model_axis`` is given
    (head-sharded attention: ``n_heads`` and ``n_kv`` both divide the model
    axis), KV leaves additionally split their head axis over it, so the
    per-shard KV cache holds ``n_kv / tp`` heads.  Pass ``None`` for a
    size-1 axis — specs stay in the canonical (elided) form XLA hands back
    on computation outputs, preserving the no-retrace invariant.

    The SAME specs cover both cache layouts because they were designed to
    line up: dense KV leaves are ``[G, slots, T, H, D]`` and paged pools
    (``serve.paged``) are ``[G, num_pages, page_size, H, D]`` — dim 1 is
    the data-split axis either way (slots, or pool pages with shard-local
    page ids) and dim 3 is the head axis.  Page tables themselves are
    per-slot ``[slots, E]`` vectors and ride the engine's slot-state spec
    (``P(data)``), not this tree.
    """
    def leaf(path, x):
        key = _leaf_key(path)
        if model_axis is not None and x.ndim >= 5 \
                and key in KV_CACHE_LEAVES:
            return P(None, data_axis, None, model_axis)
        if model_axis is not None and x.ndim >= 4 \
                and key in KV_SCALE_LEAVES:
            return P(None, data_axis, None, model_axis)
        return P(None, data_axis) if data_axis is not None else P()

    return jax.tree_util.tree_map_with_path(leaf, cache_sds)


def serving_chunk_specs():
    """PartitionSpec tuple for the unified step's chunk-entry lane:
    ``(slot, tok, pos, first, budget_one)``, each ``[prefill_chunk]``.

    All five are REPLICATED.  The slot column carries GLOBAL row ids; each
    data shard's step impl matches them against its own
    ``arange(local_slots) + axis_index(data) * local_slots`` rows, so
    non-owning shards see all-False targets and run idempotent no-op
    iterations.  Splitting these vectors over the data axis instead would
    force the host to route entries per shard and break the fixed
    ``[prefill_chunk]`` dispatch shape."""
    return (P(), P(), P(), P(), P())


def batch_specs(batch_sds, rules: Rules):
    b = rules.get("batch")

    def leaf(path, x):
        return P(*((b,) + (None,) * (x.ndim - 1)))
    return jax.tree_util.tree_map_with_path(leaf, batch_sds)


def make_batch_sds(cfg, shape: configs.ShapeSpec) -> dict:
    B, S = shape.global_batch, shape.seq_len
    if getattr(cfg, "enc_dec", False):
        return {"frames": SDS((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16),
                "tokens": SDS((B, S), jnp.int32),
                "labels": SDS((B, S), jnp.int32)}
    batch = {"tokens": SDS((B, S), jnp.int32), "labels": SDS((B, S), jnp.int32)}
    if cfg.family == "vlm":
        batch["embeddings"] = SDS((B, S, cfg.d_model), jnp.bfloat16)
        batch["mrope_positions"] = SDS((B, S, 3), jnp.int32)
    return batch


# microbatch accumulation per arch for train_4k: chosen so the remat residual
# footprint (B_mb x S x d x 2 bytes x n_groups) stays well under HBM
TRAIN_MICROBATCHES = {
    "mixtral-8x22b": 16, "qwen2-vl-72b": 16, "phi3-medium-14b": 8,
    "qwen2-7b": 8, "zamba2-2.7b": 4, "gemma2-2b": 4, "minicpm-2b": 4,
    "rwkv6-1.6b": 4, "qwen2-moe-a2.7b": 4, "whisper-large-v3": 4,
}


def build_cell(arch: str, shape_name: str, mesh: Mesh, rules: Rules,
               train_cfg: TrainConfig | None = None,
               quant: str = "none", unroll: bool = True,
               cfg_overrides: dict | None = None):
    """Returns dict(fn, args_sds, in_shardings, out_shardings, cfg).

    ``cfg_overrides`` keys are split between ModelConfig and TrainConfig
    fields (hillclimbing plumbing: ``--set bf16_params=true`` etc.).
    """
    cfg = configs.get_config(arch, quant=quant)
    import dataclasses as _dc
    over = {"unroll_groups": unroll}
    if cfg_overrides:
        over.update(cfg_overrides)
    tc_fields = {f.name for f in _dc.fields(TrainConfig)}
    tc_over = {k: v for k, v in over.items() if k in tc_fields}
    over = {k: v for k, v in over.items()
            if k in {f.name for f in _dc.fields(cfg)}}
    cfg = _dc.replace(cfg, **over)
    shape = configs.SHAPES[shape_name]
    ok, reason = configs.shape_applicable(cfg, shape)
    if not ok:
        return {"skip": reason, "cfg": cfg}
    is_encdec = getattr(cfg, "enc_dec", False)
    init_fn = encdec.init_params if is_encdec else transformer.init_params
    params_sds = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0), cfg))
    pspecs = partitioning.param_specs(params_sds, rules)

    if shape.kind == "train":
        import dataclasses as _dc2
        tcfg = train_cfg or TrainConfig(
            n_microbatches=TRAIN_MICROBATCHES.get(arch, 4))
        if tc_over:
            tcfg = _dc2.replace(tcfg, **tc_over)
        if tcfg.n_microbatches > shape.global_batch:   # smoke/tiny shapes
            tcfg = _dc2.replace(tcfg, n_microbatches=max(
                1, shape.global_batch))
        step_fn = make_train_step(cfg, tcfg)
        state_sds = jax.eval_shape(
            lambda p: init_state(p, tcfg.bf16_params), params_sds)
        sspecs = validate_specs(state_sds,
                                partitioning.state_specs(state_sds, rules),
                                mesh)
        batch_sds = make_batch_sds(cfg, shape)
        bspecs = validate_specs(batch_sds, batch_specs(batch_sds, rules), mesh)
        metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
        return {
            "fn": step_fn,
            "args_sds": (state_sds, batch_sds),
            "in_shardings": (_named(mesh, sspecs), _named(mesh, bspecs)),
            "out_shardings": (_named(mesh, sspecs), _named(mesh, metrics_spec)),
            "cfg": cfg, "kind": "train",
        }

    # inference cells use bf16 params; quantized serving stores integer
    # weight codes + fp32 scales (serve/quantize.py — the paper's technique)
    params_sds = jax.tree_util.tree_map(
        lambda s: SDS(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 and s.ndim >= 1 else s, params_sds)
    if quant != "none":
        from repro.serve.quantize import quantize_params_for_serving
        params_sds = jax.eval_shape(
            lambda p: quantize_params_for_serving(p, mode=quant), params_sds)
    pspecs = partitioning.param_specs(params_sds, rules)
    serve_cfg = cfg

    if shape.kind == "prefill":
        B, S = shape.global_batch, shape.seq_len
        if is_encdec:
            def fn(params, frames, tokens):
                return encdec.prefill(params, serve_cfg, frames, tokens)
            args = (params_sds, SDS((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16),
                    SDS((B, S), jnp.int32))
            arg_specs = (pspecs, P(rules.get("batch"), None, None),
                         P(rules.get("batch"), None))
        elif cfg.family == "vlm":
            def fn(params, embeddings, mrope_positions):
                return transformer.prefill(params, serve_cfg, None,
                                           embeddings=embeddings,
                                           mrope_positions=mrope_positions)
            args = (params_sds, SDS((B, S, cfg.d_model), jnp.bfloat16),
                    SDS((B, S, 3), jnp.int32))
            arg_specs = (pspecs, P(rules.get("batch"), None, None),
                         P(rules.get("batch"), None, None))
        else:
            def fn(params, tokens):
                return transformer.prefill(params, serve_cfg, tokens)
            args = (params_sds, SDS((B, S), jnp.int32))
            arg_specs = (pspecs, P(rules.get("batch"), None))
        out_sds = jax.eval_shape(fn, *args)
        logits_spec = validate_specs(
            out_sds[0], P(rules.get("batch"), rules.get("vocab")), mesh)
        cspecs = validate_specs(out_sds[1],
                                cache_specs(out_sds[1], rules, mesh),
                                mesh)
        arg_specs = tuple(validate_specs(a, s, mesh)
                          for a, s in zip(args, arg_specs))
        return {
            "fn": fn, "args_sds": args,
            "in_shardings": tuple(_named(mesh, s) for s in arg_specs),
            "out_shardings": (_named(mesh, logits_spec), _named(mesh, cspecs)),
            "cfg": cfg, "kind": "prefill",
        }

    # decode: one token with a cache of seq_len
    B, S = shape.global_batch, shape.seq_len
    if is_encdec:
        cache_sds = jax.eval_shape(
            lambda: encdec.init_cache(serve_cfg, B, S))
        def fn(params, token, cache, pos):
            return encdec.decode_step(params, serve_cfg, token, cache, pos)
    else:
        cache_sds = jax.eval_shape(
            lambda: transformer.init_cache(serve_cfg, B, S))
        def fn(params, token, cache, pos):
            return transformer.decode_step(params, serve_cfg, token, cache, pos)
    cspecs = validate_specs(cache_sds,
                            cache_specs(cache_sds, rules, mesh), mesh)
    args = (params_sds, SDS((B,), jnp.int32), cache_sds, SDS((), jnp.int32))
    arg_specs = (validate_specs(params_sds, pspecs, mesh),
                 validate_specs(args[1], P(rules.get("batch")), mesh),
                 cspecs, P())
    logits_spec = validate_specs(SDS((B, cfg.vocab), jnp.float32),
                                 P(rules.get("batch"), rules.get("vocab")),
                                 mesh)
    return {
        "fn": fn, "args_sds": args,
        "in_shardings": tuple(_named(mesh, s) for s in arg_specs),
        "out_shardings": (_named(mesh, logits_spec), _named(mesh, cspecs)),
        "cfg": cfg, "kind": "decode",
    }
