"""Multi-device serving engine: tensor-parallel LUT matmuls over a ``model``
mesh axis x a data-parallel slot pool over a ``data`` axis.

The LUTMUL scale-out argument — beat the roofline by fanning multiplication
across many cheap units instead of speeding one up — applied at the device
level: every quantized projection's integer codes are split across the
``model`` axis (column-parallel N split with an all-gather, row-parallel K
split with an exact int32 psum; see ``dist.tp``), while the serving state
(decode slots, per-slot positions, KV/ring caches, sampling vectors, RNG
streams) is split across the ``data`` axis so each data shard runs an
independent slot pool under ONE host-side ``serve.scheduler.Scheduler``.

``ShardedEngine`` reuses ``Engine``'s admission/decode *implementations*
unchanged — it only overrides how they are compiled: the bodies run under
``shard_map`` with an active ``tp_context``, so the same model code that is
the single-device engine becomes the per-shard program.  Because every
sharded reduction is either exact (int32 psum) or a reordering-free gather,
temperature-0 output is bit-identical to the single-device engine.

Runs anywhere ``jax.devices()`` offers enough devices — including CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI recipe).
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import tp as tp_lib
from repro.launch.specs import serving_cache_specs, serving_chunk_specs
from repro.serve import engine as engine_lib
from repro.serve.engine import Engine, ServeConfig
from repro.serve.quantize import quantize_params_for_serving


class ShardedEngine(Engine):
    """Drop-in ``Engine`` for the scheduler, executing on a (data, model)
    mesh.  ``slots`` handed to ``Scheduler``/``init_cache`` must be divisible
    by the data-axis size; quantized serving codes are required (only
    integer-code matmuls shard bit-exactly — see ``dist.tp``)."""

    def __init__(self, cfg, params, scfg: ServeConfig = ServeConfig(), *,
                 mesh: Mesh, data_axis: str = "data",
                 model_axis: str = "model"):
        if getattr(cfg, "enc_dec", False):
            raise NotImplementedError(
                "sharded serving covers decoder-only LMs")
        if not scfg.quant:
            raise ValueError(
                "ShardedEngine requires ServeConfig(quant=...): only integer "
                "weight codes shard bit-exactly (int32 psum is associative; "
                "a float row-parallel reduction would drift)")
        self.mesh = mesh
        self.data_axis, self.model_axis = data_axis, model_axis
        self.n_data = mesh.shape[data_axis]
        self.n_model = mesh.shape[model_axis]
        # quantize + mark BEFORE Engine.__init__: _build_admit_fn (called by
        # the base ctor) closes over the param/cache specs.  head_dim lets
        # the marker go head-parallel on attention groups (QKV stay local,
        # attention runs on n_heads/tp heads per shard) when the head counts
        # divide the model axis; the KV cache layout below keys off whether
        # that actually happened.
        params = quantize_params_for_serving(params, mode=scfg.quant,
                                             bits_plan=scfg.bits_plan)
        params, self._param_specs, self.n_tp_leaves = tp_lib.mark_tp_params(
            params, self.n_model, model_axis, head_dim=cfg.head_dim)
        n_attn, n_head_marked = tp_lib.attn_group_counts(params)
        if n_head_marked not in (0, n_attn):
            # the KV-cache layout below is one global choice: a tree where
            # only SOME attention groups went head-parallel (heterogeneous
            # per-layer head counts) cannot be cached consistently
            raise ValueError(
                f"head marking must be all-or-nothing across attention "
                f"groups, got {n_head_marked}/{n_attn}")
        self.head_sharded = n_head_marked > 0
        # canonical specs (no trailing Nones, size-1 axes elided) — exactly
        # the form XLA hands back on computation outputs, so round-tripped
        # slot state / caches never change the executors' cache signature
        self._dspec = P(data_axis) if self.n_data > 1 else P()
        # the struct covers BOTH layouts: dense [G, slots, T, H, D] rows and
        # paged [G, pages, page_size, H, D] pools put their data-split axis
        # (slots / pages) at dim 1 and their head axis at dim 3, so one spec
        # tree serves either
        self._cache_specs = serving_cache_specs(
            engine_lib.cache_struct(cfg, scfg, self.n_data, self.n_data),
            data_axis if self.n_data > 1 else None,
            model_axis if self.head_sharded else None)
        # paged serving: the pool page axis splits over the data axis —
        # each data shard runs an independent allocator + prefix registry
        # over shard-local page ids
        super().__init__(cfg, params,
                         dataclasses.replace(scfg, quant=None),
                         n_page_shards=self.n_data)
        self.scfg = scfg                     # keep the quant label visible
        self.params = jax.device_put(
            self.params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), self._param_specs))

    # -- shard_map-compiled executors ---------------------------------------

    def _shard_jit(self, impl, in_specs, out_specs):
        def body(*args):
            with tp_lib.tp_context(self.model_axis, self.n_model,
                                   self.data_axis):
                return impl(*args)
        # explicit in_shardings keep argument placement out of the jit cache
        # key: committed outputs fed back next round (whose specs XLA may
        # have normalized, e.g. P("data") -> P() on a size-1 axis) reshard
        # instead of retracing — the no-retrace-after-warmup invariant
        return jax.jit(
            jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            in_shardings=jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), in_specs),
            donate_argnums=1,
            compiler_options=engine_lib.STEP_COMPILER_OPTIONS)

    def _build_admit_fn(self):
        d = self._dspec
        in_specs = (self._param_specs, self._cache_specs,
                    d,                              # prompts [run, exact len]
                    d, d, d,                        # lengths, mask, budget_one
                    d, d, d, d,                     # eos, temp, top_k, top_p
                    d, d, d,                        # tok, pos, done
                    P(), P())                       # key, step0
        if self.scfg.paged:
            # page tables + start_tok split with the slots they describe
            # (table VALUES are shard-local page ids)
            in_specs += (d, d, d)
        out_specs = (self._cache_specs, d, d, d, d, d,
                     d)                              # ok0 finite-logits guard
        return self._shard_jit(self._admit_impl, in_specs, out_specs)

    def _build_step_fn(self, C: int, chunk: int, greedy: bool,
                       spec: bool = False):
        d = self._dspec
        in_specs = (self._param_specs, self._cache_specs,
                    *serving_chunk_specs(),         # slot, tok, pos, first, b1
                    d, d, d,                        # tok, pos, done
                    d, d, d, d,                     # eos, temp, top_k, top_p
                    P(), P())                       # key, step0
        if self.scfg.paged:
            in_specs += (d, d)                      # full + ring page tables
        out_specs = (self._cache_specs, d, d, d,
                     d, d,                # first tokens/dones [slots]
                     d, d,                # decode tokens/dones [slots, W]
                     d,                   # ok finite-logits guard
                     d,                   # n_valid accepted-width [slots]
                     P())                 # MoE counters, summed over data
        return self._shard_jit(self._make_step_impl(C, chunk, greedy, spec),
                               in_specs, out_specs)

    # -- scheduler-facing API ------------------------------------------------

    def init_cache(self, batch: int):
        if batch % self.n_data:
            raise ValueError(
                f"slots ({batch}) must be divisible by the data-axis size "
                f"({self.n_data}) — each data shard runs batch/{self.n_data} "
                "independent decode lanes")
        return jax.device_put(
            super().init_cache(batch), jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), self._cache_specs))

    def place_slot_state(self, x):
        return jax.device_put(x, NamedSharding(self.mesh, self._dspec))

    def place_cache(self, cache):
        """Re-pin a host-restored cache tree onto the canonical cache
        shardings (restores never change the executors' input signature)."""
        return jax.device_put(cache, jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self._cache_specs))

    def serving_state_shardings(self):
        dsh = NamedSharding(self.mesh, self._dspec)
        return {"cache": jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), self._cache_specs),
                "tok": dsh, "pos": dsh, "done": dsh}

    def kv_cache_bytes(self, batch: int) -> int:
        """PER-SHARD bytes of the attention KV leaves: the data axis splits
        the ``batch`` slots and — when head-sharded — the model axis splits
        the KV heads, so the figure shrinks by ``n_data * n_model`` on
        divisible configs (vs ``n_data`` alone with replicated heads).

        Paged engines report per-shard *allocated residency* instead: the
        busiest shard's peak in-use pages times the per-shard page
        footprint (pages hold ``n_kv / n_model`` local heads when
        head-sharded)."""
        from repro.launch.specs import (KV_CACHE_LEAVES, KV_SCALE_LEAVES,
                                        _leaf_key)
        if self.paged and self.pool is not None:
            per_page = self.page_bytes(batch)
            if self.head_sharded:
                per_page //= self.n_model
            return self.pool.peak_pages_per_shard * per_page
        names = KV_CACHE_LEAVES | KV_SCALE_LEAVES
        sds = self._cache_sds(batch)
        # the engine's live specs are batch-independent (same leaf names and
        # ranks for any slot count) — reusing them keeps this report and the
        # actual executor sharding from ever diverging
        specs = self._cache_specs
        total = 0
        for (path, leaf), spec in zip(
                jax.tree_util.tree_flatten_with_path(sds)[0],
                jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(x, P))):
            if _leaf_key(path) not in names:
                continue
            div = 1
            for entry in spec:
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, tuple) else (entry,)):
                    div *= self.mesh.shape[ax]
            total += leaf.size * leaf.dtype.itemsize // div
        return total

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "ShardedEngine serves through serve.scheduler.Scheduler "
            "(the unified step / admit_monolithic); use the single-device "
            "Engine for the static-batch generate() oracle")
