"""Batched serving engine: the unified batch-step executor under the
scheduler.

Two entry paths share the same compiled decode graph:

  * ``generate`` — static batch: all sequences prefill together and advance
    in lock-step (the legacy demo path, kept as the bit-exactness oracle for
    the scheduler).
  * the continuous-batching path driven by ``serve.scheduler.Scheduler`` —
    ONE compiled ``step`` per round that carries ``prefill_chunk`` prompt
    tokens (a scan of masked single-token iterations targeting the slots
    being admitted, sampling a request's first output token the moment its
    last prompt token lands) followed by ``chunk`` decode iterations over
    every slot.  Prefill and decode share the round, so admission never
    stalls decoding and padding waste stays ~1.0.  Models whose prompt
    state cannot be built a token at a time (recurrent layers, MoE routing,
    int8-KV, SWA prompts longer than the window) fall back to
    ``admit_monolithic`` — a batched full-KV prefill stitched into the
    masked slots of the live buffers — and then take pure-decode ``step``
    rounds.

Positions are per-sequence (``pos: [B]`` int32) everywhere in decode; a
negative position is the free-slot sentinel — the attention mask drops every
key of that row, and its cache writes land inside its own (free) row.
Mid-prefill rows park with ``done=True`` holding their next unprocessed
(token, position): every iteration that does not target them re-runs that
write, which is idempotent (same inputs, same bits).  Sampling is on-device
with per-slot temperature / top-k / top-p and a fold-in PRNG (key folded
with the global step index), so a round of tokens needs exactly one host
round-trip.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import encdec, transformer
from repro.serve.faults import CacheCorruption

NEG_INF = -1e30
# Compile options of every served program.  With XLA's excess precision on,
# a bf16 value may stay in f32 wherever fusion allows, while the Pallas
# kernels' fused epilogue always rounds; where a value rounds then depends on
# the kernel backend and the mesh, a 1-ulp difference flips int4 activation
# codes, and the same params give different tokens.  Off, every program
# rounds where the jaxpr says.
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0
    top_k: int = 0                # 0 disables top-k filtering
    top_p: float = 1.0            # 1.0 disables nucleus filtering
    seed: int = 0
    quant: Optional[str] = None   # convert weights to serving codes at load
    # optional per-leaf mixed bit widths: {param path -> mode string}, the
    # output of roofline.analysis.plan_mixed_bits (keys match the
    # serve.quantize walk paths); leaves not in the plan follow `quant`
    bits_plan: Optional[dict] = None
    # paged KV cache (serve.paged): per-layer page pools + per-slot page
    # tables instead of dense [slots, max_len] buffers
    paged: bool = False
    page_size: int = 4            # tokens per page; must divide max_len
                                  # (and the SWA ring length)
    num_pages: int = 0            # total pool pages incl. per-shard null
                                  # pages; 0 = worst-case auto-size
    prefix_reuse: bool = True     # share identical prompt-prefix pages
    # prompt tokens processed per unified round (the chunked-prefill
    # budget); must be a multiple of page_size on paged engines so chunk
    # boundaries align with page boundaries.  None = auto (2 pages when
    # paged, 8 tokens dense)
    prefill_chunk: Optional[int] = None
    # invariant guards (serve.faults): audit the page pool before every
    # dispatch and have the scheduler act on the finite-logits flags the
    # compiled executors always report (the flags cost one cheap on-device
    # reduction either way; this gates the host-side checks/raises)
    guards: bool = True
    # bitplane-truncated self-speculative decoding: draft ``draft_k`` tokens
    # per round with the top-``draft_planes``-plane view of the tmac weight
    # codes (zero extra weight memory — the draft shares the target's packed
    # planes), verify them in ONE batched (draft_k+1)-token target forward,
    # accept the longest matching prefix.  Transcripts are bit-identical to
    # the non-speculative engine at temperature 0; at temperature > 0 every
    # emitted token is still sampled from the exact target conditional.
    spec_decode: bool = False
    draft_planes: int = 2         # top planes the drafter keeps (>= 2)
    draft_k: int = 3              # tokens drafted per verify round

    def __post_init__(self):
        """Validate serving invariants at construction — a bad geometry
        should fail here with an actionable message, not deep inside the
        first compiled dispatch."""
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.paged and self.max_len % self.page_size:
            raise ValueError(
                f"page_size ({self.page_size}) must divide max_len "
                f"({self.max_len}) — pick a power-of-two page size or pad "
                f"max_len up to a multiple")
        if self.num_pages < 0:
            raise ValueError(f"num_pages must be >= 0 (0 = auto-size), got "
                             f"{self.num_pages}")
        if self.prefill_chunk is not None:
            if self.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{self.prefill_chunk}")
            if self.prefill_chunk > self.max_len:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) cannot exceed "
                    f"max_len ({self.max_len}) — no prompt is longer")
            if self.paged and self.prefill_chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"multiple of page_size ({self.page_size}) so chunk "
                    f"boundaries align with page boundaries")
        if self.spec_decode:
            if self.draft_k < 1:
                raise ValueError(
                    f"draft_k must be >= 1, got {self.draft_k}")
            if self.draft_planes < 2:
                raise ValueError(
                    f"draft_planes must be >= 2 (the drafter keeps the sign "
                    f"plane plus at least one magnitude plane), got "
                    f"{self.draft_planes}")
            if self.draft_k + 1 > self.max_len:
                raise ValueError(
                    f"draft_k ({self.draft_k}) needs max_len >= draft_k + 1 "
                    f"({self.draft_k + 1}), got {self.max_len}")

    @property
    def chunk_tokens(self) -> int:
        """The resolved prefill chunk budget (auto when unset)."""
        if self.prefill_chunk is not None:
            return self.prefill_chunk
        return 2 * self.page_size if self.paged else 8


def sample_logits(logits: jax.Array, key, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Per-row sampling: argmax where temperature <= 0 (exact greedy),
    otherwise temperature softmax restricted by top-k and/or top-p.

    logits: [B, V] float; temperature/top_k/top_p: scalars or [B].  Python
    scalars short-circuit: all-greedy skips everything but the argmax, and
    unfiltered sampling skips the vocab sort — the general (traced-vector)
    path computes both and selects per row.
    """
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    static = all(isinstance(x, (int, float))
                 for x in (temperature, top_k, top_p))
    if static and temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if static and top_k == 0 and top_p >= 1.0:
        return jax.random.categorical(
            key, logits / max(temperature, 1e-6), axis=-1).astype(jnp.int32)
    temperature = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(temperature, jnp.float32)), (B,))
    top_k = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(top_k, jnp.int32)),
                             (B,))
    top_p = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(top_p, jnp.float32)),
                             (B,))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sorted_l = -jnp.sort(-logits, axis=-1)               # descending
    kth = jnp.take_along_axis(sorted_l, (jnp.clip(top_k, 1, V) - 1)[:, None],
                              axis=-1)
    keep = jnp.where((top_k > 0)[:, None], logits >= kth, True)
    t = jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(sorted_l / t, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # nucleus: smallest prefix whose mass reaches top_p (first token always in)
    n_keep = jnp.maximum(jnp.sum((csum - probs) < top_p[:, None], axis=-1), 1)
    cutoff = jnp.take_along_axis(sorted_l, (n_keep - 1)[:, None], axis=-1)
    keep &= jnp.where((top_p < 1.0)[:, None], logits >= cutoff, True)
    sampled = jax.random.categorical(
        key, jnp.where(keep, logits, NEG_INF) / t, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _write_rows(live: jax.Array, part: jax.Array,
                mask: jax.Array) -> jax.Array:
    """Masked multi-slot write: replace batch rows where ``mask`` is set.

    live: [G, B, ...]; part: [G, B, ...] with a possibly shorter time axis
    (axis 2, P <= M) — only the leading P time slots of masked rows are
    written (the tail stays masked by the position sentinel until decode
    overwrites it).  mask: [B] bool.  One static-shape op for the whole
    admission round, regardless of how many slots fill.
    """
    m = mask.reshape((1, -1) + (1,) * (live.ndim - 2))
    if live.ndim >= 3 and part.shape[2] < live.shape[2]:
        P = part.shape[2]
        head = jnp.where(m, part.astype(live.dtype), live[:, :, :P])
        return live.at[:, :, :P].set(head)
    return jnp.where(m, part.astype(live.dtype), live)


def _ring_positions(lengths: jax.Array, T: int) -> jax.Array:
    """[B, T] absolute position held by each ring slot after stitching a
    ``lengths``-token prompt (negative = slot empty) — the addressing
    ``_ring_from_full`` and the paged ring scatter share."""
    i = jnp.arange(T)[None]                       # [1, T]
    L = lengths[:, None]                          # [B, 1]
    return (L - 1) - ((L - 1 - i) % T)            # [B, T]


def _ring_from_full(kv_full: jax.Array, lengths: jax.Array,
                    T: int) -> jax.Array:
    """Arrange full-length K/V [G, B, P, H, D] into per-row T-slot rings
    where slot i holds the token with the largest position p < lengths[b],
    p % T == i — exactly ``decode_attention``'s rolling addressing.  Slots
    with no valid token (length < T) are zeroed; their positions stay
    masked."""
    P = kv_full.shape[2]
    p = _ring_positions(lengths, T)               # [B, T]
    vals = jnp.take_along_axis(
        kv_full, jnp.clip(p, 0, P - 1)[None, :, :, None, None], axis=2)
    return jnp.where((p >= 0)[None, :, :, None, None], vals,
                     jnp.zeros((), kv_full.dtype))


def _scatter_pages(pool: jax.Array, table: jax.Array, piece: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Stitch-time page scatter: write token rows of ``piece`` into the
    pages their table rows name.

    pool: [G, P, ps, ...]; table: [B, E]; piece: [G, B, L, ...] (L <= E*ps);
    valid: [B, L] bool.  Invalid entries (unadmitted slots, pad tokens,
    prefix-shared tokens) are routed to the reserved null page 0, so one
    static-shape scatter covers the whole admission round; valid entries
    target exclusively-owned pages, so duplicate indices only ever land on
    the null page.
    """
    ps = pool.shape[2]
    B, L = valid.shape
    t = jnp.arange(L)
    page = jnp.where(valid, table[:, t // ps], 0)          # [B, L]
    off = jnp.broadcast_to(t % ps, (B, L))
    vals = piece.reshape((piece.shape[0], B * L) + piece.shape[3:])
    return pool.at[:, page.reshape(-1), off.reshape(-1)].set(
        vals.astype(pool.dtype))


_FLOAT_KV_KEYS = ("k", "v", "shared_k", "shared_v", "k_scale", "v_scale")


def _cache_finite(cache) -> jax.Array:
    """Scalar AND of ``isfinite`` over every floating-dtype attention cache
    leaf.  The finite-logits guard alone cannot see KV corruption on
    integer-code matmul paths (casting a NaN activation to int codes yields
    finite garbage), so decode also audits the cache itself once per chunk.
    Int leaves (quantized KV codes, page tables) are finite by construction
    and skipped."""
    layers = cache if isinstance(cache, (list, tuple)) else [cache]
    ok = jnp.bool_(True)
    for layer in layers:
        if not isinstance(layer, dict):
            continue
        for key in _FLOAT_KV_KEYS:
            leaf = layer.get(key)
            if leaf is not None and jnp.issubdtype(leaf.dtype, jnp.floating):
                ok = ok & jnp.isfinite(leaf).all()
    return ok


def paged_layout(cfg, scfg: ServeConfig):
    """The engine's page geometry (validated against cfg/scfg)."""
    from repro.serve.paged import PagedLayout
    return PagedLayout.build(cfg, scfg.max_len, scfg.page_size)


def resolve_pages_per_shard(cfg, scfg: ServeConfig, batch: int,
                            n_shards: int) -> int:
    """Pool pages per data shard: ``scfg.num_pages / n_shards`` when set
    (must divide), else the exhaustion-free worst case for ``batch`` slots."""
    lay = paged_layout(cfg, scfg)
    if scfg.num_pages:
        if scfg.num_pages % n_shards:
            raise ValueError(f"num_pages ({scfg.num_pages}) must divide "
                             f"over the data axis ({n_shards})")
        return scfg.num_pages // n_shards
    if batch % n_shards:
        raise ValueError(f"slots ({batch}) must divide over the data axis "
                         f"({n_shards})")
    return lay.auto_pages_per_shard(batch // n_shards)


def cache_struct(cfg, scfg: ServeConfig, batch: int, n_shards: int = 1):
    """ShapeDtypeStructs of the decode cache — dense per-slot buffers, or
    page pools + dense recurrent state when ``scfg.paged``."""
    from repro.models import encdec as _encdec
    from repro.models import transformer as _transformer
    mod = _encdec if getattr(cfg, "enc_dec", False) else _transformer
    if not scfg.paged:
        return jax.eval_shape(
            lambda: mod.init_cache(cfg, batch, scfg.max_len))
    total = resolve_pages_per_shard(cfg, scfg, batch, n_shards) * n_shards
    return jax.eval_shape(
        lambda: mod.init_paged_cache(cfg, batch, scfg.max_len, total,
                                     scfg.page_size))


class Engine:
    def __init__(self, cfg, params, scfg: ServeConfig = ServeConfig(), *,
                 n_page_shards: int = 1):
        self.cfg = cfg
        if scfg.quant:
            # quantize + pack weight codes ONCE at engine construction (the
            # weight-code cache); every decode step then reads integer codes
            from repro.serve.quantize import quantize_params_for_serving
            params = quantize_params_for_serving(params, mode=scfg.quant,
                                                 bits_plan=scfg.bits_plan)
        self.params = params
        self.scfg = scfg
        self.is_encdec = getattr(cfg, "enc_dec", False)
        # paged serving state (serve.paged): geometry validated up front,
        # the PagePool itself is created by init_cache (it needs the slot
        # count).  n_page_shards = 1 single-device; the sharded engine
        # passes its data-axis size to split the pool page axis (and the
        # slots) over the data mesh axis.
        self.pool = None
        self.n_page_shards = n_page_shards
        if scfg.paged:
            if self.is_encdec:
                raise NotImplementedError(
                    "paged serving drives decoder-only LMs through the "
                    "scheduler; enc-dec decode supports page tables at the "
                    "encdec.decode_step level only")
            paged_layout(cfg, scfg)          # raises on bad page geometry
            if scfg.num_pages and scfg.num_pages // n_page_shards < 2:
                raise ValueError(
                    f"num_pages ({scfg.num_pages}) leaves no usable pages: "
                    f"each of the {n_page_shards} shard(s) reserves page 0 "
                    f"as the null page — give every shard at least 2 pages")
        mod = encdec if self.is_encdec else transformer
        self._mod = mod
        self._prefill = jax.jit(lambda p, *a: mod.prefill(p, cfg, *a),
                                compiler_options=STEP_COMPILER_OPTIONS)
        # donate the cache: decode updates it in place (halves residency)
        self._decode = jax.jit(lambda p, t, c, pos: mod.decode_step(
            p, cfg, t, c, pos), donate_argnums=2,
            compiler_options=STEP_COMPILER_OPTIONS)
        self._admit_fn = self._build_admit_fn()
        self._step_fns: dict[tuple, callable] = {}
        # fault injection (serve.faults): a FaultPlan applied at the two
        # dispatch sites; None in production
        self.faults = None
        # attention KV tolerates right-padded prompt buckets (pad keys stay
        # position-masked until decode overwrites them); SSM/RWKV recurrent
        # states do NOT — the recurrence integrates pad embeddings — so the
        # scheduler must prefill those models at exact prompt length
        self.has_recurrent_state = (not self.is_encdec and any(
            spec.kind != "attn" for spec in cfg.pattern))
        # speculative decoding eligibility: the draft/verify round needs
        # token-at-a-time state (same precondition as the chunk lane), no
        # SWA rings (a K+1-token block write would wrap them), and tmac
        # leaves wide enough to truncate.  Fail at construction, not inside
        # the first compiled spec round.
        self.n_draftable_leaves = 0
        if scfg.spec_decode:
            if self.requires_monolithic_admission:
                raise ValueError(
                    "spec_decode needs prompt/decode state that builds one "
                    "token at a time — recurrent layers, MoE routing, "
                    "int8-KV and enc-dec models cannot run draft/verify "
                    "rounds")
            if self.chunk_window_limit is not None:
                raise ValueError(
                    "spec_decode does not support sliding-window attention: "
                    "a draft_k+1-token speculative block would wrap the "
                    "window ring before the verify pass could roll it back")
            if any(getattr(spec, "shared_attn", False)
                   for spec in getattr(cfg, "pattern", ())):
                raise ValueError(
                    "spec_decode does not support shared-attention patterns")
            from repro.serve.quantize import count_draftable_leaves
            self.n_draftable_leaves = count_draftable_leaves(
                self.params, scfg.draft_planes)
            if self.n_draftable_leaves == 0:
                raise ValueError(
                    f"spec_decode found no draftable weight leaves: the "
                    f"drafter truncates tmac bitplane stacks wider than "
                    f"draft_planes={scfg.draft_planes} — quantize with a "
                    f"w3/w4 tmac mode (e.g. quant='w4a4_tmac')")

    # -- compiled-executor construction (ShardedEngine overrides these with
    #    shard_map-wrapped variants; the impls themselves are shared) --------

    def _build_admit_fn(self):
        return jax.jit(self._admit_impl, donate_argnums=1,
                       compiler_options=STEP_COMPILER_OPTIONS)

    def _build_step_fn(self, C: int, chunk: int, greedy: bool,
                       spec: bool = False):
        return jax.jit(self._make_step_impl(C, chunk, greedy, spec),
                       donate_argnums=1,
                       compiler_options=STEP_COMPILER_OPTIONS)

    # -- scheduler-facing API ------------------------------------------------

    @property
    def paged(self) -> bool:
        return bool(self.scfg.paged)

    @property
    def prefill_chunk(self) -> int:
        """Prompt tokens carried by the chunk lane of one unified round."""
        return self.scfg.chunk_tokens

    @property
    def requires_monolithic_admission(self) -> bool:
        """True when prompt state cannot be built one token at a time and
        the scheduler must admit through the batched-prefill fallback:

        * recurrent layers (SSM/RWKV) — the recurrence must integrate the
          exact prompt, and prefill's associative scan does not decompose
          into per-token decode steps bit-identically;
        * MoE routing — grouped dispatch capacity is a function of the
          batched prompt length, so chunked routing takes different
          drop/keep decisions than the prefill the oracle uses;
        * int8-KV — prefill quantizes K/V per prompt tile; requantizing a
          token at a time would change the stored codes.
        """
        if self.is_encdec or self.has_recurrent_state:
            return True
        if getattr(self.cfg, "kv_quant", "none") == "int8":
            return True
        return any(getattr(spec, "mlp", None) == "moe"
                   for spec in getattr(self.cfg, "pattern", ()))

    @property
    def chunk_window_limit(self) -> Optional[int]:
        """Longest sequence the chunk lane may admit on SWA models (the
        window): a ring-buffered prompt longer than the window reads its
        keys in ring order during chunked admission but in chronological
        order during the oracle's prefill, and the float reduction order
        differs at the last ulp.  None = no local-attention layers."""
        pattern = getattr(self.cfg, "pattern", ())
        if getattr(self.cfg, "window", 0) and any(
                spec.kind == "attn" and spec.attn_type == "local"
                for spec in pattern):
            return int(self.cfg.window)
        return None

    def chunk_eligible(self, seq_len: int) -> bool:
        """Can a ``seq_len``-token prompt be admitted through the chunk
        lane (vs the monolithic fallback)?"""
        if self.requires_monolithic_admission:
            return False
        limit = self.chunk_window_limit
        return limit is None or seq_len <= limit

    def init_cache(self, batch: int):
        """Zero decode buffers for ``batch`` slots (static shapes).  Paged:
        page pools + dense recurrent state, plus a fresh host-side
        ``PagePool`` (allocator + page tables) under ``self.pool``."""
        if not self.paged:
            return self._mod.init_cache(self.cfg, batch, self.scfg.max_len)
        from repro.serve.paged import PagePool
        per_shard = resolve_pages_per_shard(self.cfg, self.scfg, batch,
                                            self.n_page_shards)
        self.pool = PagePool(batch, paged_layout(self.cfg, self.scfg),
                             pages_per_shard=per_shard,
                             n_shards=self.n_page_shards,
                             prefix_reuse=self.scfg.prefix_reuse)
        return self._mod.init_paged_cache(
            self.cfg, batch, self.scfg.max_len,
            per_shard * self.n_page_shards, self.scfg.page_size)

    def _cache_sds(self, batch: int):
        """ShapeDtypeStructs of the decode cache (no device allocation)."""
        return cache_struct(self.cfg, self.scfg, batch, self.n_page_shards)

    def _paged_admit_args(self):
        """Device snapshots of (full table, ring table, start_tok)."""
        place = self.place_slot_state
        return (place(jnp.asarray(self.pool.table)),
                place(jnp.asarray(self.pool.ring)),
                place(jnp.asarray(self.pool.start)))

    def _paged_decode_args(self):
        place = self.place_slot_state
        return (place(jnp.asarray(self.pool.table)),
                place(jnp.asarray(self.pool.ring)))

    def _kv_leaf_bytes(self, batch: int) -> int:
        from repro.launch.specs import (KV_CACHE_LEAVES, KV_SCALE_LEAVES,
                                        _leaf_key)
        names = KV_CACHE_LEAVES | KV_SCALE_LEAVES
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._cache_sds(batch))[0]:
            if _leaf_key(path) in names:
                total += leaf.size * leaf.dtype.itemsize
        return total

    def page_bytes(self, batch: int = 1) -> int:
        """Bytes ONE page occupies summed across every KV pool leaf (all
        groups and pattern positions)."""
        if not self.paged:
            raise ValueError("page_bytes is a paged-engine figure")
        per_shard = resolve_pages_per_shard(self.cfg, self.scfg, batch,
                                            self.n_page_shards)
        return self._kv_leaf_bytes(batch) // (per_shard * self.n_page_shards)

    def kv_cache_bytes(self, batch: int) -> int:
        """KV memory figure for the serving bench: bytes of the attention
        KV leaves (K/V + int8-KV scales + shared-attention K/V).

        Dense engines report ``max_len`` *capacity* — every slot owns a
        worst-case buffer.  Paged engines report *allocated residency*: the
        peak number of in-use pool pages times the page footprint (the pool
        backing store is larger, but untouched pages are reclaimable — the
        number that scales with the workload is the allocated one).  The
        sharded engine overrides this with the per-shard figure."""
        if self.paged and self.pool is not None:
            return self.pool.peak_pages * self.page_bytes(batch)
        return self._kv_leaf_bytes(batch)

    def place_slot_state(self, x: jax.Array) -> jax.Array:
        """Device placement for per-slot ``[slots]`` vectors (identity here;
        the sharded engine pins them to the data axis so the compiled
        executors see one stable input sharding from round one)."""
        return x

    def place_cache(self, cache):
        """Device placement for a (host-restored) decode cache tree
        (identity here; the sharded engine re-pins the canonical cache
        shardings so restored state never changes executor signatures)."""
        return jax.tree_util.tree_map(jnp.asarray, cache)

    def serving_state_shardings(self):
        """Shardings for the {"cache", "tok", "pos", "done"} serving-state
        tree a disk restore re-places (None = default placement; the
        sharded engine returns its canonical NamedSharding tree)."""
        return None

    # -- fault injection + invariant guards (serve.faults) -------------------

    def set_fault_plan(self, plan) -> None:
        """Install a ``FaultPlan`` applied at every dispatch (None clears)."""
        self.faults = plan

    def _fault_site(self, site: str, cache, pos):
        """Apply due injected faults, then audit the page pool so corrupted
        tables are caught host-side BEFORE they are snapshotted to device
        (where the scatter/gather would silently clamp them)."""
        if self.faults is not None:
            cache = self.faults.apply(site, self, cache, pos)
        if self.paged and self.scfg.guards and self.pool is not None:
            errs = self.pool.validate()
            if errs:
                raise CacheCorruption(
                    "page pool audit failed: " + "; ".join(errs[:3]))
        return cache

    def _stitch_impl(self, cache, pcache, lengths, mask, paged=()):
        """Cache-stitch-at-slot: write freshly prefilled rows into the masked
        batch slots of the live buffers.  pcache rows are slot-aligned: row b
        fills slot b where ``mask[b]``; other rows are untouched.  Static
        shapes throughout (lengths and mask are traced vectors).

        ``paged`` = (full_table, ring_table, start_tok): KV rows scatter
        into pool pages instead — full-length layers write tokens
        [start_tok, length) of masked rows through the full table (tokens
        below start_tok live in prefix-shared pages another admission
        already filled), SWA rings arrange the window from the true length
        and scatter through their exclusively-owned ring table.  Recurrent
        state stays a dense masked row write either way.
        """
        cfg = self.cfg
        table = ring_t = start = None
        if paged:
            table, ring_t, start = paged
        out = []
        for spec, live, part in zip(cfg.pattern, cache, pcache):
            c = dict(live)
            if spec.kind == "attn":
                is_local = spec.attn_type == "local" and bool(cfg.window)
                if paged:
                    Pb = part["k"].shape[2]
                    t = jnp.arange(Pb)[None]
                    if is_local:
                        Tr = ring_t.shape[1] * self.scfg.page_size
                        rv = mask[:, None] & (
                            _ring_positions(lengths, Tr) >= 0)
                    else:
                        valid = (mask[:, None] & (t >= start[:, None])
                                 & (t < lengths[:, None]))
                    for key in ("k", "v"):
                        piece = part[key]
                        if is_local:
                            piece = _ring_from_full(piece, lengths, Tr)
                            c[key] = _scatter_pages(live[key], ring_t,
                                                    piece, rv)
                        elif "k_scale" in live:      # int8 KV pool
                            q, s = attn_lib.quantize_kv(piece)
                            c[key] = _scatter_pages(live[key], table, q,
                                                    valid)
                            c[key + "_scale"] = _scatter_pages(
                                live[key + "_scale"], table, s, valid)
                        else:
                            c[key] = _scatter_pages(live[key], table,
                                                    piece, valid)
                else:
                    T = live["k"].shape[2]
                    for key in ("k", "v"):
                        piece = part[key]
                        if is_local:
                            piece = _ring_from_full(piece, lengths, T)
                        if "k_scale" in live:        # int8 KV live buffers
                            q, s = attn_lib.quantize_kv(piece)
                            c[key] = _write_rows(live[key], q, mask)
                            c[key + "_scale"] = _write_rows(
                                live[key + "_scale"], s, mask)
                        else:
                            c[key] = _write_rows(live[key], piece, mask)
            elif spec.kind == "mamba2":
                c["h"] = _write_rows(live["h"], part["h"], mask)
                c["conv"] = _write_rows(live["conv"], part["conv"], mask)
            elif spec.kind == "rwkv6":
                for key in ("S", "xt"):
                    c[key] = _write_rows(live[key], part[key], mask)
                if "xc" in live:
                    # prefill tracks the channel-mix state under "xc" only for
                    # rwkv_cm patterns; default to zeros otherwise
                    c["xc"] = _write_rows(live["xc"],
                                          part.get("xc",
                                                   jnp.zeros_like(live["xc"])),
                                          mask)
            for key in ("shared_k", "shared_v"):
                if key in live:
                    if paged:
                        valid = (mask[:, None]
                                 & (jnp.arange(part[key].shape[2])[None]
                                    >= start[:, None])
                                 & (jnp.arange(part[key].shape[2])[None]
                                    < lengths[:, None]))
                        c[key] = _scatter_pages(live[key], table, part[key],
                                                valid)
                    else:
                        c[key] = _write_rows(live[key], part[key], mask)
            out.append(c)
        return tuple(out)

    def admit_monolithic(self, cache, prompts, lengths, mask, budget_one,
                         eos, temperature, top_k, top_p, tok, pos, done,
                         step0: int):
        """Fallback admission as ONE dispatch: batched prefill of the
        admitted prompts, cache-stitch into the masked slots, first-token
        sampling, and the slot-state merge.  Used for models/requests
        ``chunk_eligible`` rejects (recurrent state, MoE routing, int8-KV,
        SWA prompts past the window); everything else admits through the
        chunk lane of :meth:`step`.

        prompts: [slots, P] int32 right-padded to the dispatch width (dummy
        rows for slots that stay empty); lengths/mask/budget_one: per-slot
        vectors (budget_one marks requests whose whole budget is the first
        token).  Returns (cache, tok, pos, done, tok0, done0, ok0) —
        tok0/done0 are the per-slot first tokens and immediately-finished
        flags the scheduler reads back for bookkeeping; ok0 is the per-slot
        finite-logits guard (False = the sampled row's logits were
        non-finite, i.e. poisoned state).  Compiles once per prompt width.

        Paged engines additionally thread the page tables + per-slot
        start_tok (snapshotted from ``self.pool``, which the scheduler's
        block accounting updated before this call).
        """
        if self.is_encdec:
            raise NotImplementedError(
                "continuous batching serves decoder-only LMs; enc-dec uses "
                "Engine.generate")
        cache = self._fault_site("admit", cache, pos)
        key = jax.random.PRNGKey(self.scfg.seed)
        extra = self._paged_admit_args() if self.paged else ()
        return self._admit_fn(
            self.params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(mask, bool),
            jnp.asarray(budget_one, bool), eos, temperature, top_k, top_p,
            tok, pos, done, key, jnp.int32(step0), *extra)

    def _admit_impl(self, params, cache, prompts, lengths, mask, budget_one,
                    eos, temperature, top_k, top_p, tok, pos, done, key,
                    step0, *paged):
        from repro.dist import tp as tp_lib
        logits, pcache = self._mod.prefill(params, self.cfg, prompts,
                                           full_kv=True, length=lengths)
        cache = self._stitch_impl(cache, pcache, lengths, mask, paged)
        key = tp_lib.fold_in_data(key)   # per-data-shard sampling stream
        tok0 = sample_logits(logits, jax.random.fold_in(key, step0),
                             temperature, top_k, top_p)
        # finite-logits guard on the sampled rows (free rows report healthy)
        ok0 = jnp.isfinite(logits).all(axis=-1) | ~mask
        done0 = ((eos >= 0) & (tok0 == eos)) | budget_one
        active = mask & ~done0
        tok = jnp.where(mask, tok0, tok)
        pos = jnp.where(mask, jnp.where(active, lengths, -1), pos)
        done = jnp.where(mask, ~active, done)
        return cache, tok, pos, done, tok0, done0, ok0

    def step(self, cache, entries, tok, pos, done, eos, temperature, top_k,
             top_p, step0: int, chunk: int, greedy: bool = False,
             spec: bool = False):
        """ONE unified serving round in a single dispatch: a chunk lane of
        ``prefill_chunk`` masked prompt-token iterations (absent when
        ``entries`` is None) followed by a decode lane advancing every slot
        ``chunk`` tokens (lax.scan with on-device sampling).

        ``entries`` describes the round's prompt-chunk work as a dict of
        [prefill_chunk] host arrays (padded with slot=-1 no-op entries):

          * ``slot`` — target batch row (GLOBAL slot id under sharding)
          * ``tok`` / ``pos`` — the prompt token and its absolute position
          * ``first`` — True on a prompt's last token: that iteration's
            logits are the request's first-token logits and are sampled
          * ``budget_one`` — with ``first``: the request's whole budget is
            that first token, so the row finishes immediately

        Each chunk iteration runs the full-batch decode graph with the
        target row's (token, position) substituted in; non-target rows
        re-run their held (token, position), whose cache writes are
        idempotent.  When ``first`` fires, the sampled token and position+1
        become the row's decode state and the row joins the decode lane of
        the SAME round.  Finished/free slots (done=True) hold token and
        position throughout.  ``greedy=True`` (every slot at temperature 0,
        no filtering — the caller knows this statically) compiles an
        argmax-only variant that skips the per-token vocab sort; its tokens
        are bit-identical to the general path's.

        ``spec=True`` (requires ``scfg.spec_decode``) swaps the decode lane
        for a draft/verify speculative round: ``draft_k`` sequential
        truncated-plane drafter steps propose tokens, ONE batched
        (draft_k+1)-token target forward verifies them, and the longest
        matching prefix is accepted — up to ``draft_k + 1`` tokens per slot
        per round, bit-identical to the non-speculative transcript at
        temperature 0.  The tokens/dones outputs are then ``[B, draft_k+1]``
        wide and only the first ``n_valid[b]`` columns of row b are real.

        Returns (cache, tok, pos, done, tok0, done0, tokens [B, W],
        dones [B, W], ok [B], n_valid [B]) with W = chunk (or draft_k+1
        under ``spec``) — tok0/done0 are per-slot first tokens /
        immediately-finished flags, meaningful at rows whose ``first``
        entry fired this round; ok is the per-slot finite-logits guard over
        the whole round; n_valid counts the tokens each row actually
        advanced (always W on non-speculative rounds).  Compiles once per
        (has-entries, chunk, greedy, spec).
        """
        if self.is_encdec:
            raise NotImplementedError(
                "continuous batching serves decoder-only LMs; enc-dec uses "
                "Engine.generate")
        if spec and not self.scfg.spec_decode:
            raise ValueError("spec=True requires ServeConfig(spec_decode=True)")
        C = self.prefill_chunk if entries is not None else 0
        fn = self._step_fns.get((C, chunk, greedy, spec))
        if fn is None:
            fn = self._build_step_fn(C, chunk, greedy, spec)
            self._step_fns[(C, chunk, greedy, spec)] = fn
        if entries is not None:
            cache = self._fault_site("admit", cache, pos)
        cache = self._fault_site("decode", cache, pos)
        key = jax.random.PRNGKey(self.scfg.seed)
        if C:
            c_args = (jnp.asarray(entries["slot"], jnp.int32),
                      jnp.asarray(entries["tok"], jnp.int32),
                      jnp.asarray(entries["pos"], jnp.int32),
                      jnp.asarray(entries["first"], bool),
                      jnp.asarray(entries["budget_one"], bool))
        else:
            # dummy [1] no-op arrays keep one signature for both variants
            z = jnp.zeros((1,), jnp.int32)
            f = jnp.zeros((1,), bool)
            c_args = (z - 1, z, z, f, f)
        extra = self._paged_decode_args() if self.paged else ()
        return fn(self.params, cache, *c_args, tok, pos, done, eos,
                  temperature, top_k, top_p, key, jnp.int32(step0), *extra)

    def _make_step_impl(self, C: int, chunk: int, greedy: bool,
                        spec: bool = False):
        mod, cfg = self._mod, self.cfg
        K = self.scfg.draft_k
        draft_planes = self.scfg.draft_planes

        def run(params, cache, c_slot, c_tok, c_pos, c_first, c_b1, tok,
                pos, done, eos, temperature, top_k, top_p, key, step0,
                *paged):
            from repro.dist import tp as tp_lib
            key = tp_lib.fold_in_data(key)   # per-data-shard sampling stream
            tables = paged if paged else None
            ok = jnp.ones(tok.shape, bool)
            tok0, done0 = tok, done

            def sample(logits, key_i):
                if greedy:
                    return sample_logits(logits, key_i, 0.0, 0, 1.0)
                return sample_logits(logits, key_i, temperature, top_k,
                                     top_p)

            if C:
                # chunk-lane rows are GLOBAL slot ids: under a data mesh
                # each shard owns a contiguous block of slots
                rows = jnp.arange(tok.shape[0], dtype=jnp.int32)
                axis = tp_lib.data_axis()
                if axis is not None:
                    rows = rows + jax.lax.axis_index(axis) * tok.shape[0]

                def fill(carry, xs):
                    cache, tok, pos, done, ok, tok0, done0 = carry
                    s, t, p, first, b1, i = xs
                    target = rows == s           # all-False for pad entries
                    tok_in = jnp.where(target, t, tok)
                    pos_in = jnp.where(target, p, pos)
                    logits, cache = mod.decode_step(params, cfg, tok_in,
                                                    cache, pos_in,
                                                    tables=tables)
                    fire = target & first
                    ok = ok & (jnp.isfinite(logits).all(axis=-1) | ~fire)
                    nxt = sample(logits, jax.random.fold_in(key, step0 + i))
                    nd = ((nxt == eos) & (eos >= 0)) | b1
                    # fire: the row becomes a decoder at (sampled, p + 1);
                    # otherwise the target row parks on this entry's (t, p)
                    # — its write next iteration is an idempotent re-run
                    tok = jnp.where(fire, nxt, tok_in)
                    pos = jnp.where(fire, p + 1, pos_in)
                    done = jnp.where(fire, nd, done)
                    tok0 = jnp.where(fire, nxt, tok0)
                    done0 = jnp.where(fire, nd, done0)
                    return (cache, tok, pos, done, ok, tok0, done0), None

                xs = (c_slot, c_tok, c_pos, c_first, c_b1,
                      jnp.arange(C, dtype=jnp.int32))
                (cache, tok, pos, done, ok, tok0, done0), _ = jax.lax.scan(
                    fill, (cache, tok, pos, done, ok, tok0, done0), xs)

            if spec:
                # -- speculative decode lane: draft K / verify 1 -----------
                # Precondition (scheduler-enforced): every non-free slot has
                # pos <= max_len - (K+1), so no block write clamps into live
                # history.  Rows done at round entry (parked mid-prefill /
                # free) hold (tok, pos) throughout; the drafter's writes at
                # their held slot are restored by the verify pass's target-
                # bits rewrite of the same slots.
                from repro.serve.quantize import draft_params_view
                S = K + 1
                # trace-time truncated-plane view: pure slices of the
                # target's packed codes (zero extra weight memory; XLA
                # hoists them as loop-invariant)
                dparams = draft_params_view(params, draft_planes)

                def draft(carry, j):
                    cache, dtok, dpos = carry
                    logits, cache = mod.decode_step(dparams, cfg, dtok,
                                                    cache, dpos,
                                                    tables=tables)
                    nxt = sample(logits,
                                 jax.random.fold_in(key, step0 + C + j))
                    nxt = jnp.where(done, dtok, nxt)
                    dpos = jnp.where(done, dpos, dpos + 1)
                    return (cache, nxt, dpos), nxt

                (cache, _, _), drafts = jax.lax.scan(
                    draft, (cache, tok, pos), jnp.arange(K, dtype=jnp.int32))
                drafts = drafts.T                               # [B, K]
                # ONE batched target forward over [t0, d_1..d_K]: logits[i]
                # conditions on the accepted-so-far prefix exactly like i
                # sequential target steps would (verify_step writes target
                # bits over every speculative slot before attending)
                vtoks = jnp.concatenate([tok[:, None], drafts], axis=1)
                logits, cache = mod.verify_step(params, cfg, vtoks, cache,
                                                pos, tables=tables)
                ok = ok & (jnp.isfinite(logits).all(axis=(-2, -1)) | done)
                v = jnp.stack(
                    [sample(logits[:, i],
                            jax.random.fold_in(key, step0 + C + K + i))
                     for i in range(S)], axis=1)                # [B, S]
                # accept the longest prefix where the target reproduces the
                # draft; v_{m+1} (the first mismatch / bonus token) is free
                match = (v[:, :K] == drafts).astype(jnp.int32)
                m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)   # [B] 0..K
                cols = jnp.arange(S, dtype=jnp.int32)[None]       # [1, S]
                is_eos = (eos[:, None] >= 0) & (v == eos[:, None])
                eos_in = is_eos & (cols <= m[:, None])
                any_eos = eos_in.any(axis=1)
                first_eos = jnp.argmax(eos_in, axis=1).astype(jnp.int32)
                n_valid = jnp.where(any_eos, first_eos + 1, m + 1)
                n_valid = jnp.where(done, 0, n_valid).astype(jnp.int32)
                newtok = jnp.take_along_axis(
                    v, jnp.maximum(n_valid - 1, 0)[:, None], axis=1)[:, 0]
                tok = jnp.where(done, tok, newtok)
                pos = pos + n_valid
                done = done | (any_eos & (n_valid > 0))
                toks, dones = v.T, (is_eos
                                    & (cols < n_valid[:, None])).T
            else:
                def step(carry, j):
                    cache, tok, pos, done, ok = carry
                    logits, cache = mod.decode_step(params, cfg, tok, cache,
                                                    pos, tables=tables)
                    # finite-logits guard: rows already done (or free)
                    # before this step never sampled these logits — ignore
                    ok = ok & (jnp.isfinite(logits).all(axis=-1) | done)
                    nxt = sample(logits,
                                 jax.random.fold_in(key, step0 + C + j))
                    nxt = jnp.where(done, tok, nxt)
                    pos = jnp.where(done, pos, pos + 1)
                    done = done | ((nxt == eos) & (eos >= 0))
                    return (cache, nxt, pos, done, ok), (nxt, done)

                (cache, tok, pos, done, ok), (toks, dones) = jax.lax.scan(
                    step, (cache, tok, pos, done, ok),
                    jnp.arange(chunk, dtype=jnp.int32))
                n_valid = jnp.full(tok.shape, chunk, jnp.int32)
            # cache-finiteness guard: quantized (integer-code) matmul paths
            # launder NaN activations into finite garbage codes, so poisoned
            # KV can yield wrong-but-FINITE logits the guard above never
            # sees.  Sweep the float attention leaves once per round; a
            # non-finite value anywhere fails every slot (recovery replays
            # the whole batch from the snapshot regardless).  Under tensor
            # parallelism each shard holds a head slice, so the verdict must
            # be all-reduced over the model axis — the ok out-spec is
            # model-replicated and an unreduced miss on the clean shards
            # would mask the poisoned one.
            cache_ok = _cache_finite(cache)
            axis = tp_lib.model_axis()
            if axis is not None:
                cache_ok = jax.lax.pmin(
                    cache_ok.astype(jnp.int32), axis).astype(bool)
            ok = ok & cache_ok
            return (cache, tok, pos, done, tok0, done0, toks.T, dones.T, ok,
                    n_valid)

        return run

    # -- cache stitching (static-batch path) ---------------------------------

    def _grow_cache(self, cache, prompt_len: int):
        """Pad prefill caches (sized S or window) into max_len buffers."""
        cfg, S, M = self.cfg, prompt_len, self.scfg.max_len
        if self.is_encdec:
            grown = dict(cache)
            for k in ("k", "v"):
                buf = jnp.zeros(cache[k].shape[:2] + (M,) + cache[k].shape[3:],
                                cache[k].dtype)
                grown[k] = jax.lax.dynamic_update_slice_in_dim(
                    buf, cache[k], 0, axis=2)
            return grown
        out = []
        for spec, c in zip(cfg.pattern, cache):
            c = dict(c)
            for key in ("k", "v", "shared_k", "shared_v"):
                if key not in c:
                    continue
                T = c[key].shape[2]
                # local/SWA k/v buffers are rings of at most `window` slots
                # (decode addresses slot pos % T); everything else grows to
                # max_len.  Prefill emits a window-size ring only when the
                # prompt exceeds the window — a shorter prompt's cache (T=S,
                # slot i == abs pos i == i % target) still needs growing.
                is_local_kv = (key in ("k", "v")
                               and spec.attn_type == "local"
                               and bool(cfg.window))
                target = min(M, cfg.window) if is_local_kv else M
                if T == target:
                    continue
                buf = jnp.zeros(c[key].shape[:2] + (target,)
                                + c[key].shape[3:], c[key].dtype)
                c[key] = jax.lax.dynamic_update_slice_in_dim(
                    buf, c[key], 0, axis=2)
            out.append(c)
        return tuple(out)

    # -- generation ----------------------------------------------------------

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 frames: Optional[jax.Array] = None,
                 use_scan: bool = True) -> jax.Array:
        """prompts: [B, S] int32 -> [B, S + max_new_tokens].

        ``use_scan=False`` runs the per-token Python loop (the reference the
        scanned decode is tested bit-exact against); both paths draw token i
        with ``fold_in(key, i)``, so they agree at any temperature.

        On a paged engine the scan executors are compiled against page
        pools, so ``generate`` always takes the python loop over a dense
        prefill cache — it stays the dense bit-exactness oracle either way.
        """
        if self.paged:
            use_scan = False
        B, S = prompts.shape
        if self.is_encdec:
            logits, cache = self._prefill(self.params, frames, prompts)
        else:
            logits, cache = self._prefill(self.params, prompts)
        cache = self._grow_cache(cache, S)
        key = jax.random.PRNGKey(self.scfg.seed)
        sc = self.scfg
        greedy = sc.temperature <= 0.0 and sc.top_k == 0 and sc.top_p >= 1.0
        tok = sample_logits(logits, jax.random.fold_in(key, 0),
                            sc.temperature, sc.top_k, sc.top_p)
        pos = jnp.full((B,), S, jnp.int32)
        if max_new_tokens <= 1:
            return jnp.concatenate([prompts, tok[:, None]], axis=1)
        if use_scan:
            done = jnp.zeros((B,), bool)
            eos = jnp.full((B,), -1, jnp.int32)
            temp = jnp.full((B,), sc.temperature, jnp.float32)
            top_k = jnp.full((B,), sc.top_k, jnp.int32)
            top_p = jnp.full((B,), sc.top_p, jnp.float32)
            ys = self.step(cache, None, tok, pos, done, eos, temp,
                           top_k, top_p, 1,
                           max_new_tokens - 1, greedy=greedy)[6]
            out = jnp.concatenate([tok[:, None], ys], axis=1)
        else:
            toks = [tok]
            for i in range(1, max_new_tokens):
                logits, cache = self._decode(self.params, tok, cache, pos)
                tok = sample_logits(logits, jax.random.fold_in(key, i),
                                    sc.temperature, sc.top_k, sc.top_p)
                toks.append(tok)
                pos = pos + 1
            out = jnp.stack(toks, axis=1)
        return jnp.concatenate([prompts, out], axis=1)

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        """Sample one token per row under the engine-wide ServeConfig
        (argmax when temperature <= 0, exactly as before; top-k / top-p via
        :func:`sample_logits`)."""
        sc = self.scfg
        return sample_logits(logits, key, sc.temperature, sc.top_k, sc.top_p)
