"""Random served weights, made from the seed in the format they are served in.

The serving engine reads quantized projection leaves (see
``src/repro/serve/quantize.py``): stored codes in the format of the serving
mode, each with a float32 per-output-channel scale.  The formats are the
benchmark's own files (``bench/formats/<name>.py``, named by a
configuration's ``serving.format``; the LM head is ``int8``), so the
benchmark makes the served codes directly on the device (no float model,
no quantizer) and the reference regenerates the same codes layer by layer
from the same seed without importing the program.  Which leaves a layer
and the model around the layers hold, and where the layers sit in the
served tree, is the architecture's (``bench/archs/<arch>.py``).

Every leaf is drawn from ``fold_in(seed key, crc32(path))`` and, for the
per-layer leaves, ``fold_in(leaf key, layer)``; ``jax.vmap`` over the
layer index gives the same bits as one layer at a time.

Distributions (one set for every configuration):
  * codes: as the format file draws them (``CODE_RMS`` is their rms);
  * per-channel scales: ``gain / (code rms * sqrt(K))`` times U[0.75, 1.25],
    so a projection of a unit-rms input has unit-rms output; residual
    output projections get ``gain = 1 / sqrt(2 * n_layers)``, the GPT-2
    residual init;
  * embedding N(0, 1) in float32; biases N(0, 0.1); norm scales
    U[0.8, 1.2].
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp

from bench import spec

HEAD_FORMAT = "int8"            # the LM head's codes, in every serving mode


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (PRNGKey keeps only 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_format(cfg: dict, name: str):
    """The weight format module ``formats/<name>.py`` of the benchmark
    tree the configuration was loaded from (``bench_dir``; default: this
    one)."""
    return spec.load_module(cfg.get("bench_dir", spec.BENCH), "formats",
                            name)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where an architecture's layers sit in the served tree.  Layers run
    in this order: the ``lead`` layers, each at its own tree path outside
    the program's layer scan, then ``groups`` repeats of ``pattern`` in the
    scan.  Layer ``g = len(lead) + i * len(pattern) + j`` is group ``i`` at
    pattern position ``j``: row ``i`` of the stacks in ``blocks[j]``."""
    pattern: tuple          # the kind of layer at each pattern position
    groups: int
    lead: tuple = ()        # (tree path, kind) of each leading layer

    def kinds(self) -> list:
        """Every layer's kind, in order."""
        return [k for _, k in self.lead] + list(self.pattern) * self.groups


def _leaf_key(key, path) -> jax.Array:
    return jax.random.fold_in(
        key, zlib.crc32("/".join(map(str, path)).encode()) & 0x7FFFFFFF)


def make_leaf(key, rule: str, shape, dtype, extra: dict):
    """One leaf of a leaf list (``(path, rule, shape, dtype, extra)``).
    Rule ``codes`` stands for the stored code leaves of the format module
    ``extra["fmt"]``, set under ``path``; ``scale`` is their per-channel
    scale (``extra``: ``K``, ``gain``, ``fmt``)."""
    if rule == "emb":
        return jax.random.normal(key, shape, jnp.float32)
    if rule == "norm":
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    if rule == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if rule == "codes":
        return extra["fmt"].make(key, *shape)
    if rule == "scale":
        base = extra["gain"] / (extra["fmt"].CODE_RMS
                                * math.sqrt(extra["K"]))
        return base * jax.random.uniform(key, shape, jnp.float32, 0.75,
                                         1.25)
    raise ValueError(rule)


def _key_path(path, rule: str) -> tuple:
    # code leaves draw from the key of their ``w_q`` leaf
    return path + ("w_q",) if rule == "codes" else path


def _put(tree: dict, path, rule: str, value) -> None:
    if rule == "codes":
        for name, leaf in value.items():
            _set(tree, path + (name,), leaf)
    else:
        _set(tree, path, value)


def _set(tree: dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def layer_params(cfg: dict, key, g, kind: str) -> dict:
    """Layer ``g``'s leaves, of the architecture's layer ``kind``, from
    the seed's key (``g`` may be traced)."""
    key = _leaf_key(key, ("blocks",))
    out: dict = {}
    for path, rule, shape, dtype, extra in spec.arch(cfg).layer_leaves(
            cfg, kind):
        k = jax.random.fold_in(_leaf_key(key, _key_path(path, rule)), g)
        _put(out, path, rule, make_leaf(k, rule, shape, dtype, extra))
    return out


def outer_params(cfg: dict, key) -> dict:
    out: dict = {}
    for path, rule, shape, dtype, extra in spec.arch(cfg).outer_leaves(cfg):
        k = _leaf_key(key, _key_path(path, rule))
        _put(out, path, rule, make_leaf(k, rule, shape, dtype, extra))
    return out


def served_params(cfg: dict, seed: int) -> dict:
    """The whole served pytree, made on the device by one jitted call:
    outer leaves, the leading layers at their paths, and ``blocks`` = (one
    dict of ``[groups, ...]`` stacks per pattern position,) as the
    architecture's ``Layout`` places them."""
    lay = spec.arch(cfg).layout(cfg)
    first, P = len(lay.lead), len(lay.pattern)

    def position(key, j: int, kind: str):
        return jax.vmap(
            lambda i: layer_params(cfg, key, first + i * P + j, kind))(
            jnp.arange(lay.groups, dtype=jnp.uint32))

    def build(key):
        out = outer_params(cfg, key)
        for g, (path, kind) in enumerate(lay.lead):
            _set(out, path, layer_params(cfg, key, jnp.uint32(g), kind))
        out["blocks"] = tuple(position(key, j, kind)
                              for j, kind in enumerate(lay.pattern))
        return out

    # the key is an argument, so every seed runs the same compiled program
    return jax.jit(build)(seed_key(seed))
