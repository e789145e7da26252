"""Random served weights, made from the seed in the format they are served in.

The serving engine reads quantized projection leaves (see
``src/repro/serve/quantize.py``): stored codes in the format of the serving
mode, each with a float32 per-output-channel scale.  The formats are the
benchmark's own files (``bench/formats/<name>.py``, named by a
configuration's ``serving.format``; the LM head is ``int8``), so the
benchmark makes the served codes directly on the device (no float model,
no quantizer) and the reference regenerates the same codes layer by layer
from the same seed without importing the program.

Every leaf is drawn from ``fold_in(seed key, crc32(path))`` and, for the
scanned per-layer stacks, ``fold_in(leaf key, layer)``; ``jax.vmap`` over
the layer index gives the same bits as one layer at a time.

Distributions (one set for every configuration):
  * codes: as the format file draws them (``CODE_RMS`` is their rms);
  * per-channel scales: ``gain / (code rms * sqrt(K))`` times U[0.75, 1.25],
    so a projection of a unit-rms input has unit-rms output; the residual
    output projections (attention ``wo``, MLP ``wo``) get
    ``gain = 1 / sqrt(2 * n_layers)``, the GPT-2 residual init;
  * embedding N(0, 1) in float32; QKV biases N(0, 0.1); norm scales
    U[0.8, 1.2].
"""
from __future__ import annotations

import math
import re
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import spec

HEAD_FORMAT = "int8"            # the LM head's codes, in every serving mode
BENCH = Path(__file__).resolve().parent


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (PRNGKey keeps only 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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


def weight_format(cfg: dict, name: str):
    """The weight format module ``formats/<name>.py`` of the benchmark
    tree the configuration was loaded from (``bench_dir``; default: this
    one)."""
    return spec.load_module(cfg.get("bench_dir", BENCH), "formats", name)


def projections(cfg: dict) -> list:
    """(group, name, K, N, residual_out) of every body projection."""
    m = dims(cfg)
    d, q, kv, ff = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    return [("attn", "wq", d, q, False), ("attn", "wk", d, kv, False),
            ("attn", "wv", d, kv, False), ("attn", "wo", q, d, True),
            ("mlp", "wi", d, ff, False), ("mlp", "wg", d, ff, False),
            ("mlp", "wo", ff, d, True)]


def layer_leaves(cfg: dict) -> list:
    """(path, rule, shape, dtype, extra) of one layer's leaves; ``path``
    is relative to the block dict of the single pattern position.  Rule
    ``codes`` stands for the stored code leaves of the format module in
    ``extra``, set under ``path``."""
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


def _leaf_key(key, path) -> jax.Array:
    return jax.random.fold_in(
        key, zlib.crc32("/".join(map(str, path)).encode()) & 0x7FFFFFFF)


def make_leaf(key, rule: str, shape, dtype, extra: dict):
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


def layer_params(cfg: dict, key, g) -> dict:
    """One layer's leaves from the seed's key (``g`` may be traced)."""
    key = _leaf_key(key, ("blocks",))
    out: dict = {}
    for path, rule, shape, dtype, extra in layer_leaves(cfg):
        k = jax.random.fold_in(_leaf_key(key, _key_path(path, rule)), g)
        _put(out, path, rule, make_leaf(k, rule, shape, dtype, extra))
    return out


def outer_params(cfg: dict, key) -> dict:
    out: dict = {}
    for path, rule, shape, dtype, extra in outer_leaves(cfg):
        k = _leaf_key(key, _key_path(path, rule))
        _put(out, path, rule, make_leaf(k, rule, shape, dtype, extra))
    return out


def served_params(cfg: dict, seed: int) -> dict:
    """The whole served pytree, made on the device by one jitted call:
    outer leaves plus ``blocks`` = (per-pattern-position dict of
    ``[n_layers, ...]`` stacks,)."""
    n = dims(cfg)["L"]

    def build(key):
        blocks = jax.vmap(lambda g: layer_params(cfg, key, g))(
            jnp.arange(n, dtype=jnp.uint32))
        return {**outer_params(cfg, key), "blocks": (blocks,)}

    # the key is an argument, so every seed runs the same compiled program
    return jax.jit(build)(seed_key(seed))
