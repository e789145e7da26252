"""Fault-tolerant checkpointing: msgpack + zstd, atomic, async, and
topology-elastic (a checkpoint saved under one mesh restores under any other).

Format: one directory per step,
    ckpt_dir/step_000123/
        manifest.json        (treedef, shapes, dtypes, step, extra metadata)
        data.msgpack.zst     (flat list of raw little-endian buffers)
        _COMMITTED           (written last; restore ignores dirs without it)

Leaves are gathered to host (global arrays) before serialization, so the
restore path is free to re-shard onto a different mesh/topology — the elastic
restart path.  Saves are atomic (tmp dir + rename) and optionally async
(background thread), so a mid-save failure never corrupts the latest
committed checkpoint.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard


def _compress(payload: bytes) -> tuple[bytes, str]:
    """Returns (bytes, codec); codec is recorded in the manifest."""
    return zstandard.ZstdCompressor(level=3).compress(payload), "zstd"


def _decompress(data: bytes, codec: str) -> bytes:
    if codec != "zstd":
        raise ValueError(f"unknown checkpoint codec {codec!r}")
    return zstandard.ZstdDecompressor().decompress(data)


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def tree_to_host(tree: Any) -> Any:
    """Gather every leaf to host as a materialized ``np.ndarray`` (sharded
    globals gather fully).  Shared by the serializer below and the serving
    scheduler's rolling fault-recovery snapshots — a host copy is the only
    safe snapshot under buffer donation (a device reference would alias the
    very buffer the next dispatch overwrites)."""
    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(jax.device_get(leaf)), tree)


def save(ckpt_dir: str, step: int, tree: Any, extra: Optional[dict] = None,
         async_save: bool = False) -> threading.Thread | None:
    """Serialize ``tree`` (gathered to host) atomically under ``ckpt_dir``."""
    paths, leaves, _ = _flatten_with_paths(tree)
    host_leaves = jax.tree_util.tree_leaves(tree_to_host(leaves))

    def _write():
        final = os.path.join(ckpt_dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        payload = msgpack.packb([leaf.tobytes() for leaf in host_leaves])
        blob, codec = _compress(payload)
        manifest = {
            "step": step,
            "paths": paths,
            "shapes": [list(leaf.shape) for leaf in host_leaves],
            "dtypes": [str(leaf.dtype) for leaf in host_leaves],
            "codec": codec,
            "extra": extra or {},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(tmp, "data.msgpack.zst"), "wb") as f:
            f.write(blob)
        with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
            f.write("ok")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)

    if async_save:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp") and \
                os.path.exists(os.path.join(ckpt_dir, d, "_COMMITTED")):
            steps.append(int(d[5:]))
    return max(steps) if steps else None


def restore(ckpt_dir: str, target_tree: Any, step: Optional[int] = None,
            shardings: Any = None) -> tuple[Any, dict]:
    """Restore into the structure of ``target_tree``; re-shards if
    ``shardings`` (a matching pytree of NamedSharding) is given — this is the
    elastic path: the checkpoint has no knowledge of the saving topology.

    Returns (tree, manifest_extra).
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(d, "data.msgpack.zst"), "rb") as f:
        payload = msgpack.unpackb(
            _decompress(f.read(), manifest.get("codec", "zstd")))
    paths, leaves, treedef = _flatten_with_paths(target_tree)
    if paths != manifest["paths"]:
        missing = set(manifest["paths"]) ^ set(paths)
        raise ValueError(f"checkpoint/model structure mismatch: {sorted(missing)[:5]}")
    out = []
    flat_sh = (treedef.flatten_up_to(shardings) if shardings is not None
               else [None] * len(leaves))
    for buf, shape, dtype, tgt, sh in zip(payload, manifest["shapes"],
                                          manifest["dtypes"], leaves, flat_sh):
        arr = np.frombuffer(buf, dtype=np.dtype(dtype)).reshape(shape)
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jnp.asarray(arr))
    return treedef.unflatten(out), manifest["extra"]
