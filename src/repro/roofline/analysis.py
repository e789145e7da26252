"""Three-term roofline from compiled artifacts (per-device-kind peaks).

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = sum over collective ops of ring-model per-device link bytes / link_bw

``cost_analysis()`` on the CPU SPMD backend reports *per-partition* flops/bytes
(verified empirically in tests), so no division by chip count is applied.
Collective bytes are parsed from the partitioned HLO text; shapes there are
already per-device.  Ring formulas (B = per-device payload bytes, n = group
size): all-reduce 2(n-1)/n*B, all-gather (n-1)/n*B_result, reduce-scatter
(n-1)*B_result (= (n-1)/n * input), all-to-all (n-1)/n*B, collective-permute B.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (four links of
# 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
# the production meshes the compile-only dry-run models are v5e pods
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; a device that is not in the
    table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add the chip's published peaks to "
            "roofline.analysis.PEAKS") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        body = m.group(1).strip()
        return len(body.split(",")) if body else 1
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, = (int(m.group(1)),)
        size = int(m.group(2))
        return size
    return 1


@dataclasses.dataclass
class Collective:
    op: str
    result_bytes: int
    group_size: int
    line: str

    @property
    def link_bytes(self) -> float:
        """Per-device ring-model bytes over the link."""
        n, b = self.group_size, self.result_bytes
        if self.op == "collective-permute":
            return float(b)
        if n <= 1:
            return 0.0
        if self.op == "all-reduce":
            return 2 * (n - 1) / n * b
        if self.op == "all-gather":
            return (n - 1) / n * b
        if self.op == "reduce-scatter":
            return (n - 1) * b          # input = n * result
        if self.op == "all-to-all":
            return (n - 1) / n * b
        return 0.0


def parse_collectives(hlo_text: str) -> list[Collective]:
    out = []
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or "=" not in s:
            continue
        m = re.search(
            r"=\s*(.*?)\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start|-done)?\(", s)
        if not m:
            continue
        if "-done(" in s:     # avoid double counting start/done pairs
            continue
        result_type, op = m.group(1), m.group(2)
        out.append(Collective(op=op, result_bytes=_shape_bytes(result_type),
                              group_size=_group_size(s), line=s[:160]))
    return out


def roofline_terms(cost: dict, hlo_text: str,
                   device_kind: str = DRYRUN_DEVICE_KIND) -> dict:
    """Returns the three terms (seconds) on ``device_kind``'s peaks +
    supporting detail."""
    peak = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(hlo_text)
    coll_bytes = sum(c.link_bytes for c in colls)
    per_op = {}
    for c in colls:
        d = per_op.setdefault(c.op, {"count": 0, "link_bytes": 0.0})
        d["count"] += 1
        d["link_bytes"] += c.link_bytes
    top = sorted(colls, key=lambda c: -c.link_bytes)[:8]
    return {
        "compute_s": flops / peak["bf16_flops"],
        "memory_s": hbm_bytes / peak["hbm_bw"],
        "collective_s": coll_bytes / peak["link_bw"],
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": hbm_bytes,
        "collective_link_bytes": coll_bytes,
        "collectives": per_op,
        "n_collectives": len(colls),
        "top_collectives": [
            {"op": c.op, "link_bytes": c.link_bytes, "n": c.group_size,
             "line": c.line[:140]} for c in top],
    }


def extrapolate_terms(t1g: dict, t2g: dict, n_groups: int) -> dict:
    """Per-group linear extrapolation: total = t1g + (G-1) * (t2g - t1g).

    The 1-group and 2-group programs share embed/head/loss/optimizer terms,
    so the delta isolates one group's cost exactly; collectives extrapolate
    per op type the same way.
    """
    g = n_groups
    out = {}
    for k in ("compute_s", "memory_s", "collective_s",
              "hlo_flops_per_device", "hlo_bytes_per_device",
              "collective_link_bytes"):
        out[k] = t1g[k] + (g - 1) * (t2g[k] - t1g[k])
    colls = {}
    ops = set(t1g["collectives"]) | set(t2g["collectives"])
    for op in ops:
        c1 = t1g["collectives"].get(op, {"count": 0, "link_bytes": 0.0})
        c2 = t2g["collectives"].get(op, {"count": 0, "link_bytes": 0.0})
        colls[op] = {
            "count": c1["count"] + (g - 1) * (c2["count"] - c1["count"]),
            "link_bytes": c1["link_bytes"]
            + (g - 1) * (c2["link_bytes"] - c1["link_bytes"]),
        }
    out["collectives"] = colls
    out["n_collectives"] = int(t1g["n_collectives"]
                               + (g - 1) * (t2g["n_collectives"]
                                            - t1g["n_collectives"]))
    out["extrapolated_from"] = "1g/2g delta"
    return out


def dominant(terms: dict) -> str:
    vals = {"compute": terms["compute_s"], "memory": terms["memory_s"],
            "collective": terms["collective_s"]}
    return max(vals, key=vals.get)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6ND / 2ND) accounting
# ---------------------------------------------------------------------------

def count_params(params_sds, moe_top_k: Optional[int] = None,
                 n_experts: Optional[int] = None) -> dict:
    """Returns {"total": N, "active": N_active} from an eval_shape'd tree."""
    import jax
    import numpy as np
    total = 0
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_sds)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        name = jax.tree_util.keystr(path)
        if re.search(r"\['moe'\]\['w[igo]'\]", name):
            expert += n
    active = total
    if expert and moe_top_k and n_experts:
        active = total - expert + expert * moe_top_k / n_experts
    return {"total": total, "active": active}


def model_flops(kind: str, n_active: float, global_batch: int,
                seq_len: int) -> float:
    if kind == "train":
        return 6.0 * n_active * global_batch * seq_len
    if kind == "prefill":
        return 2.0 * n_active * global_batch * seq_len
    return 2.0 * n_active * global_batch          # decode: one token / seq


# ---------------------------------------------------------------------------
# mixed per-layer weight bit widths (tmac serving family)
# ---------------------------------------------------------------------------

# demotion ladder: width spec -> effective bits per weight
_BITS_LADDER = ((4, 4.0), (3, 3.0), (2, 2.0), ("ternary", 1.58), (1, 1.0))


def plan_mixed_bits(params, target_bits: float, abits: int = 4,
                    attn_floor: float = 2.0,
                    mlp_floor: float = 1.0) -> dict:
    """Choose per-leaf tmac weight widths hitting a target average bit width.

    The roofline says decode GEMVs are memory-bound (at M = batch tokens,
    ``memory_s = weight_bytes / hbm_bw`` dwarfs ``compute_s`` until M is in
    the hundreds), so decode latency IS weight bytes and the tmac kernel's
    cost is linear in the plane count either way — minimizing total weight
    bits minimizes both terms at once.  Greedy: repeatedly demote the leaf
    with the largest byte saving one ladder step (4 -> 3 -> 2 -> ternary ->
    1) until the parameter-weighted average reaches ``target_bits``, subject
    to floors (attention projections keep >= ``attn_floor`` bits — their
    quantization error feeds every downstream token through the KV cache;
    MLP >= ``mlp_floor``).  Embedding and lm_head are outside the plan
    entirely (the serving walk pins them 8-bit, the paper's first/last-layer
    rule).

    Returns ``{path: mode}`` keyed by the same ``"...['wq']['w']"`` path
    strings ``serve.quantize.quantize_params_for_serving`` builds — pass it
    as that function's ``bits_plan`` (or via ``ServeConfig.bits_plan``).
    Deterministic: ties break on path order.
    """
    import numpy as np
    from repro.serve.quantize import _INNER_W

    leaves: list[list] = []       # [path, n_params, is_attn, ladder_idx]

    def walk(tree, path=""):
        if isinstance(tree, dict):
            for k, v in tree.items():
                sub = f"{path}['{k}']"
                if isinstance(v, dict) and "w" in v and _INNER_W.search(
                        sub + "['w']") and getattr(v["w"], "ndim", 0) >= 2:
                    leaves.append([sub + "['w']",
                                   int(np.prod(v["w"].shape)),
                                   "['attn']" in sub, 0])
                else:
                    walk(v, sub)
        elif isinstance(tree, (tuple, list)):
            for i, v in enumerate(tree):
                walk(v, f"{path}[{i}]")

    walk(params)
    if not leaves:
        return {}
    total = sum(n for _, n, _, _ in leaves)

    def avg() -> float:
        return sum(n * _BITS_LADDER[i][1] for _, n, _, i in leaves) / total

    while avg() > target_bits:
        best, best_save = None, 0.0
        for leaf in leaves:
            _, n, is_attn, i = leaf
            if i + 1 >= len(_BITS_LADDER):
                continue
            floor = attn_floor if is_attn else mlp_floor
            if _BITS_LADDER[i + 1][1] < floor:
                continue
            save = n * (_BITS_LADDER[i][1] - _BITS_LADDER[i + 1][1])
            if save > best_save:
                best, best_save = leaf, save
        if best is None:          # every leaf at its floor
            break
        best[3] += 1

    def mode(spec) -> str:
        return (f"ternary_a{abits}_tmac" if spec == "ternary"
                else f"w{spec}a{abits}_tmac")

    return {path: mode(_BITS_LADDER[i][0]) for path, _, _, i in leaves}
