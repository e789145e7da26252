"""Operations and bytes the served model needs, computed from shapes.

Counts are of the work the model requires, not of what a kernel happens to
do: a matmul of M real rows by a K x N weight is ``2 M K N`` integer
operations whatever rows a kernel pads to, and reads its weight codes once.
Integer operations (every quantized projection and the head) are held
against the chip's int8 peak, attention's bfloat16 operations against its
bf16 peak, and bytes against HBM bandwidth.
"""
from __future__ import annotations

import dataclasses

from bench.weights import HEAD_FORMAT as HEAD
from bench.weights import dims, projections, weight_format


@dataclasses.dataclass
class Work:
    int_ops: float = 0.0
    bf16_ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.int_ops + o.int_ops, self.bf16_ops + o.bf16_ops,
                    self.bytes + o.bytes)

    def ideal_s(self, peaks: dict) -> float:
        """Least time on the chip: the larger of compute and memory time."""
        compute = (self.int_ops / peaks["int8_ops_per_s"]
                   + self.bf16_ops / peaks["bf16_flops_per_s"])
        return max(compute, self.bytes / peaks["hbm_bytes_per_s"])


def matmul(M: int, K: int, N: int, bits: int) -> Work:
    """One quantized projection call: int8 activation codes in, bf16 out,
    weight codes of ``bits`` bits and per-channel scales read once,
    per-token scales."""
    return Work(int_ops=2.0 * M * K * N,
                bytes=K * N * bits / 8 + 4 * N
                + M * K + 4 * M + 2 * M * N)               # acts in, out


def kernel_calls(cfg: dict, kernel: str) -> list:
    """(K, N, code bits) of each call of ``kernel`` in one forward: the
    body projections run on their format's ``KERNEL``, the head on the
    head format's."""
    m = dims(cfg)
    body, head = weight_format(cfg, m["format"]), weight_format(cfg, HEAD)
    out = []
    if body.KERNEL == kernel:
        out += [(K, N, body.BITS)
                for _, _, K, N, _ in projections(cfg)] * m["L"]
    if head.KERNEL == kernel:
        out.append((m["d"], m["V"], head.BITS))
    return out


def kernel_work(cfg: dict, kernel: str, M: int) -> Work:
    """Work of one forward's calls of ``kernel`` at M rows."""
    w = Work()
    for K, N, bits in kernel_calls(cfg, kernel):
        w = w + matmul(M, K, N, bits)
    return w


def weight_bytes(cfg: dict) -> float:
    """Bytes of the served weights one forward reads: body codes and
    scales, norms, biases, the head (the embedding is gathered by row)."""
    m = dims(cfg)
    bits = weight_format(cfg, m["format"]).BITS
    body = sum(K * N * bits / 8 + 4 * N
               for _, _, K, N, _ in projections(cfg))
    body += 2 * 4 * m["d"]
    if m["qkv_bias"]:
        body += 4 * (m["h"] + 2 * m["kv"]) * m["hd"]
    head = weight_format(cfg, HEAD).BITS
    return (m["L"] * body + m["d"] * m["V"] * head / 8
            + 4 * m["V"] + 4 * m["d"])


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 K and V of one token over all layers."""
    m = dims(cfg)
    return m["L"] * 2 * m["kv"] * m["hd"] * 2


def forward(cfg: dict, ctx: list) -> Work:
    """One forward of the rows whose context lengths (the position each
    row attends from, plus one) are ``ctx``: every weight read once, each
    row's projections and head, its attention over its own context."""
    m = dims(cfg)
    rows = len(ctx)
    if not rows:
        return Work()
    per_row = 2.0 * (m["L"] * sum(K * N for _, _, K, N, _ in
                                  projections(cfg)) + m["d"] * m["V"])
    attn = sum(4.0 * m["L"] * m["h"] * m["hd"] * c for c in ctx)
    return Work(int_ops=rows * per_row, bf16_ops=attn,
                bytes=weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(ctx)
                + rows * 4 * m["d"])           # + embedding rows gathered


def decode_round(cfg: dict, live: list, chunk: int) -> Work:
    """A decode-only round: ``chunk`` forwards of every occupied slot, the
    contexts growing by one each forward (``live`` = context lengths at
    the round's start, the pending token not yet written)."""
    w = Work()
    for j in range(chunk):
        w = w + forward(cfg, [c + j for c in live])
    return w


def prefill_round(cfg: dict, positions: list) -> Work:
    """The useful work of a chunk-lane round: the prompt tokens it wrote at
    ``positions`` -- one pass over the weights, and each token's
    projections and attention over the positions before it."""
    return forward(cfg, [p + 1 for p in positions])
