"""Operations and bytes the served model needs, computed from shapes.

Counts are of the work the model requires, not of what a kernel happens to
do: a matmul of M real rows by a K x N weight is ``2 M K N`` integer
operations whatever rows a kernel pads to, and reads its weight codes once.
Integer operations (every quantized projection and the head) are held
against the chip's int8 peak, attention's bfloat16 operations against its
bf16 peak, and bytes against HBM bandwidth.  What one forward of a model
needs is its architecture's (``kernel_calls`` and ``forward`` of
``bench/archs/<arch>.py``).
"""
from __future__ import annotations

import dataclasses

from bench import spec


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
    """(K, N, code bits) of each call of ``kernel`` in one forward."""
    return spec.arch(cfg).kernel_calls(cfg, kernel)


def kernel_work(cfg: dict, kernel: str, M: int) -> Work:
    """Work of one forward's calls of ``kernel`` at M rows."""
    w = Work()
    for K, N, bits in kernel_calls(cfg, kernel):
        w = w + matmul(M, K, N, bits)
    return w


def forward(cfg: dict, ctx: list) -> Work:
    """One forward of the rows whose context lengths (the position each
    row attends from, plus one) are ``ctx``."""
    return spec.arch(cfg).forward(cfg, ctx)


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
