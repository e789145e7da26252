"""Closed loop: each of ``clients`` clients holds one request at a time
and sends its next the moment the previous one finishes.

Mix keys (besides those of ``bench/traffic.py``):

    clients        number of clients; each sends one request before the
                   window
    prompt_len     [lo, hi] inclusive
    output_len     [lo, hi] inclusive

Requests go out in waves of ``clients``: every wave holds the same multiset
of (prompt, output) lengths, a stratified grid over the two ranges, in a
seed-dependent order.  So seeds change which tokens are served and in which
order, never how much work there is.
"""
import numpy as np

from bench.traffic import Traffic, grid


class ClosedLoop(Traffic):
    def __init__(self, mix: dict, seed: int, vocab: int):
        super().__init__(mix, seed, vocab)
        self.clients = int(mix["clients"])
        self.wave: list = []

    def next(self) -> dict:
        if not self.wave:
            n = self.clients
            # one fixed pairing of the two grids; the seed orders the pairs
            pairs = list(zip(
                grid(*self.mix["prompt_len"], n),
                grid(*self.mix["output_len"], n)[
                    np.random.default_rng(0).permutation(n)]))
            self.wave = [pairs[i] for i in self.rng.permutation(n)]
        p_len, o_len = self.wave.pop(0)
        return self.request(p_len, o_len)

    def first(self) -> list:
        return [self.next() for _ in range(self.clients)]

    def after_round(self, elapsed_s: float, finished: int) -> list:
        return [self.next() for _ in range(finished)]


def make(mix: dict, seed: int, vocab: int) -> ClosedLoop:
    return ClosedLoop(mix, seed, vocab)
