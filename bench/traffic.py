"""Traffic: what a mix file asks for, and the parts every kind shares.

A mix file (``bench/traffic/<name>.json``) holds only parameters.  Its
``kind`` names the module that sends its requests,
``bench/traffic_kinds/<kind>.py``, which defines ``make(mix, seed, vocab)``
returning a ``Traffic``.  Keys every kind reads:

    kind           the traffic kind
    start          "batched": the requests sent before the window are
                   admitted in one batched prefill (the engine's monolithic
                   admission, one exact prompt length); "queued": the
                   window starts with them queued
    serve          slots and decode_chunk (decode tokens per round) for the
                   ``Scheduler``; every other key is a ``ServeConfig``
                   field (max_len, prefill_chunk, paged, spec_decode, ...)

A request is the keyword arguments of the program's ``Request``
(``prompt``, ``max_new_tokens``, and any per-request sampling keys).
Token ids are uniform over the vocabulary, drawn from the seed.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths spread evenly over [lo, hi] inclusive."""
    lo, hi = int(lo), int(hi)
    return lo + ((np.arange(n) + 0.5) * (hi - lo + 1) / n).astype(np.int64)


class Traffic:
    """Requests of one mix, drawn from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                          int(seed) >> 32, 0x7E57])

    def request(self, prompt_len: int, max_new: int, **extra) -> dict:
        prompt = self.rng.integers(0, self.vocab, int(prompt_len))
        return {"prompt": prompt.tolist(), "max_new_tokens": int(max_new),
                **extra}

    def warm(self, prefill_chunk: int) -> dict:
        """A set-up request that runs one round with a full chunk lane and
        one decode-only round: a ``prefill_chunk``-token prompt whose last
        token samples the first output, then ``2 * decode_chunk`` more."""
        return self.request(prefill_chunk,
                            1 + 2 * int(self.mix["serve"]["decode_chunk"]))

    def first(self) -> list:
        """The requests sent before the window opens."""
        raise NotImplementedError

    def after_round(self, elapsed_s: float, finished: int) -> list:
        """The requests sent after a round that ended ``elapsed_s`` into
        the window and in which ``finished`` of this traffic's requests
        finished."""
        raise NotImplementedError


def load(bench, mix: dict, seed: int, vocab: int) -> Traffic:
    """The traffic of ``mix``, by the kind file under the benchmark tree
    ``bench``."""
    return spec.load_module(bench, "traffic_kinds", mix["kind"]).make(
        mix, seed, vocab)
