"""Drive one cell through ``make_engine`` -> ``Scheduler``: set-up, the
measured window, and what the window leaves for the check and the metrics.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock):
``bench.round`` around each ``Scheduler.step`` and ``bench.clients``
around the closed loop's own bookkeeping between rounds.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import traffic


@dataclasses.dataclass
class Round:
    t0: float                    # host clock at the call of Scheduler.step
    t1: float                    # host clock when it returned
    prefill: int                 # chunk-lane prompt tokens it carried
    chunk_lane: bool             # the round ran the chunk lane (C > 0)
    emitted: int                 # tokens delivered to requests
    decoding: list               # context length, at the round's start, of
                                 # each slot in the decode lane
    prompt_pos: list             # positions the chunk lane wrote


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    rounds: list
    gaps: list                   # seconds between successive tokens of a
                                 # request, both delivered in the window
    requests: list               # every request the traffic sent
    mono: dict                   # id(request) -> prompt positions admitted
                                 # by the batched prefill
    stats0: dict
    stats1: dict


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def set_up(bench, eng, mix: dict, seed: int, vocab: int):
    """Warm every program the window runs, then send the requests the
    traffic sends before the window.  Returns (scheduler, traffic,
    requests, prompt positions admitted by batched prefill)."""
    from repro.serve import Request, Scheduler
    s = mix["serve"]
    sched = Scheduler(eng, slots=int(s["slots"]),
                      chunk=int(s["decode_chunk"]))
    gen = traffic.load(bench, mix, seed, vocab)
    # one chunk-lane round and one decode-only round
    warm = sched.submit(Request(**gen.warm(eng.prefill_chunk)))
    while not warm.done:
        sched.step()
    sched.finished.clear()
    reqs = [sched.submit(Request(**kw)) for kw in gen.first()]
    mono = {}
    if mix["start"] == "batched":
        # every first prompt in one exact-length batched prefill (the
        # engine's monolithic admission; the scheduler has no public entry
        # for it)
        lens = {len(r.prompt) for r in reqs}
        if len(lens) != 1 or len(reqs) > int(s["slots"]):
            raise ValueError("a batched start needs one prompt length and "
                             "no more requests than slots")
        sched._admit()
        for r in reqs:
            mono[id(r)] = len(r.prompt)
    elif mix["start"] != "queued":
        raise ValueError(f"unknown start {mix['start']!r}")
    return sched, gen, reqs, mono


def measure(sched, gen, reqs: list, mono: dict, seconds: float) -> Window:
    """Rounds from now until the first round boundary at or after
    ``seconds``; after each round the traffic sends what it sends."""
    from repro.serve import Request
    last = {id(r): None for r in reqs}      # host time of latest delivery
    seen = {id(r): len(r.tokens) for r in reqs}
    done = {id(r) for r in reqs if r.done}
    rounds, gaps = [], []
    stats0 = dict(sched.stats)
    t0 = time.perf_counter()
    for r in reqs:
        if r.tokens:
            last[id(r)] = t0
    while True:
        decoding = [len(r.prompt) + len(r.tokens)
                    for s, r in enumerate(sched.slots) if r is not None
                    and sched._progress[s] >= sched._target[s]]
        progress = list(sched._progress)
        before = dict(sched.stats)
        a = time.perf_counter()
        with _annotate("bench.round"):
            emitted = sched.step()
        b = time.perf_counter()
        with _annotate("bench.clients"):
            prefill = sched.stats["admitted_tokens"] - before[
                "admitted_tokens"]
            chunk_lane = sched.stats["admission_rounds"] > before[
                "admission_rounds"]
            written = [p for s, p1 in enumerate(sched._progress)
                       for p in range(progress[s], p1)]
            rounds.append(Round(a, b, prefill, chunk_lane, emitted,
                                decoding, written))
            finished = 0
            for r in reqs:
                n = len(r.tokens)
                new = n - seen[id(r)]
                if new > 0:
                    if last[id(r)] is not None:
                        gaps.append(b - last[id(r)])
                    else:
                        new -= 1                # first token: not a gap
                    gaps.extend([0.0] * new)
                    last[id(r)] = b
                    seen[id(r)] = n
                if r.done and id(r) not in done:
                    done.add(id(r))
                    finished += 1
            for kw in gen.after_round(b - t0, finished):
                nr = sched.submit(Request(**kw))
                reqs.append(nr)
                last[id(nr)], seen[id(nr)] = None, 0
        if b - t0 >= seconds:
            break
    return Window(t0, b, rounds, gaps, reqs, mono, stats0,
                  dict(sched.stats))


# -- what the check compares ---------------------------------------------

def sample_requests(win: Window, n: int, seed: int) -> list:
    """Up to ``n`` requests that hold served tokens, drawn from the seed,
    always with the one that holds the most."""
    have = [r for r in win.requests if r.tokens]
    if not have:
        return []
    longest = max(have, key=lambda r: (len(r.prompt) + len(r.tokens),
                                       -win.requests.index(r)))
    rest = [r for r in have if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC4EC])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def sample_slots(sched, n: int, seed: int) -> list:
    """(slot, request, positions written) of up to ``n`` occupied slots
    whose cache rows hold prompt positions, drawn from the seed."""
    rows = []
    for s, r in enumerate(sched.slots):
        if r is None:
            continue
        if sched._progress[s] < sched._target[s]:
            written = sched._progress[s]
        else:
            written = len(r.prompt) + len(r.tokens) - 1
        if written > 0:
            rows.append((s, r, written))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5107])
    pick = sorted(rng.permutation(len(rows))[:n])
    return [rows[i] for i in pick]


def cache_rows(sched, slots: list, P: int) -> tuple:
    """Device copies of the K and V rows the program wrote for ``slots``,
    the first ``P`` positions of each: [n_layers, len(slots), P, Hkv, D]
    in the cache's dtype."""
    import jax.numpy as jnp
    cache = sched.cache[0]
    idx = jnp.asarray(slots, jnp.int32)
    return tuple(cache[key][:, idx, :P] for key in ("k", "v"))
