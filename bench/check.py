"""What decides ``correct``: the numbers compared with the plain reference.

A check file (``bench/checks/<workload>.json``) names the numbers a cell
compares, each with its limit and the readings the limit was set from, how
many requests or slots the sample draws, and its ``controls``: the
reference one precision step below the configuration, put in the
program's place (``bench.reference``), which some number must fail:

    logit_gap   served tokens: over a sample of requests the window served
                (always with the one holding the most tokens), the widest
                gap by which a served token's reference logit lies below
                the reference's best logit at that position.  Greedy
                serving puts the best token first, so a sound run reads
                only rounding; a wrong token, cache or kernel reads O(1).
    kv_gap      cache rows: over a sample of occupied slots, the largest
                |K - K_ref| and |V - V_ref| the program wrote for the
                positions it has fed, relative to the largest |K_ref| or
                |V_ref| of the same layer.  Rows a batched prefill wrote
                are compared with the reference's batched form, the rest
                with its one-position-at-a-time form.
    kv_gap_layer0  the same, of the first layer alone: its K/V rows come
                straight from the embedding through one norm and one
                projection, so rounding differences that later layers
                amplify have not yet grown, while a K/V cache held in a
                lower precision shows at full size.

Both run the reference over the same prompts and served tokens, teacher
forced, once the window has closed and the program's state is freed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


@dataclasses.dataclass
class Sample:
    """Copies of what the window produced, taken before the program's
    state is freed."""
    requests: list       # (prompt, served tokens, positions batch-prefilled)
    slots: list          # (tokens fed so far, positions batch-prefilled)
    kv: tuple = ()       # the slots' K and V rows, device arrays
                         # [n_layers, slots, P, Hkv, D], P as _pad gives


def take_sample(win, sched, check: dict, seed: int) -> Sample:
    from bench import serve
    n = int(check["sample"])
    reqs = []
    if "logit_gap" in check["numbers"]:
        for r in serve.sample_requests(win, n, seed):
            reqs.append((list(r.prompt), list(r.tokens),
                         win.mono.get(id(r), 0)))
    slots, kv = [], ()
    if any(name.startswith("kv_gap") for name in check["numbers"]):
        rows = serve.sample_slots(sched, n, seed)
        for s, r, written in rows:
            seq = (list(r.prompt) + list(r.tokens))[:written]
            slots.append((seq, win.mono.get(id(r), 0)))
        if rows:
            P = _pad([s for s, _ in slots], 1, sched.engine.scfg.max_len
                     ).shape[1]
            kv = serve.cache_rows(sched, [s for s, _, _ in rows], P)
    return Sample(reqs, slots, kv)


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad(seqs: list, rows: int, T: int) -> np.ndarray:
    """Right-padded [rows, L] token block; L rounds up to a multiple of
    128 (at most T) so the reference compiles once per cell, not per
    run."""
    L = min(_up(max(len(s) for s in seqs), 128), T)
    out = np.zeros((max(rows, len(seqs)), L), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def _served_logits(cfg, seed, reqs, rows, T, lower, score_fn):
    """Run the reference over each group of requests that share a batched
    prefix; yields (group, reference output)."""
    groups: dict = {}
    for req in reqs:
        groups.setdefault(req[2], []).append(req)
    for mono, group in sorted(groups.items()):
        tokens = _pad([p + t[:-1] for p, t, _ in group], rows, T)
        n = _up(max(len(t) for _, t, _ in group), 64)
        at = np.zeros((tokens.shape[0], n), np.int32)
        score = np.zeros((tokens.shape[0], n), np.int32)
        for i, (p, t, _) in enumerate(group):
            at[i, :len(t)] = len(p) - 1 + np.arange(len(t))
            at[i, len(t):] = len(p) - 1
            score[i] = score_fn(i, group, n)
        out = reference.run(cfg, seed, tokens, mono=mono, T=T, at=at,
                            score=score, lower=lower)
        yield group, out


def logit_gap(cfg: dict, seed: int, sample: Sample, rows: int, T: int,
              control: str = ""):
    """Widest gap over the sampled served tokens; with ``control`` (a
    ``reference.run`` precision step) the tokens scored are those that
    control puts first instead."""
    if not sample.requests:
        return None
    worst = 0.0

    def served(i, group, n):
        t = group[i][1]
        return np.asarray(t + [t[-1]] * (n - len(t)), np.int32)

    ctrl = {}
    if control:
        for group, out in _served_logits(cfg, seed, sample.requests, rows,
                                         T, control, served):
            for i, req in enumerate(group):
                ctrl[id(req)] = out["argmax"][i]
    pick = (lambda i, group, n: ctrl[id(group[i])]) if control else served
    for group, out in _served_logits(cfg, seed, sample.requests, rows, T,
                                     "", pick):
        for i, (_, t, _) in enumerate(group):
            gap = out["best"][i, :len(t)] - out["got"][i, :len(t)]
            worst = max(worst, float(gap.max()))
    return worst


@jax.jit
def _rel_gap(got, want, n):
    """Largest |got - want| over each slot's first ``n`` rows, relative to
    the largest |want| there, per layer: [n_layers, slots]."""
    P = got.shape[2]
    keep = (jnp.arange(P)[None, :] < n[:, None])[None, :, :, None, None]
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    num = jnp.max(jnp.where(keep, jnp.abs(g - w), 0.0), axis=(2, 3, 4))
    den = jnp.max(jnp.where(keep, jnp.abs(w), 0.0), axis=(2, 3, 4))
    return num / jnp.maximum(den, 1e-30)


def kv_gaps(cfg: dict, seed: int, sample: Sample, rows: int, T: int,
            control: str = ""):
    """{"k": [n_layers], "v": [n_layers]}: the largest relative difference
    of each layer's K and V rows over the sampled slots; with ``control``
    that lower-precision reference stands in for the program."""
    if not sample.slots:
        return None
    worst = {}
    groups: dict = {}
    for i, (_, mono) in enumerate(sample.slots):
        groups.setdefault(mono, []).append(i)
    for mono, idx in sorted(groups.items()):
        seqs = [sample.slots[i][0] for i in idx]
        tokens = _pad(seqs, rows, T)
        P = tokens.shape[1]
        zero = np.zeros((tokens.shape[0], 1), np.int32)
        ref = reference.run(cfg, seed, tokens, mono=mono, T=T, at=zero,
                            score=zero, kv_rows=P)
        if control:
            got = reference.run(cfg, seed, tokens, mono=mono, T=T, at=zero,
                                score=zero, kv_rows=P, lower=control)
            got = [got[name][:, :len(idx)] for name in ("k", "v")]
        else:
            sel = jnp.asarray(idx)
            got = [kv[:, sel, :P] for kv in sample.kv]
        n = jnp.asarray([len(s) for s in seqs], jnp.int32)
        for name, g in zip(("k", "v"), got):
            gap = np.asarray(_rel_gap(g, ref[name][:, :len(idx)], n)).max(
                axis=1)
            worst[name] = np.maximum(worst.get(name, gap), gap)
    return worst


def kv_gap_by_layer(cfg: dict, seed: int, sample: Sample, rows: int,
                    T: int, control: str = ""):
    """The larger of the K and V gaps of each layer, [n_layers]."""
    gaps = kv_gaps(cfg, seed, sample, rows, T, control)
    return None if gaps is None else np.maximum(gaps["k"], gaps["v"])


def kv_gap(cfg: dict, seed: int, sample: Sample, rows: int, T: int,
           control: str = ""):
    """Largest relative K/V difference over the sampled slots' rows."""
    gap = kv_gap_by_layer(cfg, seed, sample, rows, T, control)
    return None if gap is None else float(gap.max())


def kv_gap_layer0(cfg: dict, seed: int, sample: Sample, rows: int, T: int,
                  control: str = ""):
    """The same, of the first layer's rows alone."""
    gap = kv_gap_by_layer(cfg, seed, sample, rows, T, control)
    return None if gap is None else float(gap[0])


NUMBERS = {"logit_gap": logit_gap, "kv_gap": kv_gap,
           "kv_gap_layer0": kv_gap_layer0}


def compare(cfg: dict, seed: int, sample: Sample, check: dict, T: int,
            control: str = "") -> dict:
    """{name: (number or None, limit)} for every number the check lists;
    with ``control`` (one of the check's ``controls``) that control stands
    in for the program."""
    rows = int(check["sample"])
    return {name: (NUMBERS[name](cfg, seed, sample, rows, T, control),
                   spec["limit"])
            for name, spec in check["numbers"].items()}


def verdict(results: dict) -> bool:
    """A number that is missing, not finite, or above its limit fails."""
    return all(v is not None and lim is not None and np.isfinite(v)
               and v <= lim for v, lim in results.values())
