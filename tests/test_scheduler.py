"""Continuous-batching scheduler: equivalence with static batching, EOS
slot-freeing, per-sequence-position ring addressing, scanned-decode
bit-exactness, no-retrace static shapes, and top-k/top-p sampling."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as A
from repro.models import transformer as T
from repro.serve import Engine, Request, Scheduler, ServeConfig, sample_logits


def _engine(arch="qwen2-7b", max_len=32, **scfg):
    cfg = dataclasses.replace(configs.get_config(arch, smoke=True),
                              compute_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, Engine(cfg, params, ServeConfig(max_len=max_len,
                                                        **scfg))


# ---------------------------------------------------------------------------
# scheduler == static batching (temperature 0)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,S", [("qwen2-7b", 6), ("gemma2-2b", 4),
                                    ("gemma2-2b", 12)])
def test_staggered_continuous_matches_static(arch, S):
    """Continuous batching with staggered admission emits the same tokens
    per request as one-shot static batching — including through gemma's
    SWA ring caches for prompts shorter AND longer than the window."""
    cfg, params, eng = _engine(arch)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, S), 0, cfg.vocab)
    want = eng.generate(prompts, max_new_tokens=5)[:, S:]
    sched = Scheduler(eng, slots=2, chunk=2)
    reqs = [Request(prompt=np.asarray(prompts[i]).tolist(), max_new_tokens=5)
            for i in range(4)]
    sched.submit(reqs[0])
    sched.submit(reqs[1])
    sched.step()                     # first two requests mid-flight...
    sched.submit(reqs[2])            # ...then more arrive
    sched.submit(reqs[3])
    while sched.has_work:
        sched.step()
    for i, r in enumerate(reqs):
        assert r.tokens == np.asarray(want[i]).tolist(), i
        assert r.done and r.finish_reason == "length"


def test_chunked_prefill_matches_static():
    """Chunked admission (prompts split across rounds at prefill_chunk
    granularity) must not change any emitted token, and under backlog the
    chunk lane carries no pad entries (padding waste exactly 1.0)."""
    cfg, params, eng = _engine(prefill_chunk=4)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    want = eng.generate(prompts, max_new_tokens=5)[:, 6:]
    sched = Scheduler(eng, slots=2, chunk=4)
    reqs = [Request(prompt=np.asarray(prompts[i]).tolist(), max_new_tokens=5)
            for i in range(2)]
    sched.run(reqs)
    for i, r in enumerate(reqs):
        assert r.tokens == np.asarray(want[i]).tolist()
    assert sched.padding_waste == 1.0


def test_prompt_bucket_kwarg_is_deprecated_and_ignored():
    """The pre-chunking admission knob warns and changes nothing."""
    cfg, params, eng = _engine()
    want = np.asarray(eng.generate(jnp.asarray([[1, 2, 3, 4]]), 3)[:, 4:])
    req = Request(prompt=[1, 2, 3, 4], max_new_tokens=3)
    with pytest.warns(DeprecationWarning, match="prefill_chunk"):
        sched = Scheduler(eng, slots=1, chunk=2, prompt_bucket="pow2")
    sched.run([req])
    assert req.tokens == want[0].tolist()


# ---------------------------------------------------------------------------
# EOS early-exit frees the slot
# ---------------------------------------------------------------------------

def test_eos_early_exit_frees_slot():
    cfg, params, eng = _engine()
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    want = np.asarray(eng.generate(prompts, max_new_tokens=6)[:, 6:])
    eos = int(want[0, 2])            # req0's greedy stream hits this early
    hit = int(np.argmax(want[0] == eos))       # first occurrence
    sched = Scheduler(eng, slots=1, chunk=2)
    r0 = Request(prompt=np.asarray(prompts[0]).tolist(), max_new_tokens=6,
                 eos_id=eos)
    r1 = Request(prompt=np.asarray(prompts[1]).tolist(), max_new_tokens=6)
    sched.run([r0, r1])
    # r0 stopped at (and including) the first EOS token, under budget
    assert r0.finish_reason == "eos"
    assert r0.tokens == want[0, :hit + 1].tolist() and r0.tokens[-1] == eos
    # the freed slot served r1, whose stream matches static batching
    assert r1.finish_reason == "length"
    assert r1.tokens == want[1].tolist()
    assert all(s is None for s in sched.slots) and not sched.queue


# ---------------------------------------------------------------------------
# per-sequence positions
# ---------------------------------------------------------------------------

def test_decode_attention_per_sequence_ring_positions():
    """SWA ring addressing with a [B] position vector must match per-row
    scalar-position calls (each sequence at its own depth)."""
    B, W, H, D = 3, 8, 2, 16
    key = jax.random.PRNGKey(0)
    p = A.init_attention(key, H * D, H, H, D, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, H * D), jnp.float32)
    ck = jax.random.normal(jax.random.PRNGKey(2), (B, W, H, D), jnp.float32)
    cv = jax.random.normal(jax.random.PRNGKey(3), (B, W, H, D), jnp.float32)
    pos = jnp.asarray([3, 7, 12], jnp.int32)
    y, nk, nv = A.decode_attention(p, x, ck, cv, pos, n_heads=H, n_kv=H,
                                   head_dim=D, window=W, rolling=True,
                                   compute_dtype=jnp.float32)
    for b in range(B):
        yb, nkb, nvb = A.decode_attention(
            p, x[b:b + 1], ck[b:b + 1], cv[b:b + 1], jnp.int32(int(pos[b])),
            n_heads=H, n_kv=H, head_dim=D, window=W, rolling=True,
            compute_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(y[b:b + 1]), np.asarray(yb))
        np.testing.assert_array_equal(np.asarray(nk[b:b + 1]), np.asarray(nkb))
        np.testing.assert_array_equal(np.asarray(nv[b:b + 1]), np.asarray(nvb))


def test_negative_position_is_free_slot_sentinel():
    """A negative per-sequence position masks every key of that row and
    writes only inside its own row — active neighbours are untouched: the
    live row's output and cache are bit-identical whatever the free row
    holds (compared at one batch shape — XLA may order a reduction
    differently at another)."""
    B, Tlen, H, D = 2, 6, 2, 8
    p = A.init_attention(jax.random.PRNGKey(0), H * D, H, H, D,
                         dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, H * D), jnp.float32)
    ck = jax.random.normal(jax.random.PRNGKey(2), (B, Tlen, H, D), jnp.float32)
    cv = jax.random.normal(jax.random.PRNGKey(3), (B, Tlen, H, D), jnp.float32)
    pos = jnp.asarray([2, -1], jnp.int32)      # row 1 is a free slot
    y, nk, nv = A.decode_attention(p, x, ck, cv, pos, n_heads=H, n_kv=H,
                                   head_dim=D, compute_dtype=jnp.float32)
    # same live row, different free-row token and cache contents
    x2 = x.at[1].set(100.0 * x[1])
    ck2 = ck.at[1].set(-ck[1])
    cv2 = cv.at[1].set(cv[1] + 7.0)
    y2, nk2, nv2 = A.decode_attention(p, x2, ck2, cv2, pos, n_heads=H,
                                      n_kv=H, head_dim=D,
                                      compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y[:1]), np.asarray(y2[:1]))
    np.testing.assert_array_equal(np.asarray(nk[:1]), np.asarray(nk2[:1]))
    np.testing.assert_array_equal(np.asarray(nv[:1]), np.asarray(nv2[:1]))
    assert np.isfinite(np.asarray(y)).all()    # free row: garbage but finite


# ---------------------------------------------------------------------------
# scanned decode == python-loop decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_scanned_decode_matches_python_loop(temperature):
    cfg, params, eng = _engine(temperature=temperature)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, cfg.vocab)
    a = eng.generate(prompts, max_new_tokens=6, use_scan=True)
    b = eng.generate(prompts, max_new_tokens=6, use_scan=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# static shapes: no retrace after warmup
# ---------------------------------------------------------------------------

def test_no_retrace_across_staggered_admissions():
    """After warmup (one chunk-carrying round + one pure-decode round) no
    new traces appear for any later prompt length or admission pattern —
    the unified step's shapes are fully static."""
    cfg, params, eng = _engine(max_len=48, prefill_chunk=4)
    sched = Scheduler(eng, slots=2, chunk=2)
    sched.submit(Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=6))
    while sched.has_work:
        sched.step()                 # warmup: chunked admission + decode
    C = eng.prefill_chunk
    assert set(eng._step_fns) == {(C, 2, True, False), (0, 2, True, False)}
    sizes = {k: fn._cache_size() for k, fn in eng._step_fns.items()}
    assert all(v == 1 for v in sizes.values())
    for p in ([7, 7, 7], [5, 4, 3, 2, 1], [1, 2, 3, 4, 5, 6, 7, 8]):
        sched.submit(Request(prompt=p, max_new_tokens=5))
    while sched.has_work:
        sched.step()
    assert {k: fn._cache_size() for k, fn in eng._step_fns.items()} == sizes


# ---------------------------------------------------------------------------
# sampling: top-k / top-p
# ---------------------------------------------------------------------------

def test_sample_logits_temperature_zero_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    got = sample_logits(logits, jax.random.PRNGKey(1), 0.0, 0, 1.0)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_logits_topk1_and_tiny_topp_are_greedy():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    greedy = np.asarray(jnp.argmax(logits, -1))
    for key in range(3):
        k1 = sample_logits(logits, jax.random.PRNGKey(key), 1.0, 1, 1.0)
        np.testing.assert_array_equal(np.asarray(k1), greedy)
        p0 = sample_logits(logits, jax.random.PRNGKey(key), 1.0, 0, 1e-6)
        np.testing.assert_array_equal(np.asarray(p0), greedy)


def test_sample_logits_topk_support():
    """Sampled tokens always come from the k highest logits."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 32))
    top5 = np.asarray(jnp.argsort(-logits, axis=-1)[:, :5])
    for key in range(8):
        got = np.asarray(sample_logits(logits, jax.random.PRNGKey(key),
                                       1.5, 5, 1.0))
        for b in range(2):
            assert got[b] in top5[b]


def test_sample_logits_per_row_mix():
    """Per-slot sampling params: greedy rows stay exact argmax while
    sampled rows draw from their own filtered distribution."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    temp = jnp.asarray([0.0, 1.0, 0.0])
    got = np.asarray(sample_logits(logits, jax.random.PRNGKey(7), temp,
                                   jnp.asarray([0, 1, 0]), 1.0))
    greedy = np.asarray(jnp.argmax(logits, -1))
    np.testing.assert_array_equal(got, greedy)  # row 1 top_k=1 -> also argmax


def test_scheduler_per_request_sampling_flags():
    """A temperature>0 top-k request runs alongside greedy requests; its
    tokens stay inside the model's top-k support at every step."""
    cfg, params, eng = _engine(max_len=32)
    g_req = Request(prompt=[1, 2, 3, 4], max_new_tokens=4)
    s_req = Request(prompt=[5, 6, 7, 8], max_new_tokens=4, temperature=1.0,
                    top_k=3)
    sched = Scheduler(eng, slots=2, chunk=2)
    sched.run([g_req, s_req])
    want = np.asarray(eng.generate(jnp.asarray([[1, 2, 3, 4]]), 4)[:, 4:])
    assert g_req.tokens == want[0].tolist()      # greedy row unaffected
    assert len(s_req.tokens) == 4


def test_recurrent_state_mixed_length_admission_matches_static():
    """SSM/RWKV recurrent states are not pad-invariant: mixed-length
    requests must still decode exactly as their own static runs (the
    scheduler admits them unpadded, in equal-length groups)."""
    cfg = configs.get_config("rwkv6-1.6b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig(max_len=24))
    assert eng.has_recurrent_state
    p5 = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab)
    p7 = jax.random.randint(jax.random.PRNGKey(2), (1, 7), 0, cfg.vocab)
    want5 = np.asarray(eng.generate(p5, max_new_tokens=4)[:, 5:])
    want7 = np.asarray(eng.generate(p7, max_new_tokens=4)[:, 7:])
    assert eng.requires_monolithic_admission  # chunking can't rebuild state
    sched = Scheduler(eng, slots=2, chunk=2)
    r5 = Request(prompt=np.asarray(p5[0]).tolist(), max_new_tokens=4)
    r7 = Request(prompt=np.asarray(p7[0]).tolist(), max_new_tokens=4)
    sched.run([r5, r7])
    assert r5.tokens == want5[0].tolist()
    assert r7.tokens == want7[0].tolist()


def test_long_prompt_admits_over_many_rounds():
    """A prompt much longer than prefill_chunk admits across several rounds
    and still matches its static run exactly."""
    cfg, params, eng = _engine(max_len=48, prefill_chunk=4)
    prompt = list(range(1, 34))                # len 33 -> 9 chunk rounds
    want = np.asarray(eng.generate(jnp.asarray([prompt]), 6)[:, 33:])
    req = Request(prompt=prompt, max_new_tokens=6)
    sched = Scheduler(eng, slots=2, chunk=3)
    sched.run([req])
    assert req.tokens == want[0].tolist()
    assert sched.stats["admission_rounds"] >= 9


def test_freed_slot_restores_greedy_fast_path():
    """A finished sampling request must not leave its slot's sampling
    mirrors behind — later all-greedy rounds take the argmax-only decode
    variant again."""
    cfg, params, eng = _engine(max_len=32)
    sched = Scheduler(eng, slots=2, chunk=2)
    sched.run([Request(prompt=[1, 2, 3, 4], max_new_tokens=3,
                       temperature=0.9, top_k=4)])
    assert all(t <= 0.0 and k == 0 and p >= 1.0 for t, k, p in
               zip(sched._temp_h, sched._topk_h, sched._topp_h))
    want = np.asarray(eng.generate(jnp.asarray([[5, 6, 7, 8]]), 4)[:, 4:])
    req = Request(prompt=[5, 6, 7, 8], max_new_tokens=4)
    sched.run([req])
    assert req.tokens == want[0].tolist()


# ---------------------------------------------------------------------------
# degenerate requests must not pin their slot
# ---------------------------------------------------------------------------

def test_prompt_ending_in_eos_frees_slot():
    """A prompt that already ends in the EOS token decodes normally (the
    trailing EOS is prompt context, not an emission) and its slot frees on
    retirement — it must not wedge the pool."""
    cfg, params, eng = _engine()
    eos = 7
    sched = Scheduler(eng, slots=1, chunk=2)
    r0 = Request(prompt=[1, 2, 3, eos], max_new_tokens=3, eos_id=eos)
    r1 = Request(prompt=[4, 5, 6, 8], max_new_tokens=3)
    done = sched.run([r0, r1], max_rounds=16)
    assert len(done) == 2 and r0.done and r1.done
    assert 1 <= len(r0.tokens) <= 3
    if r0.finish_reason == "eos":
        assert r0.tokens[-1] == eos
    else:
        assert r0.finish_reason == "length" and len(r0.tokens) == 3
    assert all(s is None for s in sched.slots) and not sched.queue


def test_budget_zero_request_finishes_at_admission():
    """budget=0 finishes at admission without emitting and without ever
    occupying the slot — the next queued request runs immediately (before
    this fix the slot stayed RUNNING forever: ``remaining`` went negative
    and the retirement check never fired)."""
    cfg, params, eng = _engine()
    want = np.asarray(eng.generate(jnp.asarray([[5, 6, 7, 8]]), 3)[:, 4:])
    sched = Scheduler(eng, slots=1, chunk=2)
    r0 = Request(prompt=[1, 2, 3, 4], max_new_tokens=0)
    r1 = Request(prompt=[5, 6, 7, 8], max_new_tokens=3)
    done = sched.run([r0, r1], max_rounds=16)
    assert len(done) == 2
    assert r0.done and r0.tokens == [] and r0.finish_reason == "length"
    # the freed slot served r1 with unchanged numerics
    assert r1.tokens == want[0].tolist()
    assert all(s is None for s in sched.slots) and not sched.queue


def test_budget_zero_and_one_mixed_with_normal_requests():
    """A pile of degenerate budgets drains in bounded rounds alongside a
    normal stream (regression guard on the admission fast-finish path)."""
    cfg, params, eng = _engine()
    sched = Scheduler(eng, slots=2, chunk=2)
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=b)
            for b in (0, 1, 0, 4, 1, 0)]
    done = sched.run(reqs, max_rounds=32)
    assert len(done) == len(reqs)
    for r, b in zip(reqs, (0, 1, 0, 4, 1, 0)):
        assert len(r.tokens) == b and r.done


def test_request_streaming_callback():
    cfg, params, eng = _engine()
    seen = []
    req = Request(prompt=[1, 2, 3, 4], max_new_tokens=4,
                  on_token=lambda r, t: seen.append(t))
    Scheduler(eng, slots=1, chunk=2).run([req])
    assert seen == req.tokens and len(seen) == 4
