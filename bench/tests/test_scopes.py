"""The reduction of the serving program's own names in a profiler trace
(``bench/scopes.py``): host phases a round, time by scope, the HLO scope
join, idle gaps labelled by the innermost span.  No TPU library is used."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import scopes

STEP = "jit(run)/{}/while/body/closed_call/{}"
ONEHOT = ("%closed_call.177 = bf16[8,3584]{1,0:T(8,128)(2,1)S(1)} custom-call("
          "u8[8,18944]{1,0:T(8,128)(4,1)S(1)} %fusion.277, s32[16,16]{1,0"
          ":T(8,128)S(1)} %get-tuple-element.3442), custom_call_target="
          "\"tpu_custom_call\"")
KV_WRITE = ("%dynamic-update-slice.3 = bf16[16,2048,4,128]{3,2,1,0} "
            "dynamic-update-slice(bf16[16,2048,4,128]{3,2,1,0} %p, "
            "bf16[16,1,4,128]{3,2,1,0} %k)")
CACHE_COPY = ("%copy.121 = bf16[28,16,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)} "
              "copy(bf16[28,16,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)} %gte)")
SMALL_COPY = "%copy.5 = bf16[16,4096]{1,0} copy(bf16[16,4096]{1,0} %p)"
CACHE = {(28, 16, 2048, 4, 128), (16, 2048, 4, 128)}


def _recorded():
    """Two traced rounds of the program: a chunk-lane round (chunk lane,
    then decode lane with a named one-hot kernel) and a decode-only round
    (a KV write, a whole-cache copy the compiler put in the lane, sampling,
    one op no name claims), each round's host phases inside it."""
    ops = [("%fusion.1 = bf16[8,3584]{1,0} fusion(bf16[8]{0} %p)", 200, 400),
           ("%fusion.2 = bf16[8,3584]{1,0} fusion(bf16[8]{0} %p)", 400, 700),
           (ONEHOT, 700, 900),
           (KV_WRITE, 1200, 1300), (CACHE_COPY, 1300, 1400),
           ("%fusion.9 = s32[16]{0} fusion(f32[16,512]{1,0} %p)", 1400, 1500),
           (SMALL_COPY, 1500, 1550)]
    names = [(STEP.format("chunk_lane", "attend/dot_general"), ""),
             (STEP.format("decode_lane", "attend/dot_general"), ""),
             (STEP.format("decode_lane", "lutmul_onehot_fused/pallas_call"),
              "lutmul_onehot_fused"),
             (STEP.format("decode_lane", "kv_write/dynamic_update_slice"),
              ""),
             ("jit(run)/decode_lane/while", ""),
             (STEP.format("decode_lane", "sample/argmax"), ""),
             ("", "")]
    spans = [("bench.round", 100, 1000), ("serve.step", 110, 990),
             ("serve.admin", 110, 120), ("serve.assemble", 120, 150),
             ("serve.dispatch", 150, 200), ("serve.wait", 200, 950),
             ("serve.deliver", 950, 990), ("bench.clients", 1000, 1150),
             ("bench.round", 1150, 1600), ("serve.step", 1155, 1595),
             ("serve.admin", 1155, 1160), ("serve.assemble", 1160, 1170),
             ("serve.dispatch", 1170, 1200), ("serve.wait", 1200, 1560),
             ("serve.deliver", 1560, 1590)]
    return scopes.ScopedTrace({"/device:TPU:0": ops}, spans,
                              {"/device:TPU:0": names})


def test_host_phases_a_round():
    t = _recorded()
    per = scopes.phase_ms(t, t.rounds)
    assert [sum(r[p] for p in scopes.HOST_PHASES) for r in per] == [
        pytest.approx((10 + 30 + 50 + 40) / 1e6),
        pytest.approx((5 + 10 + 30 + 30) / 1e6)]
    assert per[0]["serve.wait"] == pytest.approx(750 / 1e6)
    s = scopes.summary(t)
    assert s["rounds"] == 2 and s["host_ms"] == [
        pytest.approx(130 / 1e6), pytest.approx(75 / 1e6)]
    assert s["phase_cover"] == pytest.approx(min(880 / 900, 435 / 450))
    assert t.steps == [(110, 990), (1155, 1595)]


@pytest.mark.parametrize("what,keep,want", [
    # round 1 is busy 200..900, round 2 1200..1550; the decode lane holds
    # 400..900 and 1200..1500
    ("decode lane", lambda n, s: scopes.under(s, "decode_lane"),
     [500, 300]),
    # round 2 is busy 1200..1550: the KV write and the whole-cache copy
    ("KV cache", scopes.cache_op(CACHE), [0, 200]),
    ("no name", lambda n, s: not scopes.scoped(s), [0, 50]),
    ("kernel", lambda n, s: bool(s[1]), [200, 0])])
def test_device_time_by_name(what, keep, want):
    t = _recorded()
    assert scopes.busy_each(t, keep, t.rounds) == want
    assert scopes.share(t, t.rounds, keep) == pytest.approx(
        100 * sum(want) / (700 + 350))


def test_idle_gap_label_is_the_innermost_span():
    t = _recorded()
    # 900..1200 under the clients; 100..200 in the first round's dispatch
    # (inside serve.step, inside bench.round); 1550..1600 in the second
    # round's delivery
    assert scopes.idle_gaps(t, 100, 1600) == [
        ("bench.clients", 300 / 1e9), ("serve.dispatch", 100 / 1e9),
        ("serve.deliver", 50 / 1e9)]
    assert scopes.innermost([("a", 0, 10), ("b", 0, 5)], 2) == "b"
    assert scopes.innermost([("a", 0, 10)], 12) == "no host span"


def test_scope_paths_and_kernel_names():
    path = STEP.format("decode_lane", "lutmul_tmac/pallas_call")
    assert scopes.op_scope(path) == (path, "lutmul_tmac")
    assert scopes.op_scope("") == ("", "")
    for unnamed in ("jit(run)/decode_lane/closed_call/pallas_call",
                    "jit(run)/jit(_matmul)/pallas_call"):
        assert scopes.op_scope(unnamed) == (unnamed, "")
    assert scopes.under((path, ""), "decode_lane")
    assert not scopes.under(("jit(run)/decode_lanes/x", ""), "decode_lane")
    assert scopes.scoped(("", "lutmul_tmac"))
    assert not scopes.scoped(("jit(run)/while/body/add", ""))


def test_scopes_join_the_trace_to_its_programs_hlo(tmp_path):
    """A profiled program's compiled HLO, as the profiler keeps it on the
    metadata plane, names each instruction's scope; instructions the
    compiler added (a fusion's parameters) take their caller's; ops join
    it through the program whose module event holds them."""
    @jax.jit
    def f(x):
        def body(c, _):
            with jax.named_scope("kv_write"):
                return c.at[0].set(c[1] * 2), None
        with jax.named_scope("decode_lane"):
            x, _ = jax.lax.scan(body, x, jnp.arange(3))
        with jax.named_scope("sample"):
            return jnp.sin(x) @ x.T

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw, = [p.read_bytes() for p in tmp_path.rglob("*.xplane.pb")]
    names = scopes.program_op_names(raw)
    prog, = [k for k in names if k.startswith("jit_f(")]
    ops = names[prog]
    fused = [i for i in ops if i.startswith("param_")]
    assert fused and all(ops[i].startswith("jit(f)/") for i in fused)
    assert any("/decode_lane/" in p and "/kv_write/" in p
               for p in ops.values())
    assert any(p.startswith("jit(f)/sample/") for p in ops.values())
    inst = next(i for i, p in ops.items() if "/kv_write/" in p)
    modules = [(100, 200, prog), (300, 400, "jit_g(9)")]
    got = scopes.join_scopes(
        [(f"%{inst} = f32[64,64]{{1,0}} fusion(f32[64]{{0}} %p)", 150),
         (f"%{inst} = (f32[8]{{0}}, f32[8]{{0}}) fusion(f32[8]{{0}} %p)",
          180),
         (f"%{inst} = f32[64,64]{{1,0}} fusion(f32[64]{{0}} %p)", 350),
         ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 250)], modules, names)
    assert got == [(ops[inst], "")] * 2 + [("", "")] * 2


LOOP_HLO = """
HloModule m

cond {
  p = (s32[], f32[8]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  n = s32[] constant(3)
  ROOT lt = pred[] compare(i, n), direction=LT, metadata={op_name="jit(run)/decode_lane/while/cond/lt"}
}

body {
  p = (s32[], f32[8]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  x = f32[8] get-tuple-element(p), index=1
  c = f32[8] copy(x)
  one = s32[] constant(1)
  j = s32[] add(i, one), metadata={op_name="jit(run)/decode_lane/while/body/add"}
  ROOT t = (s32[], f32[8]) tuple(j, c)
}

ENTRY main {
  x = f32[8] parameter(0)
  z = s32[] constant(0)
  t = (s32[], f32[8]) tuple(z, x)
  w = (s32[], f32[8]) while(t), condition=cond, body=body, metadata={op_name="jit(run)/decode_lane/while"}
  g = f32[8] get-tuple-element(w), index=1
  ROOT y = f32[8] copy(g)
}
"""


def test_compiler_added_ops_take_their_loops_scope():
    # a copy the compiler put in a loop body has no op_name of its own:
    # it takes the while loop's (found through ``body=``, after
    # ``condition=``); one outside every loop keeps none
    from jax._src.lib import _jax
    names = scopes.hlo_op_names(_jax.hlo_module_from_text(
        LOOP_HLO).as_serialized_hlo_module_proto())
    assert names["c"] == "jit(run)/decode_lane/while"
    assert names["j"] == "jit(run)/decode_lane/while/body/add"
    assert names["lt"] == "jit(run)/decode_lane/while/cond/lt"
    assert names["y"] == ""


def test_a_traced_serving_loop_holds_the_programs_spans(tmp_path):
    """Each Scheduler.step writes one serve.step holding its five phases in
    order; driven as the benchmark drives it, inside a bench.round."""
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.serve import Engine, Request, Scheduler, ServeConfig

    cfg = dataclasses.replace(configs.get_config("qwen2-7b", smoke=True),
                              compute_dtype="float32")
    eng = Engine(cfg, T.init_params(jax.random.PRNGKey(0), cfg),
                 ServeConfig(max_len=32, prefill_chunk=4))
    sched = Scheduler(eng, slots=2, chunk=2)
    sched.submit(Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=4))
    sched.step()                               # compile outside the trace
    n = 0
    with jax.profiler.trace(str(tmp_path)):
        while sched.has_work:
            with jax.profiler.TraceAnnotation("bench.round"):
                sched.step()
            n += 1
    t = scopes.load(tmp_path)
    assert len(t.rounds) == len(t.steps) == n > 0
    for a, b in t.rounds:
        inside = [s for s in t.spans if a <= s[1] and s[2] <= b]
        step, = [s for s in inside if s[0] == "serve.step"]
        kids = [s for s in inside if s[0] in scopes.PHASES]
        assert [s[0] for s in kids] == list(scopes.PHASES)
        assert all(step[1] <= s[1] and s[2] <= step[2] for s in kids)
    assert all(h > 0 for h in scopes.summary(t)["host_ms"])


def _ctx(tr):
    """A run's reader context over ``tr``: round 1 carries the chunk lane,
    round 2 is decode-only."""
    import types

    from bench.run import Context
    from bench.serve import Round, Window
    from bench.tests.tiny import REPO
    rounds = [Round(0.0, 1.0, 8, True, 8, [], list(range(8))),
              Round(1.0, 2.0, 0, False, 16, [10] * 2, [])]
    win = Window(0.0, 2.0, rounds, [], [], {}, {}, {}, {})
    cell = types.SimpleNamespace(root=REPO, config={})
    return Context(cell, win, 1.0, tr, None, 2, 8, CACHE)


@pytest.mark.parametrize("name,want", [
    ("host_ms.prefill", (10 + 30 + 50 + 40) / 1e6),
    ("host_ms.decode", (5 + 10 + 30 + 30) / 1e6),
    ("decode_lane_share.prefill", 100 * 500 / 700),
    ("kv_share.decode", 100 * 200 / 350)])
def test_readers_of_the_scoped_trace(name, want):
    from bench.spec import load_reader
    from bench.tests.tiny import REPO
    assert load_reader(REPO, name)(_ctx(_recorded())) == pytest.approx(want)


@pytest.mark.parametrize("name", ["decode_lane_share.prefill",
                                  "kv_share.decode"])
def test_share_readers_stay_silent_without_scopes(name):
    """A trace whose ops were not joined to their programs reads nothing,
    not 0."""
    from bench.spec import load_reader
    from bench.tests.tiny import REPO
    bare = _recorded()
    bare.scopes = {}
    assert load_reader(REPO, name)(_ctx(bare)) is None
