"""Serving engine end-to-end + partitioning specs + small-mesh integration
(8 fake devices in a subprocess so the main process stays single-device)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.dist import partitioning
from repro.dist.sharding import production_rules
from repro.models import transformer as T
from repro.serve.engine import Engine, ServeConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_engine_generate_matches_forward_greedy():
    cfg = configs.get_config("qwen2-7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    out = eng.generate(prompts, max_new_tokens=4)
    assert out.shape == (2, 10)
    # greedy decode must match teacher-forced argmax on its own outputs
    logits, _ = T.forward(params, cfg, out[:, :-1])
    want = jnp.argmax(logits[:, 5:], axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 6:]), np.asarray(want))


def test_engine_rwkv_generate():
    cfg = configs.get_config("rwkv6-1.6b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig(max_len=24))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, cfg.vocab)
    out = eng.generate(prompts, max_new_tokens=3)
    assert out.shape == (2, 8)


def test_param_specs_match_rules():
    from jax.sharding import PartitionSpec as P
    cfg = configs.get_config("qwen2-7b", smoke=True)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    rules = production_rules()
    rules["fsdp"] = "data"
    specs = partitioning.param_specs(params, rules)
    # stacked attn wq: [G, d, H*dh] -> (None, fsdp, model)
    assert specs["blocks"][0]["attn"]["wq"]["w"] == P(None, "data", "model")
    assert specs["blocks"][0]["attn"]["wo"]["w"] == P(None, "model", "data")
    assert specs["blocks"][0]["mlp"]["wi"]["w"] == P(None, "data", "model")
    assert specs["embed"]["emb"] == P("model", "data")
    assert specs["final_norm"]["scale"] == P()


def test_moe_param_specs_ep_vs_tp():
    from jax.sharding import PartitionSpec as P
    cfg = configs.get_config("qwen2-moe-a2.7b", smoke=True)
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    ep = production_rules()
    ep.update(expert="model", expert_mlp=None, fsdp="data")
    specs = partitioning.param_specs(params, ep)
    assert specs["blocks"][0]["moe"]["wi"] == P(None, "model", "data", None)
    tp = production_rules()
    tp.update(expert=None, expert_mlp="model", fsdp="data")
    specs = partitioning.param_specs(params, tp)
    assert specs["blocks"][0]["moe"]["wi"] == P(None, None, "data", "model")
    assert specs["blocks"][0]["moe"]["wo"] == P(None, None, "model", "data")


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """Compile a smoke-config train step + decode step on a (2,4) fake mesh:
    proves the sharding rules produce a partitionable program end-to-end."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json, sys
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.dist.sharding import use_rules
        from repro.launch.mesh import rules_for
        from repro.launch.specs import build_cell
        from repro.roofline import analysis

        from repro.dist.sharding import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        orig = configs.get_config
        configs.get_config = lambda a, quant="none", **kw: orig(
            a, smoke=True, quant=quant)
        configs.SHAPES["_t"] = configs.ShapeSpec("_t", 64, 8, "train")
        configs.SHAPES["_d"] = configs.ShapeSpec("_d", 64, 8, "decode")
        results = {}
        for arch, shape in [("qwen2-7b", "_t"), ("mixtral-8x22b", "_t"),
                            ("gemma2-2b", "_d"), ("zamba2-2.7b", "_d")]:
            cfg = configs.get_config(arch)
            rules = rules_for(cfg, configs.SHAPES[shape].kind, shape)
            with mesh, use_rules(rules, mesh):
                cell = build_cell(arch, shape, mesh, rules)
                jf = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                             out_shardings=cell["out_shardings"])
                compiled = jf.lower(*cell["args_sds"]).compile()
                cost = compiled.cost_analysis()
                terms = analysis.roofline_terms(cost, compiled.as_text())
                results[f"{arch}:{shape}"] = {
                    "flops": terms["hlo_flops_per_device"],
                    "ncoll": terms["n_collectives"],
                    "mem": compiled.memory_analysis().temp_size_in_bytes,
                }
        print("RESULTS" + json.dumps(results))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULTS")][0]
    results = json.loads(line[len("RESULTS"):])
    assert len(results) == 4
    for k, v in results.items():
        assert v["flops"] > 0 and v["ncoll"] > 0, (k, v)


@pytest.mark.slow
def test_compressed_psum_multidevice_subprocess():
    """Error-feedback int8 psum across 8 fake devices: mean within int8
    quantization error of the exact mean, residual carries the rest."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import grad_compress
        from repro.dist.sharding import make_mesh
        mesh = make_mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        r = jnp.zeros((8, 64))
        def f(g, r):
            out, r2 = grad_compress.compressed_psum(
                {"w": g[0]}, {"w": r[0]}, "dp")
            return out["w"][None], r2["w"][None]
        out, r2 = jax.shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                                out_specs=(P("dp"), P("dp")),
                                check_vma=False)(g, r)
        exact = jnp.mean(g, axis=0)
        got = np.asarray(out[0])
        err = np.abs(got - np.asarray(exact)).max()
        scale = float(jnp.max(jnp.abs(g)) / 127.0)
        assert err <= scale + 1e-6, (err, scale)
        print("OK maxerr", err, "scale", scale)
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_compile_cache_directory(monkeypatch):
    """The chip entry points keep XLA's cache in $JAX_COMPILATION_CACHE_DIR
    (set nothing else in code) or else in the fixed, git-ignored
    <repo>/.jax_cache."""
    from repro.launch import compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
