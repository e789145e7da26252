"""The main-path lutmul kernels compile for a TPU v5e at qwen2-7b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
for one chip of a described ``v5e:2x2`` topology, so the TPU compiler
refuses here what interpret mode would let through (sub-tile reshapes,
VMEM overruns).  The topology is described inside a fixture — never at
import — because only one process at a time may load the TPU library; the
worker that runs this file keeps it, so every compile stays in this
process.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lut import plane_decomposition
from repro.kernels.lutmul import kernel, ops

K, N = 3584, 18944                     # qwen2-7b d_model x d_ff


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _tmac(wbits, g, fused):
    n_planes, coeffs, const = plane_decomposition(wbits)
    w = ((n_planes, K // 8, N), jnp.uint8)
    if fused:
        return (lambda a, w, s1, s2, **b: kernel.lutmul_tmac_fused_pallas(
            a, w, s1, s2, coeffs=coeffs, const=const, g=g, **b)), w
    return (lambda a, w, **b: kernel.lutmul_tmac_pallas(
        a, w, coeffs=coeffs, const=const, g=g, **b)), w


def _case(name):
    """(kernel(*args, bm, bn, bk, interpret), activation dtype, weight
    (shape, dtype), fused)."""
    if name == "onehot_fused":
        table = ops._get_table(True)
        return ((lambda a, w, s1, s2, **b: kernel.lutmul_fused_pallas(
            a, w, table, s1, s2, **b)), jnp.uint8,
            ((K // 2, N), jnp.uint8), True)
    if name == "int8_fused":
        return (kernel.int_matmul_fused_pallas, jnp.int8,
                ((K, N), jnp.int8), True)
    _, wbits, g, variant = name.split("_")
    fused = variant == "fused"
    fn, w = _tmac("ternary" if wbits == "ternary" else int(wbits[1:]),
                  int(g[1:]), fused)
    return fn, jnp.int8, w, fused


@pytest.mark.parametrize("M", [8, 16, 128])
@pytest.mark.parametrize("name", [
    "onehot_fused", "tmac_w4_g1_fused", "tmac_w4_g2_fused",
    "tmac_ternary_g2_fused", "tmac_w4_g2_raw", "int8_fused"])
def test_kernel_compiles_for_v5e(one_chip, name, M):
    fn, a_dtype, (w_shape, w_dtype), fused = _case(name)
    bm, bn, bk = ops._clip_blocks(M, K, N, *ops._CANDIDATES[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((M, K), a_dtype), sds(w_shape, w_dtype)]
    if fused:
        args += [sds((M, 1), jnp.float32), sds((1, N), jnp.float32)]
    lowered = jax.jit(lambda *a: fn(*a, bm=bm, bn=bn, bk=bk,
                                    interpret=False)).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()          # raises what the chip's compiler would raise


# -- stable names: what a profiler trace of the served step reads -----------

def _named(name):
    """(kernel(*args, bm, bn, bk, interpret), argument (shape, dtype)s, the
    kind bench/kernels.json must give it)."""
    table = ops._get_table(True)
    a_u8, a_s8 = ((8, K), jnp.uint8), ((8, K), jnp.int8)
    scales = [((8, 1), jnp.float32), ((1, N), jnp.float32)]
    if name == "lutmul_onehot":
        return ((lambda a, w, **b: kernel.lutmul_pallas(a, w, table, **b)),
                [a_u8, ((K // 2, N), jnp.uint8)], "onehot")
    if name == "int_matmul":
        return kernel.int_matmul_pallas, [a_s8, ((K, N), jnp.int8)], "int8"
    if name.startswith("lutmul_tmac"):
        fn, w = _tmac(4, 2, name.endswith("fused"))
        return fn, [a_s8, w] + (scales if name.endswith("fused") else []), \
            "tmac"
    fn, a_dtype, w, _ = _case({"lutmul_onehot_fused": "onehot_fused",
                               "int_matmul_fused": "int8_fused"}[name])
    return fn, [((8, K), a_dtype), w] + scales, (
        "onehot" if a_dtype == jnp.uint8 else "int8")


@pytest.mark.parametrize("name", [
    "lutmul_onehot", "lutmul_onehot_fused", "lutmul_tmac",
    "lutmul_tmac_fused", "int_matmul", "int_matmul_fused"])
def test_kernel_carries_its_name_for_v5e(one_chip, name):
    """The compiled custom call is named after the kernel (its instruction
    and its op_name), and the benchmark's operand-type map
    (bench/kernels.json) still finds it as its own kind and no other."""
    import json
    import re
    from pathlib import Path

    from jax._src.lib import _jax

    fn, shapes, kind = _named(name)
    bm, bn, bk = ops._clip_blocks(8, K, N, *ops._CANDIDATES[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(lambda *a: fn(*a, bm=bm, bn=bn, bk=bk,
                                     interpret=False)).lower(*args).compile()
    assert f'/{name}/pallas_call"' in compiled.as_text()
    # the instruction as a v5e trace names its event: HLO text with operand
    # types, without metadata
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and calls[0].startswith(f"%{name}.")
    kernels = json.loads((Path(__file__).resolve().parents[1] / "bench"
                          / "kernels.json").read_text())
    assert [k for k, rx in kernels.items() if re.search(rx, calls[0])] == [
        kind]


def test_served_step_names_its_scopes():
    """The served step's ops carry the step's named scopes in their op_name
    metadata, for both program variants (with and without the chunk
    lane); lowered on the CPU."""
    import dataclasses
    import re

    from repro import configs
    from repro.models import transformer as T
    from repro.serve import Engine, Request, Scheduler, ServeConfig

    cfg = dataclasses.replace(configs.get_config("qwen2-7b", smoke=True),
                              compute_dtype="float32")
    eng = Engine(cfg, T.init_params(jax.random.PRNGKey(0), cfg),
                 ServeConfig(max_len=32, prefill_chunk=4))
    texts, build = {}, eng._build_step_fn

    def recording(C, *key):
        fn = build(C, *key)

        def call(*args):
            texts.setdefault(C, fn.lower(*args).as_text(
                dialect="hlo", debug_info=True))
            return fn(*args)
        return call
    eng._build_step_fn = recording
    Scheduler(eng, slots=2, chunk=2).run(
        [Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=4)])
    assert sorted(texts) == [0, 4]
    for C, text in texts.items():
        parts = {p for path in re.findall(r'op_name="([^"]*)"', text)
                 for p in path.split("/")}
        assert {"decode_lane", "sample", "guard", "kv_write",
                "attend"} <= parts
        assert ("chunk_lane" in parts) == (C > 0)
