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


@pytest.mark.parametrize("M", [8, 128])
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
