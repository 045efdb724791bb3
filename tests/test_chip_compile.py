"""Each Pallas kernel compiles for a TPU v5e at a real model's widths.

Nothing runs: the TPU compiler, which ships with jax, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(more scoped VMEM than a kernel may use, unaligned slices, primitives
Mosaic cannot lower) -- all of which interpret mode accepts.  The topology
is described inside a fixture, never at import: only one process at a time
may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash_attention():            # minicpm-2b: 36 heads x 64
    from repro.kernels.flash_attention import ops
    fn = lambda q, k, v: ops.flash_attention(q, k, v, interpret=False)
    return fn, [((1, 4096, 36, 64), bf16)] * 3


def _moe_gmm():                    # mixtral-8x7b: E=8, d=4096, f=14336
    from repro.kernels.moe_gmm import ops
    fn = lambda x, w1, w3, w2: ops.expert_ffn(
        x, {"w1": w1, "w3": w3, "w2": w2}, "swiglu", interpret=False)
    E, C, d, f = 8, 512, 4096, 14336
    return fn, [((E, C, d), bf16), ((E, d, f), bf16), ((E, d, f), bf16),
                ((E, f, d), bf16)]


def _mlstm_scan():                 # xlstm-1.3b: 4 heads x 512
    from repro.kernels.mlstm_scan import ops
    fn = lambda q, k, v, i, g: ops.mlstm_chunkwise(q, k, v, i, g,
                                                   interpret=False)
    return fn, [((1, 2048, 4, 512), bf16)] * 3 + [((1, 2048, 4), f32)] * 2


def _rglru_scan():                 # recurrentgemma-9b: d_rnn 4096
    from repro.kernels.rglru_scan import ops
    fn = lambda x, lam, ga, gx: ops.rglru(x, lam, ga, gx, interpret=False)
    return fn, [((8, 2048, 4096), bf16), ((4096,), f32),
                ((8, 2048, 4096), bf16), ((8, 2048, 4096), bf16)]


@pytest.mark.parametrize("kernel", [_flash_attention, _moe_gmm, _mlstm_scan,
                                    _rglru_scan],
                         ids=["flash_attention", "moe_gmm", "mlstm_scan",
                              "rglru_scan"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache):
    fn, shapes = kernel()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
