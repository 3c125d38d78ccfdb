"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The installed TPU compiler compiles for a topology that is described,
not attached: the Pallas kernels at the real widths ``chip_smoke.py``
runs (``repro.kernels.REAL_WIDTHS``) must lower to a Mosaic
``tpu_custom_call``, and the ``h2o-danube-1.8b`` decode step must fit one
chip. The topology is described only inside the fixture below, so every
worker collects the same tests and only the one running this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compat import tree_map
from repro.kernels import REAL_WIDTHS
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.rowstream_matmul.kernel import rowstream_matmul
from repro.kernels.rwkv_scan.kernel import rwkv_scan

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip can be written to the persistent
        # cache but never read back without one: keep the cache out.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _kernel_call(name, sharding):
    """(kernel, argument shapes) at REAL_WIDTHS, placed on `sharding`."""
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)
    w = REAL_WIDTHS[name]
    bf, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_decode":
        kv = spec((w["b"], w["hkv"], w["s"], w["d"]), bf)
        return flash_decode, (spec((w["b"], w["h"], w["d"]), bf), kv, kv,
                              spec((), jnp.int32))
    if name == "rowstream_matmul":
        return rowstream_matmul, (spec((w["m"], w["k"]), bf),
                                  spec((w["k"], w["n"]), bf))
    x = spec((w["b"], w["s"], w["H"], w["hd"]), f32)
    return rwkv_scan, (x, x, x, x, spec((w["H"], w["hd"]), f32))


@pytest.mark.parametrize("name", sorted(REAL_WIDTHS))
def test_kernel_lowers_to_mosaic(one_chip, name):
    kernel, args = _kernel_call(name, one_chip)
    compiled = kernel.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_h2o_danube_decode_step_fits_one_chip(one_chip):
    from repro.configs.registry_configs import ALL_ARCHS
    from repro.launch.serve import make_decode_step
    from repro.models.registry import get_adapter

    adapter = get_adapter(ALL_ARCHS["h2o-danube-1.8b"])
    place = lambda tree: tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda: adapter.init(
        jax.random.PRNGKey(0), tp=1)))
    cache = place(jax.eval_shape(lambda: adapter.init_decode_state(2, 128)))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = make_decode_step(adapter).lower(params, tokens, cache,
                                               pos).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 3.6e9 < mem.argument_size_in_bytes      # 1.83 B bf16 params
    assert used < V5E_HBM_BYTES, used
