"""The main-path Pallas kernels compile for one TPU v5e chip at real widths.

The chip is described, not attached: the TPU compiler that ships with jaxlib
compiles each kernel for one chip of a ``v5e:2x2`` topology.  It refuses
what interpret mode accepts (blocks that break the (8, 128) tiling, too
much VMEM), so these tests guard what the CPU parity tests cannot.

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture (never at import, in ``parametrize`` or
in ``skipif``), and the compiles run in this test process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("b,h,kv,s,d,dtype", [
    (8, 4, 2, 8, 32, jnp.float32),        # TransformerPolicyConfig defaults
    (64, 32, 4, 8, 128, jnp.float32),     # Yi-6B body widths, serve batch
    (8, 32, 4, 4096, 128, jnp.bfloat16),  # Yi-6B heads over a long cache
])
def test_decode_attention_compiles_for_v5e(one_chip, b, h, kv, s, d, dtype):
    def shape(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    compiled = _compile(decode_attention, shape(b, h, d), shape(b, s, kv, d),
                        shape(b, s, kv, d), shape(b, dt=jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    qkv = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.float32,
                               sharding=one_chip)
    compiled = _compile(flash_attention, qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()
