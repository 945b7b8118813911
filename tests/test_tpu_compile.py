"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret-mode tests (test_kernels.py, test_adapters.py) check what the
kernels compute; only the TPU compiler checks that they lower — block
shapes against the (8, 128) tiling, in-kernel shape casts, VMEM use. These
tests compile at BitNet-2B's published widths (Hkv=5, G=4, D=128; K=2560
into the q and v projections) for a v5e described with no chip attached,
and look for the kernel's ``tpu_custom_call`` in the executable.

The topology is described only inside a module fixture: every xdist worker
then collects the same tests, and only the worker running this file loads
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_lora.batched_lora import batched_lora_matmul
from repro.kernels.flash_decode.paged import paged_flash_decode


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # an executable for a described chip can be written to the persistent
    # cache but not read back without one: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "can't"
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _kernel_calls(compiled) -> list:
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("page", [64, 16])
def test_paged_flash_decode_compiles(one_chip, page):
    b, hkv, g, d, n_pages, ctx = 8, 5, 4, 128, 129, 1024

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((n_pages, hkv, page, d), jnp.float8_e4m3fn)
    compiled = paged_flash_decode.lower(
        s((b, hkv, g, d), jnp.bfloat16), pool, pool,
        s((b, ctx // page), jnp.int32), s((b,), jnp.int32),
        s((), jnp.float32)).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "paged_flash_decode" in calls[0]


@pytest.mark.parametrize("n", [2560, 640])
@pytest.mark.parametrize("rank", [8, 16])
def test_batched_lora_matmul_compiles(one_chip, n, rank):
    b, k, n_adapters = 8, 2560, 5

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = batched_lora_matmul.lower(
        s((b, k), jnp.bfloat16), s((n_adapters, k // 4, rank), jnp.uint8),
        s((n_adapters, rank // 4, n), jnp.uint8),
        s((n_adapters,), jnp.float32), s((b,), jnp.int32)).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "batched_lora_matmul" in calls[0]
