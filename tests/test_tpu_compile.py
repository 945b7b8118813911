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
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_lora.batched_lora import batched_lora_matmul
from repro.kernels.flash_decode.paged import (paged_flash_decode,
                                              paged_kv_append)


@pytest.fixture(scope="module")
def v5e():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


@pytest.fixture(scope="module")
def two_chips(v5e):
    """Two chips of the described host as one replica's (data=1, model=2)
    mesh, the mesh `serve.py --tp 2` cuts."""
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(v5e.devices[:2]).reshape(1, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _kernel_calls(compiled) -> list:
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("page", [64, 16])
def test_paged_flash_decode_compiles(one_chip, page):
    b, hkv, g, d, n_pages, ctx, n_layers = 8, 5, 4, 128, 129, 1024, 30

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((n_layers, n_pages, hkv, page, d), jnp.float8_e4m3fn)
    compiled = paged_flash_decode.lower(
        s((b, hkv, g, d), jnp.bfloat16), pool, pool,
        s((b, ctx // page), jnp.int32), s((b,), jnp.int32),
        s((), jnp.float32), s((), jnp.int32)).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "paged_flash_decode" in calls[0]


@pytest.mark.parametrize("page", [64, 16])
def test_paged_kv_append_compiles_in_place(one_chip, page):
    b, hkv, d, n_pages, n_layers = 8, 5, 128, 129, 30

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f8 = jnp.float8_e4m3fn
    pool = s((n_layers, n_pages, hkv, page, d), f8)
    compiled = jax.jit(paged_kv_append, donate_argnums=(0, 1)).lower(
        pool, pool, s((b, hkv, d), f8), s((b, hkv, d), f8),
        s((), jnp.int32), s((b,), jnp.int32), s((b,), jnp.int32)).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "paged_kv_append" in calls[0]
    mem = compiled.memory_analysis()
    pool_bytes = n_layers * n_pages * hkv * page * d
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


def test_paged_decode_step_never_copies_the_pool(one_chip, monkeypatch):
    """BitNet-2B's decode step as the benchmark serves it (30 layers at the
    published widths, 64 slots, 1,024 tokens in pages of 64), kernel path,
    pool donated: both pools alias the outputs, and no op of the compiled
    step has the pool's shape but its parameters and their views — the
    kernels read and write the pool in place. (An XLA scatter of the new
    token makes the compiler re-lay the pool out around the attention
    kernel, copying it twice per layer.)"""
    import re
    from repro.configs.base import get_config
    from repro.launch.train import reduce_config
    from repro.models.attention import PagedKVState
    from repro.models.transformer import Model
    # the model picks its kernel path from the backend it runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = reduce_config(get_config("bitnet-2b"), "full")
    model = Model(cfg, mode="serve")
    b, n_p, page = 64, 16, 64

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: s(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    shape = (cfg.num_layers, b * n_p + 1, cfg.num_kv_heads, page,
             cfg.head_dim)
    pool = s(shape, jnp.float8_e4m3fn)
    i32 = jnp.int32
    state = PagedKVState(pool, pool, s((b, n_p), i32), s((b,), i32),
                         s((b,), i32), s((b,), i32))
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    compiled = step.lower(params, state, s((b,), i32), s((b,), i32)).compile()
    dims = ",".join(map(str, shape))
    on_pool = re.findall(rf"= f8e4m3fn\[{dims}\]{{[^}}]*}} ([\w-]+)\(",
                         compiled.as_text())
    assert "parameter" in on_pool
    assert set(on_pool) <= {"parameter", "get-tuple-element", "bitcast"}, \
        sorted(set(on_pool))
    pool_bytes = int(np.prod(shape))
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    names = [c.split("=", 1)[0] for c in _kernel_calls(compiled)]
    assert any("paged_kv_append" in n for n in names)
    assert any("paged_flash_decode" in n for n in names)


@pytest.mark.parametrize("n_pages", [1025, 1026],
                         ids=["pool-replicated", "pages-split"])
def test_paged_decode_step_at_tp2_moves_no_pool(two_chips, monkeypatch,
                                                n_pages):
    """`serve.py --tp 2`: BitNet-2B's decode step at its published widths
    (4 of its 30 layers, 64 slots, pages of 64) with the parameters placed
    by the paper-tree spec and the pool as `shard_engine` places it, kernel
    path, state donated. XLA cannot partition a Mosaic kernel, so the step
    runs both kernels per lane: on a replicated pool (1,025 pages, which 2
    does not divide) no collective touches the pool; on a pool whose pages
    are split over the lanes each layer all-gathers that one layer and
    stores the lane's own pages back in place. Neither copies the pool,
    and both pools alias the outputs."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.launch.train import reduce_config
    from repro.models.attention import PagedKVState
    from repro.models.sharding import (paged_pool_spec, param_spec_tree,
                                       to_named)
    from repro.models.transformer import Model
    import dataclasses
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(reduce_config(get_config("bitnet-2b"), "full"),
                              num_layers=4)
    model = Model(cfg, mode="serve")
    b, n_p, page = 64, 16, 64
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    placed = to_named(param_spec_tree(shapes, two_chips, mode="serve"),
                      two_chips)
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, placed)
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, page, cfg.head_dim)
    spec = paged_pool_spec(shape, two_chips)
    split = spec[1] is not None
    assert split == (n_pages % 2 == 0)
    pool = jax.ShapeDtypeStruct(shape, jnp.float8_e4m3fn,
                                sharding=NamedSharding(two_chips, spec))
    rep = NamedSharding(two_chips, P())
    i32 = lambda sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=rep)
    state = PagedKVState(pool, pool, i32((b, n_p)), i32((b,)), i32((b,)),
                         i32((b,)))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, state, i32((b,)), i32((b,))).compile()
    text = compiled.as_text()
    lane_pool = (shape[0], shape[1] // 2 if split else shape[1]) + shape[2:]
    one_layer = (1,) + shape[1:]
    # every fp8 array a collective makes: one layer, gathered, when split
    moved = [(op, tuple(int(x) for x in dims.split(",")))
             for res, op in re.findall(
                 r"= (\(?f8e4m3fn\[[^=]*?) (all-gather|all-reduce|all-to-all"
                 r"|collective-permute|reduce-scatter)(?:-start)?\(", text)
             for dims in re.findall(r"f8e4m3fn\[([\d,]+)\]", res)]
    if split:
        assert moved and set(moved) == {("all-gather", one_layer)}, moved
    else:
        assert moved == [], moved
    # the lane's pool: parameters, views and (split) the in-place store of
    # the lane's pages of one layer; never a copy
    dims = ",".join(map(str, lane_pool))
    on_pool = re.findall(rf"%([\w.-]+) = f8e4m3fn\[{dims}\]{{[^}}]*}} "
                         rf"([\w-]+)\(", text)
    assert ("parameter" in {op for _, op in on_pool}), on_pool
    stores = {"dynamic-update-slice", "fusion"} if split else set()
    for name, op in on_pool:
        assert op in {"parameter", "get-tuple-element", "bitcast"} or (
            op in stores and "copy" not in name
            and re.search("dynamic[-_]update[-_]slice", name)), (name, op)
    lane_bytes = int(np.prod(lane_pool))
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * lane_bytes
    names = [c.split("=", 1)[0] for c in _kernel_calls(compiled)]
    assert any("paged_kv_append" in n for n in names)
    assert any("paged_flash_decode" in n for n in names)


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
