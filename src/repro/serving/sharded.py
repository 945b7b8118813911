"""Sharded replica construction: mesh-placed engines for the fleet router.

TOM's architecture is distributed by construction — ternary ROM banks
co-located with the processing lanes, KV tiles in per-lane SRAM. The jax
mapping: each serving replica owns a ``(data=1, model=tp)`` submesh cut
from the host's device grid, with

  * **base params** placed by `models/sharding.param_spec_tree` (paper-tree
    strategy: contracting dim over the ``model`` lanes — Fig 7a),
  * **paged KV pool** sharded over its *pages* axis — pages play the
    context role, so lanes each hold a slice of the pooled SRAM tiles
    (`kv_cache_spec_tree`'s context rule, transposed to pool layout),
  * **dense caches** placed by `kv_cache_spec_tree` directly,
  * **adapter stacks** replicated (they are SRAM-budget-bounded and
    gathered per slot inside the decode — sharding the stack would turn
    the SGMV gather into cross-lane traffic).

Every spec passes through `fit_spec`, so axes that don't divide a tiny
test shape degrade to replication instead of erroring — a tp=1 replica on
one CPU device is the identity placement, which is exactly what the
sharded↔single-device token-identity lane asserts.

Replicas beyond the device-row count reuse rows round-robin: ``--replicas
2`` on a 1-device host builds two engines time-sharing one chip —
correctness (and the router's behavior) is unchanged, only the parallel
speedup is gone.
"""
from __future__ import annotations

from typing import Any, List

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.models.sharding import (fit_spec, kv_cache_spec_tree,
                                   paged_pool_spec, param_spec_tree, to_named)

Params = Any


def fleet_mesh(tp: int = 1) -> Mesh:
    """All visible devices as one (data, model) grid — the canvas replica
    submeshes are cut from."""
    return make_host_mesh(model=tp)


def replica_meshes(n_replicas: int, tp: int = 1) -> List[Mesh]:
    """One ``(data=1, model=tp)`` submesh per replica, row-sliced from the
    fleet mesh (round-robin reuse when replicas outnumber rows)."""
    assert n_replicas >= 1
    rows = fleet_mesh(tp).devices.reshape(-1, tp)
    return [Mesh(rows[r % rows.shape[0]][None, :], ("data", "model"))
            for r in range(n_replicas)]


def shard_params(params: Params, mesh: Mesh, *,
                 strategy: str = "paper_tree") -> Params:
    """device_put the param tree onto ``mesh`` under the named spec tree
    (explicit input shardings — jit then compiles against these placements
    instead of inferring them)."""
    specs = param_spec_tree(params, mesh, strategy=strategy, mode="serve")
    return jax.device_put(params, to_named(specs, mesh))


def pool_spec(pool, mesh: Mesh) -> P:
    """PartitionSpec for the paged pool (`paged_pool_spec`: pages over the
    ``model`` lanes; tiny test pools simply replicate). The decode step's
    Pallas kernels read the pool per lane under this same spec."""
    return paged_pool_spec(pool.k.shape, mesh)


def shard_engine(engine, mesh: Mesh):
    """Place one engine's device state onto ``mesh`` with explicit
    shardings: params by the paper-tree spec, KV storage by the cache/pool
    spec, adapter stacks replicated. Stamps ``engine.mesh`` and invalidates
    the engine's installed multi-tenant param tree so the next
    ``_effective_params()`` grafts adapters onto the *sharded* base.
    Returns the engine (mutated in place)."""
    engine.params = shard_params(engine.params, mesh)
    if engine.kv.supports_paging:
        sh = NamedSharding(mesh, pool_spec(engine.pool, mesh))
        engine.pool.k = jax.device_put(engine.pool.k, sh)
        engine.pool.v = jax.device_put(engine.pool.v, sh)
    elif engine.cache is not None:
        cache = engine.kv.cache
        specs = kv_cache_spec_tree(cache, mesh)
        flat_c, treedef = jax.tree.flatten(cache)
        flat_s, _ = jax.tree.flatten(specs,
                                     is_leaf=lambda x: isinstance(x, P))
        shardings = jax.tree.unflatten(treedef, [
            NamedSharding(mesh, fit_spec(tuple(s), leaf.shape, mesh))
            for leaf, s in zip(flat_c, flat_s)])
        engine.kv.cache = jax.device_put(cache, shardings)
    if engine.adapters is not None:
        rep = NamedSharding(mesh, P())
        for pack in engine.adapters.pack.values():
            for k in list(pack):
                pack[k] = jax.device_put(pack[k], rep)
        engine._mt_params = None
        engine._mt_version = -1
    engine.mesh = mesh
    return engine
