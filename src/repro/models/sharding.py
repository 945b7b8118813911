"""Partition rules: how every parameter / activation / cache shards over the
production mesh (data, model[, pod]).

Strategies (selected per run; §Perf records the deltas):

  * ``paper_tree`` — the paper-faithful layout (Fig 7a): EVERY linear weight
    is sharded along its contracting (K) dimension over ``model``; each
    matmul produces partials that the reduction tree (all-reduce) sums. One
    collective per GEMV, no other cross-lane traffic — exactly TOM's
    "lanes synchronize only via the global reduction tree".
  * ``megatron`` — beyond-paper: pair column-sharded (q/k/v/up/gate) with
    row-sharded (o/down) linears so only block boundaries reduce (2
    all-reduces per layer instead of ~7). Decode attention keeps the paper's
    context sharding either way (it is decode-optimal and is the C3 claim).
  * MoE experts: ``tp`` K-shards each expert (paper-faithful, tree-only);
    ``ep`` shards the expert dim (all-to-all dispatch, beyond-paper).

QAT (training) additionally shards the non-contracting weight dim over
``data`` (FSDP/ZeRO-style) so 100B+ masters + optimizer state fit; XLA
all-gathers per layer under the scan.

Rules are expressed as path-regex → PartitionSpec over logical axis names,
resolved against the concrete mesh axes at apply time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axes: "dp" (data ∥, maps to ('pod','data') or ('data',)), "tp"
# (tensor ∥ = the paper's lanes, maps to 'model'), None (replicated).


def logical_to_mesh_axes(mesh: Mesh) -> Dict[str, Any]:
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data", "replica"))
    return {"dp": dp if len(dp) > 1 else (dp[0] if dp else None), "tp": "model"}


def _resolve(spec: Tuple[Optional[str], ...], mesh: Mesh) -> P:
    m = logical_to_mesh_axes(mesh)
    return P(*(m.get(a, None) if a else None for a in spec))


# ---------------------------------------------------------------------------
# Parameter rules (matched against "/"-joined pytree paths)
# ---------------------------------------------------------------------------

# (regex, spec-for-2D-(K,N), spec-for-packed-(K/4,N)) — matched in order.
def param_rules(strategy: str, mode: str, fsdp: bool):
    col = ("tp",) if strategy == "megatron" else ()     # N-shard set
    # In paper_tree, everything K-shards. In megatron, these N-shard:
    col_names = r"(q|k|v|gate|up|q_b|kv_b|in_proj)$" if strategy == "megatron" else r"$^"
    dp = "dp" if fsdp else None
    rules = [
        # MoE stacked experts (E, K, N)
        (r"experts_ep/.*(up|gate|down)/(w|packed)$", ("tp", None, dp)),
        (r".*/(up|gate|down)/(w|packed)$/expert", None),  # placeholder, unused
        # embedding: vocab-sharded rows
        (r".*embed.*/(w|packed_rows)$", ("tp", dp)),
        # lm head (D, V): vocab-sharded output
        (r".*head/(w|packed)$", (dp, "tp") if strategy == "megatron" else ("tp", dp)),
        # column-parallel linears (megatron only)
        (col_names + r"/(w|packed)" if strategy == "megatron" else r"$^", (dp, "tp")),
        # default 2-D linear: K-sharded (paper Fig 7a)
        (r".*/(w|packed)$", ("tp", dp)),
        # everything else (norms, scales, biases, conv, a_log...): replicated
        (r".*", ()),
    ]
    return rules


def _is_expert_leaf(path: str) -> bool:
    return "/moe/" in path and any(s in path for s in ("/up/", "/gate/", "/down/")) \
        and not any(s in path for s in ("shared", "dense_residual", "router"))


def _axis_extent(mesh: Mesh, part) -> int:
    names = part if isinstance(part, tuple) else (part,)
    e = 1
    for n in names:
        e *= mesh.shape[n]
    return e


def fit_spec(parts, shape, mesh: Mesh) -> P:
    """Drop/shrink axes that don't divide their dimension.

    Rule: for each dim, if the assigned axis (or axis tuple) extent does not
    divide the dim, try successively smaller suffixes of the tuple (e.g.
    ('pod','data') → ('data',)), else replicate that dim. Keeps the dry-run
    honest for shapes like zamba2's in_proj N=14704 (divisible by 16, not by
    the 32-wide multi-pod dp)."""
    fitted = []
    for dim, part in zip(shape, parts):
        if part is None:
            fitted.append(None)
            continue
        cand = part if isinstance(part, tuple) else (part,)
        chosen = None
        while cand:
            if dim % _axis_extent(mesh, cand) == 0:
                chosen = cand if len(cand) > 1 else cand[0]
                break
            cand = cand[1:]
        fitted.append(chosen)
    return P(*fitted)


def paged_pool_spec(shape, mesh) -> P:
    """PartitionSpec for a paged pool's ``(L, pages, Hkv, page, D)`` arrays:
    pages over the ``model`` lanes (the context dim of the paper's per-lane
    SRAM tiling). `fit_spec` drops the axis when the page count doesn't
    divide, and the pool is then replicated. ``mesh`` may be abstract."""
    tp = "model" if "model" in mesh.axis_names else None
    return fit_spec((None, tp, None, None, None), shape, mesh)


def param_spec_tree(params_or_specs, mesh: Mesh, *, strategy: str = "paper_tree",
                    mode: str = "serve", fsdp: bool = False,
                    moe_sharding: str = "tp"):
    """PartitionSpec tree (same structure as params)."""
    m = logical_to_mesh_axes(mesh)
    dp = m["dp"] if fsdp else None
    tp = m["tp"]

    col_re = re.compile(r"/(q|k|v|gate|up|q_a|q_b|kv_a|kv_b|in_proj)/(w|packed)$")
    embed_re = re.compile(r"embed/(w|packed_rows)$")
    head_re = re.compile(r"head/(w|packed)$")
    lin_re = re.compile(r"/(w|packed)$")
    lora_re = re.compile(r"/lora/(a|b)$")

    def spec_for(path: str, leaf) -> P:
        ndim = len(leaf.shape)
        # strip the stacked-layers leading axis for rule matching
        stacked = path.startswith("layers/") or path.startswith("mamba/") or "/layers/" in path
        wdim = ndim - 1 if stacked else ndim

        if _is_expert_leaf(path) and lin_re.search(path):
            # (…, E, K, N) or (…, E, K/4, N)
            if moe_sharding == "ep":
                e_spec = (tp, None, dp)
            elif moe_sharding == "megatron":
                # column-parallel up/gate + row-parallel down: the silu(gate)·up
                # nonlinearity runs lane-LOCAL on the dff/16 slice and the only
                # reduction is ONE psum of the combined (T, D) output — vs the
                # paper-tree layout's (E, C, dff) f32 reductions (§Perf cell B).
                if "/down/" in path:
                    e_spec = (None, tp, dp)      # row: K=dff over lanes
                else:
                    e_spec = (None, dp, tp)      # col: N=dff over lanes
            else:
                e_spec = (None, tp, dp)
            pad = (None,) * (ndim - 3)
            return P(*pad, *e_spec)
        if lora_re.search(path):
            # adapters: A (K, r) K-sharded, B (r, N) replicated-K
            pad = (None,) * (ndim - 2)
            return P(*pad, tp, None) if path.endswith("/a") else P(*pad, None, None)
        if embed_re.search(path):
            # (V, D): feature dim over lanes (gathers stay device-local),
            # vocab dim over dp (FSDP). Vocab-over-lanes would force an
            # all-gather of the whole table per embed lookup.
            pad = (None,) * (ndim - 2)
            return P(*pad, dp, tp)
        if head_re.search(path):
            pad = (None,) * (ndim - 2)
            return P(*pad, dp, tp) if strategy == "megatron" else P(*pad, tp, dp)
        if wdim >= 2 and lin_re.search(path):
            pad = (None,) * (ndim - 2)
            if strategy == "megatron" and col_re.search(path):
                return P(*pad, dp, tp)
            return P(*pad, tp, dp)   # paper Fig 7a: K over lanes
        return P()

    def build(tree):
        def walk(path, node):
            if isinstance(node, dict):
                return {k: walk(f"{path}/{k}" if path else k, v) for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                t = [walk(f"{path}/{i}", v) for i, v in enumerate(node)]
                return type(node)(t)
            spec = spec_for(path, node)
            return fit_spec(tuple(spec), node.shape, mesh)
        return walk("", tree)

    return build(params_or_specs)


# ---------------------------------------------------------------------------
# Activation / batch / cache specs
# ---------------------------------------------------------------------------


def batch_spec(mesh: Mesh) -> P:
    dp = logical_to_mesh_axes(mesh)["dp"]
    return P(dp)


def tokens_spec(mesh: Mesh) -> P:
    dp = logical_to_mesh_axes(mesh)["dp"]
    return P(dp, None)


def embeds_spec(mesh: Mesh) -> P:
    dp = logical_to_mesh_axes(mesh)["dp"]
    return P(dp, None, None)


def kv_cache_spec_tree(cache_specs, mesh: Mesh) -> Any:
    """KV caches shard over (dp on batch, model on CONTEXT) — the paper's
    SRAM tiling. Works for GQA (L,B,H,S,D), MLA latent (L,B,S,R) and SSM
    states (L,B,H,P,N — heads over model, no context dim)."""
    m = logical_to_mesh_axes(mesh)
    dp, tp = m["dp"], m["tp"]

    def spec_for(path: str, leaf) -> P:
        nd = len(leaf.shape)
        leafname = path.rsplit("/", 1)[-1]
        if leafname in ("k", "v"):                            # (L,B,H,S,D)
            return P(None, dp, None, tp, None)
        if "latent" in path or "k_rope" in path:              # (L,B,S,R)
            return P(None, dp, tp, None)
        if path.endswith("ssm"):                              # (L,B,H,P,N)
            return P(None, dp, tp, None, None)
        if path.endswith("conv"):                             # (L,B,W,C)
            return P(None, dp, None, tp)
        return P(*([None] * nd))

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(f"{path}/{k}" if path else k, v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)([walk(f"{path}/{i}", v) for i, v in enumerate(node)])
        return spec_for(path, node)

    return walk("", cache_specs)


def to_named(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
