"""Name/shape-pattern sharding rules -> PartitionSpec trees.

One rule engine covers every leaf of all 10 arch configs (attention, MoE,
SSM, conv frontends, enc-dec) on the 2-D ("data", "model") production mesh
(and its 3-D ("pod", "data", "model") multi-pod variant):

  * column-parallel weights (wq/wk/wv, mlp up/gate, router, x_proj, ...):
    input dim sharded over "data" (ZeRO/FSDP-style), output dim over "model"
    (Megatron tensor parallelism);
  * row-parallel weights (wo, mlp down, out_proj): input dim over "model"
    so they consume model-sharded activations, output dim over "data";
  * MoE expert banks (w_gate/w_up/w_down, shape (L, E, d, f)):
      - moe_partition="expert": expert axis E over "model" (expert
        parallelism — DeepSeek, 64 experts >= 16-way axis), d_model over
        "data";
      - moe_partition="ffn": d_ff_expert over "model" (tensor parallelism
        inside each expert — Mixtral, 8 experts < 16-way axis), d_model over
        "data";
  * embedding table (V, d): vocab over "model" (the tied unembed projection
    is then column-parallel), d over "data";
  * biases, norm scales and other vectors/scalars: replicated.

Every assignment passes a HARD divisibility guard: a dim whose size does not
divide its mesh-axis size stays unsharded (None). This is what makes one
table safe across the whole zoo — e.g. gemma3's 8 KV-head projection stays
replicated on a 16-way model axis instead of crashing the partitioner.

FFIP exactness note: these specs shard the *operands* of the GEMM provider;
data-parallel batch splits and output-dim (N) tensor splits never split the
inner K contraction of a kernel invocation, and K-dim ("data") sharding is
combined by XLA's all-gather/reduce in int32 accumulators, so the paper's
bit-exact int8 claim survives sharding (tests/test_dist_rules.py proves it).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# Leaves that are never worth sharding (biases, norm params, scalars, and
# the tiny per-output-channel int8 epilogue vectors from repro.prepare).
_REPLICATED_LEAVES = frozenset({"b", "bias", "scale", "step", "pos",
                                "zp", "neg_beta", "colsum"})
# Row-parallel projections: they consume model-sharded activations.
_ROW_PARALLEL_PARENTS = frozenset({"wo", "down", "out_proj"})
# Stacked per-expert weight banks from moe_init.
_MOE_EXPERT_LEAVES = frozenset({"w_gate", "w_up", "w_down"})


def _axis_sizes(mesh) -> Dict[str, int]:
    """{axis_name: size} — duck-typed so shape-only mesh stand-ins work."""
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


def _batch_axes(mesh, batch_size: Optional[int] = None):
    """The mesh axes a batch dim is split over, degrading gracefully.

    Prefers ("pod", "data") jointly, then "data", then "pod": a batch that
    divides the data axis but not pod*data still gets data-parallel sharding
    instead of silently replicating across every chip (same ladder idea as
    the shard_map spec chooser in models/attention.py). With no batch_size
    the full ladder head is returned and the caller's guard decides.
    """
    names = tuple(mesh.axis_names)
    present = tuple(a for a in ("pod", "data") if a in names)
    if not present:
        return None
    sizes = _axis_sizes(mesh)
    singles = sorted(((a,) for a in present),
                     key=lambda c: -sizes[c[0]])   # widest axis first
    ladder = ([present] if len(present) > 1 else []) + singles
    if batch_size is None:
        axes = ladder[0]
    else:
        axes = next((cand for cand in ladder
                     if batch_size % _axes_size(cand, sizes) == 0), None)
        if axes is None:
            return None
    return axes if len(axes) > 1 else axes[0]


def _axes_size(axes, sizes: Dict[str, int]) -> int:
    if axes is None:
        return 1
    if isinstance(axes, tuple):
        n = 1
        for a in axes:
            n *= sizes[a]
        return n
    return sizes[axes]


def _guarded(axes_per_dim, shape, sizes) -> P:
    """Apply the divisibility guard: drop any axis that does not divide."""
    out = []
    for dim, axes in enumerate(axes_per_dim):
        n = _axes_size(axes, sizes)
        out.append(axes if (axes is not None and n > 0
                            and shape[dim] % n == 0) else None)
    return P(*out)


def _match_spec(path: str, shape: Tuple[int, ...], mesh,
                moe_partition: str = "expert") -> P:
    """Rule table for a single parameter leaf.

    path: "/"-joined tree path, e.g. "layers/attn/wq/w"; shape: leaf shape.
    Returns a PartitionSpec with exactly len(shape) entries.
    """
    if moe_partition not in ("expert", "ffn"):
        raise ValueError(f"moe_partition must be 'expert' or 'ffn', "
                         f"got {moe_partition!r}")
    sizes = _axis_sizes(mesh)
    parts = [p for p in path.split("/") if p]
    leaf = parts[-1] if parts else ""
    parent = parts[-2] if len(parts) > 1 else ""
    if parent == "q" and len(parts) > 2:
        # offline-quantized leaves (qw/neg_beta/colsum under a "q" subtree,
        # repro.prepare) shard like the projection that owns them, so e.g.
        # wo/q/qw is row-parallel exactly like wo/w.
        parent = parts[-3]
    ndim = len(shape)
    axes: list = [None] * ndim

    if ndim <= 1 or leaf in _REPLICATED_LEAVES:
        return P(*axes)

    if leaf in _MOE_EXPERT_LEAVES and ndim >= 3:
        # (..., E, d_model, d_ff) for w_gate/w_up; (..., E, d_ff, d_model)
        # for w_down. Leading dims (layer stack) stay replicated.
        e, d_in, d_out = ndim - 3, ndim - 2, ndim - 1
        dm = d_in if leaf != "w_down" else d_out      # the d_model dim
        df = d_out if leaf != "w_down" else d_in      # the d_ff_expert dim
        if moe_partition == "expert":
            axes[e] = "model"
            axes[dm] = "data"
        else:  # "ffn": TP inside every expert
            axes[df] = "model"
            axes[dm] = "data"
    elif leaf == "table":
        # embedding (V, d): vocab over model => tied unembed is column-parallel
        axes[ndim - 2] = "model"
        axes[ndim - 1] = "data"
    elif parent in _ROW_PARALLEL_PARENTS:
        axes[ndim - 2] = "model"
        axes[ndim - 1] = "data"
    else:
        # generic column-parallel dense / conv / SSM weight
        axes[ndim - 2] = "data"
        axes[ndim - 1] = "model"

    if "model" in axes and "model" not in sizes:
        axes = [None if a == "model" else a for a in axes]
    if "data" in axes and "data" not in sizes:
        axes = [None if a == "data" else a for a in axes]
    return _guarded(axes, shape, sizes)


def _path_str(key_path) -> str:
    out = []
    for k in key_path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def param_specs(params: PyTree, mesh, moe_partition: str = "expert") -> PyTree:
    """PartitionSpec tree mirroring `params` (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _match_spec(_path_str(path), tuple(leaf.shape),
                                       mesh, moe_partition),
        params)


def data_specs(batch: PyTree, mesh) -> PyTree:
    """Data-parallel input specs: dim 0 over ("pod",)"data", rest replicated.

    Scalars are fully replicated; the (B,) per-slot decode position vector
    shards over the batch axes exactly like the (B, 1) token it accompanies.
    The divisibility guard applies: a global batch that does not divide the
    data axes is replicated rather than rejected.
    """
    sizes = _axis_sizes(mesh)

    def one(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        baxes = _batch_axes(mesh, shape[0])
        return _guarded([baxes] + [None] * (len(shape) - 1), shape, sizes)

    return jax.tree_util.tree_map(one, batch)


def cache_specs(cache: PyTree, mesh, *, batch: int) -> PyTree:
    """Decode/prefill cache specs: the batch dim is data-parallel.

    Cache leaves are stacked on leading layer-group dims — (L, B, ...), or
    (n_groups, period, B, ...) under the "hybrid_groups" subtree — so the
    batch dim position is known structurally from the path (init_cache's
    layout), with a size-equality scan only as fallback for foreign trees;
    size-matching alone would mis-shard when a stack dim happens to equal
    the batch size. KV caches additionally shard the kv-head dim over
    "model" when it divides, mirroring the attention projections: third from
    last in the blocked self-attention leaves (..., B, J, KV, hd, blk),
    second from last in the encoder's cross K/V rows (..., B, T, KV, hd).
    """
    sizes = _axis_sizes(mesh)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        ndim = len(shape)
        if ndim == 0:
            return P()
        axes: list = [None] * ndim
        parts = _path_str(path).split("/")
        bdim = 2 if parts[0] == "hybrid_groups" else 1
        if not (bdim < ndim and shape[bdim] == batch):
            bdim = next((d for d in range(ndim) if shape[d] == batch),
                        None)
        if bdim is not None:
            axes[bdim] = _batch_axes(mesh, batch)
        leaf_name = parts[-1]
        if leaf_name in ("k", "v") and ndim >= 4:
            kv_dim = ndim - (2 if parts[0] == "cross_kv" else 3)
            axes[kv_dim] = "model" if "model" in sizes else None
        return _guarded(axes, shape, sizes)

    return jax.tree_util.tree_map_with_path(one, cache)


def to_named(specs: PyTree, mesh) -> PyTree:
    """PartitionSpec tree -> NamedSharding tree on `mesh` (jit in_shardings)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
