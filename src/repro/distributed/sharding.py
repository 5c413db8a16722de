"""Sharding rules: param / batch / cache PartitionSpecs for the production
mesh.

Axes:
  pod    (multi-pod only)  pure data parallelism across pods; params are
                           replicated across pods, gradients all-reduce
                           over ('pod','data').
  data   FSDP: batch parallelism + ZeRO-3 parameter/optimizer sharding
         (weights shard their *input* dim over 'data'; XLA all-gathers
         them per layer and the backward reduce-scatters — classic FSDP
         realized through GSPMD annotations).
  model  tensor parallelism (attention heads / FFN columns / vocab) and
         expert parallelism (MoE expert dim).

Rules are name+shape driven: special-cases for embed / lm_head / expert
stacks / routers, then a generic "last dim -> model, second-to-last ->
data" for 2D+ weights, with divisibility checks (a dim that doesn't
divide stays replicated). 1D leaves (norms, biases) replicate.

Batch specs: tokens/labels shard over ('pod','data') on batch; decode
caches shard batch over data and heads (or sequence, when heads don't
divide) over model.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _axis(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _leading_nones(shape, n_tail):
    return (None,) * (len(shape) - n_tail)


def spec_for_param(path: str, shape, mesh: Mesh, *, serve: bool = False) -> P:
    """serve=False (train): FSDP x TP — weights shard input dim over 'data'
    (ZeRO-3 gather per layer) and output dim over 'model' (TP).
    serve=True: TP only — weights replicate over 'data' so a decode step
    never pays the per-layer FSDP all-gather (weights are read-only and
    batch-per-device is tiny; the gather would dominate the step)."""
    d_sz = _axis(mesh, "data")
    m_sz = _axis(mesh, "model")
    nd = len(shape)
    data_ax = None if serve else "data"

    # --- special cases ------------------------------------------------------
    if path.endswith("embed"):                       # (V, D): vocab -> model
        v, d = shape
        return P("model" if _div(v, m_sz) else None,
                 data_ax if (data_ax and _div(d, d_sz)) else None)
    if path.endswith("lm_head"):                     # (D, V)
        d, v = shape
        return P(data_ax if (data_ax and _div(d, d_sz)) else None,
                 "model" if _div(v, m_sz) else None)
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("w_gate", "w_up", "w_down") and nd >= 3:
        # expert stacks (..., E, D, F) / (..., E, F, D): experts -> model (EP)
        e, a, b = shape[-3:]
        return P(*_leading_nones(shape, 3),
                 "model" if _div(e, m_sz) else None,
                 data_ax if (data_ax and _div(a, d_sz)) else None,
                 None)
    if leaf in ("wq", "wk", "wv") and nd >= 3 and shape[-1] == shape[-2]:
        # per-head block-diagonal stacks (..., H, hd, hd): heads -> model
        h = shape[-3]
        return P(*_leading_nones(shape, 3),
                 "model" if _div(h, m_sz) else None, None, None)

    # --- generic ------------------------------------------------------------
    if nd >= 2:
        a, b = shape[-2], shape[-1]
        return P(*_leading_nones(shape, 2),
                 data_ax if (data_ax and _div(a, d_sz)) else None,
                 "model" if _div(b, m_sz) else None)
    return P()                                        # 1D: replicate


def param_specs(params_or_shapes, mesh: Mesh, *, serve: bool = False):
    """PartitionSpec tree matching the param tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_or_shapes)
    specs = [spec_for_param(_path_str(p), l.shape, mesh, serve=serve)
             for p, l in flat]
    return jax.tree.unflatten(treedef, specs)


def opt_state_specs(params_or_shapes, mesh: Mesh):
    """Adam m/v mirror the param sharding; step is replicated."""
    ps = param_specs(params_or_shapes, mesh)
    return {"m": ps, "v": ps, "step": P()}


# ---------------------------------------------------------------------------
# batch / activation specs
# ---------------------------------------------------------------------------

def _batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def batch_specs(mesh: Mesh, batch_shapes: dict, *, seq_shard: bool = False):
    """Specs for a train/prefill batch dict. Batch dim -> (pod, data) when
    divisible; optionally shard sequence over 'model' (SP for long prefill)."""
    baxes = _batch_axes(mesh)
    bsz = int(np.prod([_axis(mesh, a) for a in baxes]))
    m_sz = _axis(mesh, "model")

    def one(leaf):
        shape = leaf.shape
        b = shape[0]
        first = baxes if _div(b, bsz) else (
            "data" if _div(b, _axis(mesh, "data")) else None)
        rest = [None] * (len(shape) - 1)
        if seq_shard and len(shape) >= 2 and _div(shape[1], m_sz):
            rest[0] = "model"
        return P(first, *rest)

    return jax.tree.map(one, batch_shapes)


def cache_specs(mesh: Mesh, cache_shapes, batch: int):
    """Decode-cache specs.

    The batch dim is identified *by size* (the serving batch is known),
    never by position — scan-stacked segment caches carry a leading
    period dim. Rules: batch -> 'data' when divisible; then the largest
    remaining divisible dim (sequence for KV rings, state width for
    recurrent states) -> 'model' (context parallelism for decode)."""
    d_sz = _axis(mesh, "data")
    m_sz = _axis(mesh, "model")

    def one(leaf):
        shape = leaf.shape
        nd = len(shape)
        spec = [None] * nd
        bdim = None
        if batch > 1:
            for i, s in enumerate(shape):
                if s == batch:
                    bdim = i
                    break
        if bdim is not None and _div(shape[bdim], d_sz):
            spec[bdim] = "data"
        cand = [i for i in range(nd) if i != bdim and spec[i] is None
                and _div(shape[i], m_sz) and shape[i] >= m_sz]
        if cand:
            best = max(cand, key=lambda i: shape[i])
            spec[best] = "model"
        return P(*spec)

    return jax.tree.map(one, cache_shapes)


def named_sharding_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# flow-table shard mesh (streaming tier)
# ---------------------------------------------------------------------------

def flow_shard_mesh(n_shards: Optional[int] = None,
                    n_data: int = 1) -> Mesh:
    """2D ('shard', 'data') mesh for the sharded flow-table tier.

    'shard' partitions flow-table *buckets* (storage: each shard owns
    bucket % n_shards == s); 'data' is pure batch parallelism over the
    partitioned classify lanes and the backend slices — registers are
    replicated along it (DESIGN.md §16). ``n_data=1`` (the default)
    degenerates to the historical 1D behavior; ``n_shards`` defaults to
    every local device not consumed by 'data' — on a CPU host-platform
    run that is whatever ``--xla_force_host_platform_device_count``
    provided. The flow-table axes are deliberately separate from the
    ('data','model') training axes above: bucket shards are storage
    partitions, not tensor parallelism.
    """
    n = n_shards or max(1, jax.local_device_count() // n_data)
    return jax.make_mesh((n, n_data), ("shard", "data"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def as_flow_mesh(mesh: Mesh) -> Mesh:
    """Normalize a flow-table mesh to the 2D ('shard', 'data') form.

    A legacy 1D ('shard',) mesh gains a size-1 'data' axis (same
    devices, same shard blocks), so every shard_map body can reference
    both axes unconditionally; a 2D ('shard', 'data') mesh passes
    through. Anything else is not a flow-table mesh. The result has Auto
    axes whatever the input had: the streaming tier leaves the placement
    of everything outside its ``shard_map`` bodies to the compiler, which
    Explicit axes (``jax.make_mesh``'s default) forbid.
    """
    if mesh.axis_names == ("shard",):
        devices = mesh.devices.reshape(-1, 1)
    elif mesh.axis_names == ("shard", "data"):
        devices = mesh.devices
    else:
        raise ValueError(
            f"flow-table mesh must have axes ('shard',) or ('shard', 'data'), "
            f"got {mesh.axis_names}")
    return Mesh(devices, ("shard", "data"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def flow_table_sharding(mesh: Mesh, state_tree):
    """NamedSharding tree placing a sharded flow-table pytree on ``mesh``.

    Every leaf shards its leading (n_shards) dim over 'shard' and
    replicates the rest — registers are (n_shards, n_local), the epoch
    register is (n_shards,); both derive from ndim, so the rule survives
    new registers being added to the state. On a 2D ('shard', 'data')
    mesh the registers replicate along 'data' (the data axis parallelizes
    classify lanes and backend slices, never storage).
    """
    spec = jax.tree.map(
        lambda a: P("shard", *([None] * (a.ndim - 1))), state_tree)
    return named_sharding_tree(mesh, spec)


def shard_hint(x, *spec):
    """Best-effort with_sharding_constraint: a no-op when traced outside a
    mesh context (single-device tests), a GSPMD hint inside one (dry-run /
    launcher). Keeps model code mesh-agnostic."""
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (RuntimeError, ValueError):
        # RuntimeError: no ambient mesh (single-device tests);
        # ValueError: spec rank does not divide this shape — both mean
        # "no hint applies here", never a real serving failure
        return x


def _ambient_mesh():
    try:
        from jax.interpreters.pxla import thread_resources
        m = thread_resources.env.physical_mesh
        return m if m.devices.size > 1 else None
    except (ImportError, AttributeError):
        # private jax internals moved — treat as "no ambient mesh"
        return None


def hint_batch_heads(x, heads_dim: int = 2):
    """Pin an activation (B, S, H, hd)-like tensor: batch over the batch
    axes, heads over 'model' (when divisible). No-op without a mesh.

    This is the anti-"involuntary full remat" hint: it keeps q/k/v in the
    head-sharded layout through the blockwise attention scan, so GSPMD
    never invents a batch<->head resharding mid-loop."""
    m = _ambient_mesh()
    if m is None:
        return x
    baxes = ("pod", "data") if "pod" in m.axis_names else ("data",)
    bsz = int(np.prod([m.shape[a] for a in baxes]))
    spec = [None] * x.ndim
    if x.shape[0] % bsz == 0:
        spec[0] = baxes
    elif x.shape[0] % m.shape["data"] == 0:
        spec[0] = "data"
    if heads_dim < x.ndim and x.shape[heads_dim] % m.shape["model"] == 0:
        spec[heads_dim] = "model"
    return shard_hint(x, *spec)
