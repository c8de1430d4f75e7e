"""Production partition-spec rules over abstract param/input pytrees.

Rules are assigned by parameter name (path in the pytree) and expressed
as mesh-independent :class:`~jax.sharding.PartitionSpec` trees
(:func:`param_specs`); the mesh-aware entry points
(:func:`param_shardings`, :func:`opt_state_shardings`,
:func:`batch_shardings`, :func:`cache_shardings`) turn them into
``NamedSharding``s after repairing illegal placements with
:func:`fit_spec`. See ``repro/dist/__init__.py`` for the rule table.
"""
from __future__ import annotations

from collections.abc import Sequence
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import dp_axes

# attention module names across the decoder / encoder / cross-decoder
_ATTN_KEYS = ("attn", "self", "cross")
# kernels sharded on their LAST dim (output features)
_COL_PARALLEL = ("q", "k", "v", "up", "gate", "in_proj")
# kernels sharded on dim -2 (input features)
_ROW_PARALLEL = ("o", "down", "out_proj")


def output_shards(mesh, site: str) -> int:
    """How many ways ``mesh`` splits the output channels of the projection
    at ``site`` (a site name such as ``layer_3/attn/q``): the ``model``
    degree for a column-parallel kernel, which :func:`param_specs` shards
    on its last dim, else 1. Row-parallel kernels (``o``, ``down``) split
    their input; routed experts (``moe/up``) their expert axis.
    """
    parts = site.split("/")
    routed = len(parts) > 1 and parts[-2] == "moe"
    if parts[-1] in _COL_PARALLEL and not routed:
        return int(dict(mesh.shape).get("model", 1))
    return 1


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _axis_size(mesh, axis) -> int:
    """Size of one spec entry: a mesh axis name or a tuple of them."""
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def fit_spec(spec: P, shape: Sequence[int], mesh) -> P:
    """Repair ``spec`` so every assignment divides its dim on ``mesh``.

    An axis assigned to a non-divisible dim is relocated to the nearest
    free (None) dim that IS divisible — e.g. 16-way ``model`` on an
    8-head kv dim moves to the adjacent head_dim; ``data`` on a batch=1
    decode moves to the seq dim. Ties prefer the later (inner) dim.
    With no legal dim the axis is dropped (replicated) — always safe,
    never wrong, just less parallel. A spec longer than the shape is
    truncated (its extra axes are dropped the same way).

    **Joint placement** — when a *tuple* of mesh axes contends for one
    dim and their product does not divide it (the multi-pod
    ``("pod", "data")`` batch split at ``batch < dp_size``), the tuple
    is SPLIT rather than moved whole: the largest-product sub-tuple
    that does divide stays on the dim, and each remaining axis is
    relocated independently by the single-axis rule. A 2×16 pod×data
    fleet with global batch 8 keeps ``pod`` (2 | 8) on the batch dim
    and moves ``data`` (16) to the sequence dim, instead of giving up
    all 32-way data parallelism on the batch at once.
    """
    entries = list(spec)[: len(shape)] + [None] * (len(shape) - len(spec))

    def relocate_one(i, axis):
        n = _axis_size(mesh, axis)
        cands = [
            j
            for j, e in enumerate(entries)
            if e is None and shape[j] % n == 0
        ]
        if cands:
            best = min(cands, key=lambda j: (abs(j - i), 0 if j > i else 1))
            entries[best] = axis

    for i, axis in enumerate(list(entries)):
        if axis is None:
            continue
        n = _axis_size(mesh, axis)
        if n <= 1 or shape[i] % n == 0:
            continue
        entries[i] = None
        if isinstance(axis, tuple) and len(axis) > 1:
            # joint placement: keep the biggest divisible sub-tuple on
            # this dim, relocate the leftover axes one by one
            best_sub, best_n = (), 1
            for mask in range(1, 1 << len(axis)):
                sub = tuple(
                    a for k, a in enumerate(axis) if mask & (1 << k)
                )
                sn = _axis_size(mesh, sub)
                if shape[i] % sn == 0 and sn > best_n:
                    best_sub, best_n = sub, sn
            if best_sub:
                entries[i] = best_sub if len(best_sub) > 1 else best_sub[0]
            for a in axis:
                if a not in best_sub:
                    relocate_one(i, a)
        else:
            relocate_one(i, axis)
    return P(*entries)


def _rule_for(path, leaf) -> P:
    """Mesh-independent spec for one named parameter leaf."""
    ndim = getattr(leaf, "ndim", None)
    if ndim is None:
        ndim = len(getattr(leaf, "shape", ()))
    none = [None] * ndim
    keys = [
        str(k.key)
        for k in path
        if hasattr(k, "key")  # DictKey; skip SequenceKey indices
    ]

    if "embed" in keys and keys[-1] == "table":
        # [V, d]: vocab-sharded embedding + tied unembedding
        sp = list(none)
        sp[0] = "model"
        return P(*sp)

    if keys and keys[-1] == "w" and "router" not in keys:
        name = keys[-2] if len(keys) >= 2 else ""
        if name in _COL_PARALLEL and ndim >= 2:
            sp = list(none)
            sp[-1] = "model"
            return P(*sp)
        if name in _ROW_PARALLEL and ndim >= 2:
            sp = list(none)
            sp[-2] = "model"
            return P(*sp)

    if "moe" in keys and keys[-1] in ("gate", "up", "down") and ndim >= 3:
        # stacked expert tensors [np, E, d, ff] / [np, E, ff, d]:
        # expert-parallel over the model axis
        sp = list(none)
        sp[1] = "model"
        return P(*sp)

    # norms, biases, router, ssm conv/A/dt/D scalars: replicated
    return P(*none)


def param_specs(a_params: Any, *, replicate_kv: bool = False) -> Any:
    """PartitionSpec pytree matching ``a_params`` (abstract or concrete).

    ``replicate_kv=True`` replicates the k/v projection kernels —
    serving configs keep kv-heads < TP degree, and replicated kv avoids
    GSPMD resharding the score tensor every layer (§Perf iteration 4).
    """

    def one(path, leaf):
        sp = _rule_for(path, leaf)
        if replicate_kv:
            keys = [str(k.key) for k in path if hasattr(k, "key")]
            in_attn = any(k in _ATTN_KEYS for k in keys)
            if in_attn and len(keys) >= 2 and keys[-2] in ("k", "v"):
                return P(*([None] * len(sp)))
        return sp

    return jax.tree_util.tree_map_with_path(one, a_params)


def param_shardings(
    mesh, a_params: Any, *, replicate_kv: bool = False
) -> Any:
    """NamedSharding pytree for the params of one model on ``mesh``."""
    specs = param_specs(a_params, replicate_kv=replicate_kv)
    return jax.tree.map(
        lambda a, s: NamedSharding(mesh, fit_spec(s, a.shape, mesh)),
        a_params,
        specs,
    )


def opt_state_shardings(mesh, a_params: Any, **kw) -> Any:
    """Adam m/v mirror the param layout (same shapes, fp32)."""
    return param_shardings(mesh, a_params, **kw)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _batch_axis(mesh):
    dpax = dp_axes(mesh)
    if not dpax:
        return None
    return dpax if len(dpax) > 1 else dpax[0]


def batch_shardings(mesh, batch: Any) -> Any:
    """Inputs: leading (batch) dim over the data-parallel axes."""
    baxis = _batch_axis(mesh)

    def one(a):
        ndim = getattr(a, "ndim", 0)
        if not ndim:
            return replicated(mesh)
        spec = P(*([baxis] + [None] * (ndim - 1)))
        return NamedSharding(mesh, fit_spec(spec, a.shape, mesh))

    return jax.tree.map(one, batch)


def cache_shardings(
    mesh, a_cache: Any, *, seq_shard: bool = False, paged: bool = False
) -> Any:
    """Decode caches: batch over dp; kv-heads (or seq) over model.

    Cache leaves are period-stacked ``[np, B, ...]``. Attention k/v
    ``[np, B, T, KV, hd]`` put ``model`` on the kv-head dim, or on the
    seq dim with ``seq_shard=True`` (long-context decode: partial
    softmax over a seq-sharded cache, §Perf iteration 3). SSM states
    ``[np, B, H, N, P]`` shard the head dim; conv buffers shard their
    channel dim.

    ``paged=True`` declares the paged layout
    (:func:`repro.models.model.init_paged_cache`): attention k/v leaves
    are a page pool ``[np, n_blocks, bs, KV, hd]`` whose page axis is
    **replicated** — block tables index the pool globally, so sharding
    pages over ``data`` would turn every table gather into a
    cross-replica collective. ``model`` stays on the kv-head dim
    (``seq_shard`` moves it to the within-page dim, which only helps
    when ``block_size`` spans the model axis — rarely what you want; the
    kv-head default is right for paged serving). SSM/conv leaves are
    still slot-major and shard exactly as the contiguous layout.
    """
    baxis = _batch_axis(mesh)

    def one(path, a):
        ndim = getattr(a, "ndim", 0)
        entries = [None] * ndim
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        kv_leaf = name in ("k", "v") and ndim >= 5
        if ndim >= 2 and not (paged and kv_leaf):
            entries[1] = baxis
        if kv_leaf:
            entries[2 if seq_shard else 3] = "model"
        elif name == "state" and ndim >= 3:
            entries[2] = "model"
        elif name == "conv" and ndim >= 3:
            entries[-1] = "model"
        return NamedSharding(mesh, fit_spec(P(*entries), a.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, a_cache)


def swap_shardings(mesh, a_swapped: Any) -> Any:
    """Staging shardings for one slot's swapped-out cache bundle.

    Swap preemption stages a slot's cache state through the host
    (:meth:`repro.serve.cache.PagedCacheManager.swap_out` /
    ``swap_in``); on the way back in, each leaf should land on the mesh
    already laid out like the pool it is scattered into, so the
    ``.at[...].set`` needs no resharding collective. Bundle leaves have
    the slot/batch dim removed relative to :func:`cache_shardings`:

    * K/V page bundles ``[np, n_pages, bs, KV, hd]`` — ``model`` on the
      kv-head dim, page axis replicated (matching the paged pool rule);
    * SSM state rows ``[np, H, N, P]`` — ``model`` on the head dim;
    * conv rows ``[np, K-1, C]`` — ``model`` on the channel dim.

    Anything else is replicated. Illegal placements are repaired with
    :func:`fit_spec` like every other rule table.
    """

    def one(path, a):
        ndim = getattr(a, "ndim", 0)
        entries = [None] * ndim
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        if name in ("k", "v") and ndim >= 5:
            entries[3] = "model"
        elif name == "state" and ndim >= 2:
            entries[1] = "model"
        elif name == "conv" and ndim >= 2:
            entries[-1] = "model"
        return NamedSharding(mesh, fit_spec(P(*entries), a.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, a_swapped)


def block_table_sharding(mesh) -> NamedSharding:
    """Block tables are small int32 host state — replicated everywhere
    (every shard of the pool needs the full logical→physical map)."""
    return replicated(mesh)
