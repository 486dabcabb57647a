"""Logical-axis sharding resolver (DESIGN.md §5).

Every parameter in ``repro.models`` carries a parallel *logical axis*
annotation (the ``*_AXES`` tables next to each ``*_init``); this module
resolves those annotations against a concrete mesh into ``PartitionSpec``
trees.  The mapping is megatron-style tensor parallelism over ``"model"``
(heads / mlp / experts / vocab sharded, ``embed`` dim replicated) with the
batch over the data-parallel axes (``"pod"`` and/or ``"data"``).

The resolver is *shape-driven*: ``_fit`` reconciles a wanted spec against
the actual array shape — it pads for stacked leading axes (parameters are
stacked over blocks by ``jax.vmap``), drops mesh axes that do not exist on
the mesh, refuses to shard a dim the mesh axis does not divide, and never
uses one mesh axis twice.  The same resolver therefore works on the
production 16×16 ``("data", "model")`` pod mesh, the 2×16×16
``("pod", "data", "model")`` multi-pod mesh, the 2×2 debug mesh, and all
the degenerate (1-device, axis-size-1) meshes in between.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.attention import ATTN_AXES
from repro.models.layers import (CONV, EMBED, EXPERT, HEADS, KV_HEADS, MLP,
                                 MLP_AXES, QKV, STATE, VOCAB)
from repro.models.mamba import MAMBA_AXES
from repro.models.moe import MOE_AXES
from repro.models.rwkv6 import RWKV_AXES, RWKV_CM_AXES

# logical axis -> mesh axis it shards over (None = always replicated).
# ``embed`` stays replicated: the paired dim of every matmul is the
# tensor-parallel one, so activations enter/leave TP regions replicated
# over "model" and the all-reduce happens on the output projection.
MESH_RULES: dict[str, str | None] = {
    EMBED: None,
    MLP: "model",
    HEADS: "model",
    KV_HEADS: "model",
    QKV: "model",
    VOCAB: "model",
    EXPERT: "model",
    CONV: None,
    STATE: None,
}

# data-parallel axes in outer-to-inner order (subset present on the mesh
# is used; see launch/mesh.py).
DP_AXES = ("pod", "data")

# module key (pytree path component) -> {param name: logical axes}
_MODULE_AXES: dict[str, dict] = {
    "attn": ATTN_AXES,
    "xattn": ATTN_AXES,
    "mlp": MLP_AXES,
    "moe": MOE_AXES,
    "mamba": MAMBA_AXES,
    "rwkv": RWKV_AXES,
    "cmix": RWKV_CM_AXES,
    "embed": {"tokens": (VOCAB, EMBED)},
    "lm_head": {"w": (EMBED, VOCAB)},
    "frontend_proj": {"w": (None, EMBED)},
}


def _mesh_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def _fit(mesh, shape, want) -> P:
    """Reconcile a wanted spec against an actual shape on a mesh.

    ``want`` is a per-dim tuple of mesh-axis names (a str, a tuple of
    strs, or None).  Rules, in order:

    * shorter ``want`` than rank: pad with None on the *left* (stacked
      leading axes — blocks-stacked params, microbatch dims);
      longer: drop leading entries.
    * a mesh axis that is not on the mesh is ignored;
    * each mesh axis is used at most once across the whole spec;
    * a dim is only sharded if the (product of) axis sizes divides it —
      otherwise the axis is dropped (replicate rather than fail, which is
      what makes 1-device and axis-size-1 meshes degenerate no-ops).
    """
    sizes = _mesh_sizes(mesh)
    shape = tuple(shape)
    want = tuple(want)
    rank = len(shape)
    if len(want) < rank:
        want = (None,) * (rank - len(want)) + want
    elif len(want) > rank:
        want = want[len(want) - rank:]

    used: set[str] = set()
    out = []
    for dim, w in zip(shape, want):
        axes = (w,) if isinstance(w, str) else tuple(w or ())
        kept = []
        prod = 1
        for a in axes:
            if a not in sizes or a in used:
                continue
            if dim % (prod * sizes[a]) != 0:
                continue
            kept.append(a)
            prod *= sizes[a]
        used.update(kept)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:  # canonical short form
        out.pop()
    return P(*out)


def _dp(mesh) -> tuple[str, ...]:
    return tuple(a for a in DP_AXES if a in _mesh_sizes(mesh))


def _logical_to_want(axes) -> tuple:
    return tuple(None if a is None else MESH_RULES.get(a) for a in axes)


def _path_keys(path) -> list[str]:
    return [p.key if hasattr(p, "key") else str(getattr(p, "idx", p))
            for p in path]


def _param_want(path) -> tuple | None:
    """Logical-axes lookup for one parameter leaf by its pytree path."""
    keys = _path_keys(path)
    for key in reversed(keys[:-1]):
        table = _MODULE_AXES.get(key)
        if table is not None:
            axes = table.get(keys[-1])
            return None if axes is None else _logical_to_want(axes)
    return None  # norms, biases, unknown leaves: replicate


def param_specs(params, mesh):
    """Resolve a params pytree (arrays or ShapeDtypeStructs) to a matching
    tree of ``PartitionSpec``.  Unannotated leaves (norm scales, biases)
    are replicated; annotated leaves shard per ``MESH_RULES``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        want = _param_want(path)
        specs.append(P() if want is None else _fit(mesh, leaf.shape, want))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_specs(batch, mesh):
    """Input batches shard dim 0 (the global batch) over the data axes."""
    dp = _dp(mesh)
    return jax.tree.map(
        lambda a: _fit(mesh, a.shape, (dp,) + (None,) * (len(a.shape) - 1)),
        batch)


def state_specs(state, mesh):
    """Decode-state trees: batch dim over data axes, KV heads over "model".

    State leaves are stacked over blocks ([n_blocks, B, ...]); the per-slot
    ``pos`` bookkeeping arrays stay replicated.
    """
    dp = _dp(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    specs = []
    for path, leaf in flat:
        name = _path_keys(path)[-1]
        rank = len(leaf.shape)
        if name == "pos" or rank < 3:
            specs.append(P())
        elif name in ("k", "v") and rank == 5:
            # [n_blocks, B, S, Hkv, dh]
            specs.append(_fit(mesh, leaf.shape, (None, dp, None, "model", None)))
        elif name in ("k_scale", "v_scale") and rank == 4:
            specs.append(_fit(mesh, leaf.shape, (None, dp, None, "model")))
        else:  # SSM / conv / WKV states: [n_blocks, B, ...]
            specs.append(_fit(mesh, leaf.shape,
                              (None, dp) + (None,) * (rank - 2)))
    return jax.tree_util.tree_unflatten(treedef, specs)


def constrain_activations(x, mesh, *, seq_axis: bool = False):
    """Constrain a residual-stream activation [B, S, D] at a layer boundary.

    Batch over the data axes; with ``seq_axis`` the *sequence* dim is
    sharded over "model" (sequence parallelism — bounds the remat storage
    of 96-layer models; DESIGN.md §5).  ``mesh=None`` is the unsharded
    CPU/smoke path and is a no-op.
    """
    if mesh is None:
        return x

    dp = _dp(mesh)

    def con(a):
        want = (dp, "model" if seq_axis else None) + (None,) * (len(a.shape) - 2)
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, _fit(mesh, a.shape, want)))

    return jax.tree.map(con, x)


def shard_put(tree, mesh, specs=None):
    """Convenience: ``device_put`` a tree with resolved (or given) specs."""
    specs = param_specs(tree, mesh) if specs is None else specs
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(tree, shardings)


def stacked_specs(tree, mesh, *, axis: str = "shard"):
    """Specs for shard-stacked arrays: dim 0 over ``axis``, rest replicated.

    `repro.shard` stacks every per-shard reference array along a leading
    ``[num_shards, ...]`` axis; this resolves that convention against a
    1-D ``(axis,)`` mesh through the same `_fit` rules as the model
    params (a mesh without the axis, or a leading dim the axis size
    does not divide, degrades to replication instead of failing).
    """
    return jax.tree.map(
        lambda a: _fit(mesh, a.shape, (axis,) + (None,) * (len(a.shape) - 1)),
        tree)


def shard_mesh(num_shards: int, *, axis: str = "shard"):
    """1-D device mesh over the first ``num_shards`` devices, or None.

    Returns None when ``num_shards == 1`` (nothing to place), and on the
    CPU backend when fewer than ``num_shards`` devices exist (callers
    then run a vmapped single-device execution of the same program —
    the test path).  On an accelerator too few devices is an error: a
    sharded deployment never silently runs its shards on one chip.
    """
    import numpy as np

    if num_shards <= 1:
        return None
    if jax.device_count() < num_shards:
        if jax.default_backend() != "cpu":
            raise ValueError(
                f"num_shards={num_shards} needs {num_shards} devices; "
                f"found {jax.device_count()} {jax.default_backend()} "
                f"device(s)")
        return None
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:num_shards]), (axis,))
