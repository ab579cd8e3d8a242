"""Logical-axis sharding rules: param/cache/input pytrees -> PartitionSpecs.

Conventions (see ``repro.models.layers``):
  * ``*_in``   [d_in, d_out], d_out tensor-parallel      -> P(fsdp, tp)
  * ``*_out``  [d_in, d_out], d_in  tensor-parallel      -> P(tp, fsdp)
  * ``embed``  [vocab, d]                                 -> P(tp, fsdp)
  * ``w_experts_{gate,up}`` [E, d, f]  (expert parallel)  -> P(tp, fsdp, ·)
  * ``w_experts_down``      [E, f, d]                     -> P(tp, ·, fsdp)
  * 1-D scales/biases                                     -> replicated

Rules apply to the TRAILING dims; leading stack dims (scan-over-layers /
unit stacking) are always unsharded.  Every dim is guarded by a
divisibility check — a dim that doesn't divide its mesh axis is replicated
rather than failing, so one rule set serves every arch (e.g. gemma3's 4 KV
heads on a 16-way model axis fall back to head-dim sharding in the cache
rules below).

The multi-pod design: weights are FSDP-sharded *within* a pod (``data``)
and replicated *across* pods; the batch spans ("pod", "data").  Cross-pod
traffic is therefore exactly the gradient all-reduce — the target of the
compressed grad-sync optimization.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def _guard(spec: tuple, shape: tuple, mesh: Mesh) -> P:
    """Replicate any dim that doesn't divide its mesh axis; trim/extend."""
    spec = (None,) * (len(shape) - len(spec)) + tuple(spec[-len(shape):] if spec else ())
    out = []
    for dim, ax in zip(shape, spec):
        out.append(ax if ax is not None and dim % _axis_size(mesh, ax) == 0 else None)
    return P(*out)


# trailing-name -> trailing-dims spec (applied to the last len(spec) dims)
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("model", "data")),
    (r"w_experts_(gate|up)$", ("model", "data", None)),
    (r"w_experts_down$", ("model", None, "data")),
    (r"r_gates$", ("model", None, None)),
    (r"conv_w$", (None, "model")),
    (r".*_in$", ("data", "model")),
    (r".*_out$", ("model", "data")),
]

_CACHE_RULES: list[tuple[str, tuple, tuple]] = [
    # (name, primary trailing spec, fallback trailing spec)
    (r"^(k|v)$", ("batch", None, "model", None), ("batch", None, None, "model")),
    (r"^state$", ("batch", "model", None, None), ("batch", None, None, None)),
    (r"^conv$", ("batch", None, "model"), ("batch", None, None)),
    (r"^S$", ("batch", "model", None, None), ("batch", None, None, None)),
    (r"^(n|c|h)$", ("batch", "model", None), ("batch", None, None)),
    (r"^m$", ("batch", "model"), ("batch", None)),
]

BATCH_AXES = ("pod", "data")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(abstract_params, mesh: Mesh):
    """PartitionSpec tree for a param pytree (by path-name rules)."""

    def assign(path, leaf):
        name = _path_str(path).split("/")[-1]
        shape = leaf.shape
        if len(shape) <= 1:
            return P()
        for pat, spec in _PARAM_RULES:
            if re.search(pat, name):
                return _guard(spec, shape, mesh)
        return P()  # replicate anything unmatched

    return jax.tree_util.tree_map_with_path(assign, abstract_params)


def batch_axes_for(mesh: Mesh, batch_size: int):
    """Largest batch sharding the mesh supports for this batch size."""
    full = tuple(a for a in BATCH_AXES if a in mesh.shape)
    if full and batch_size % _axis_size(mesh, full) == 0:
        return full
    for a in reversed(full):
        if batch_size % _axis_size(mesh, (a,)) == 0:
            return (a,)
    return None


def cache_pspecs(abstract_cache, mesh: Mesh, batch_size: int):
    batch = batch_axes_for(mesh, batch_size)

    def assign(path, leaf):
        name = _path_str(path).split("/")[-1]
        for pat, spec, fallback in _CACHE_RULES:
            if re.search(pat, name):
                primary = list(batch if a == "batch" else a for a in spec)
                fb = list(batch if a == "batch" else a for a in fallback)
                # Long-context decode with unshardable batch (e.g. B=1 at
                # 500k): sequence-parallel KV cache over the data axis.
                if re.match(r"^(k|v)$", name) and batch is None:
                    primary[1] = "data"
                    fb[1] = "data"
                cand = _guard(tuple(primary), leaf.shape, mesh)
                # If the model-parallel dim was dropped by the guard, try the
                # fallback (e.g. shard head_dim when KV heads don't divide).
                if "model" in spec and "model" not in cand:
                    return _guard(tuple(fb), leaf.shape, mesh)
                return cand
        return P()

    return jax.tree_util.tree_map_with_path(assign, abstract_cache)


def input_pspecs(specs: dict, mesh: Mesh, *, seq_shard: bool = False):
    """Input batch shardings: batch over (pod, data); optional SP on seq."""

    def assign(name, leaf):
        batch = batch_axes_for(mesh, leaf.shape[0])
        rest = [None] * (len(leaf.shape) - 1)
        if seq_shard and len(leaf.shape) >= 2 and leaf.shape[1] % _axis_size(mesh, "model") == 0:
            rest[0] = "model"
        return P(batch, *rest)

    return {k: assign(k, v) for k, v in specs.items()}


def opt_pspecs(param_specs):
    """AdamW state: moments follow the params; step is replicated."""
    from ..optim.adamw import AdamWState

    return AdamWState(step=P(), mu=param_specs,
                      nu=jax.tree.map(lambda s: s, param_specs))


def to_named(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation sharding constraints (anchor SPMD propagation inside scans).
#
# Model code calls ``constrain(x, ("batch", None, "model"))`` at key points
# (embedding output, q/k/v, MLP hidden).  Without these anchors the
# partitioner replicates the flash-attention inner loops — measured 256× FLOP
# waste on the first dry-run (see EXPERIMENTS.md §Perf iteration 0).
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Mesh | None = None

def set_active_mesh(mesh: Mesh | None):
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh():
    return _ACTIVE_MESH


def constrain(x, spec: tuple):
    """Apply a guarded with_sharding_constraint; no-op without a mesh.

    ``"batch"`` resolves to the (pod, data) axes that divide the dim;
    any other axis name is kept only if the dim divides it.
    """
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    resolved = []
    for dim, ax in zip(x.shape, spec):
        if ax == "batch":
            ax = batch_axes_for(mesh, dim)
        if ax is None:
            resolved.append(None)
        elif dim % _axis_size(mesh, ax) == 0:
            resolved.append(ax)
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))
