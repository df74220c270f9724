"""Logical-axis → mesh PartitionSpec resolution (DP/FSDP/TP/EP/SP).

Two rule tables:

``TRAIN_RULES``  2-D sharding for training: "embed" (and other fan-in
                 dims) shard over the **data** axis (ZeRO/FSDP — params,
                 grads, and optimizer state are all sharded so 70B-class
                 models fit v5e HBM), TP dims over **model**, batch over
                 (pod, data). Pods are pure DP replicas (gradient
                 all-reduce crosses pods once per step) — the fault
                 containment boundary.

``SERVE_RULES``  latency-oriented pure TP for serving: params replicated
                 over data (no per-layer all-gather on the decode path),
                 TP dims over model, batch over (pod, data); for the
                 batch=1 long-context cell the KV cache time axis shards
                 over data instead (sequence parallelism).

Divisibility: any dim not divisible by its mesh axis size falls back to
replicated (None) for that dim — never a lowering failure.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "TRAIN_RULES",
    "SERVE_RULES",
    "spec_for_axes",
    "tree_pspecs",
    "tree_shardings",
    "batch_spec",
    "cache_pspecs",
    "maybe_shard",
]


def _ambient_mesh():
    """The mesh from either jax.set_mesh or the legacy ``with mesh:``.

    Both probes reach into version-dependent jax surfaces, so each is
    narrowed to the exact failure its jax version produces — a missing
    accessor (older/newer jax) degrades to the next probe; anything
    else is a real bug and propagates."""
    try:
        m = jax.sharding.get_abstract_mesh()
    except AttributeError:      # jax < get_abstract_mesh
        m = None
    if m is not None and not m.empty:
        return m
    try:
        from jax._src.mesh import thread_resources
    except ImportError:         # private module moved/removed
        return None
    pm = getattr(getattr(thread_resources, "env", None),
                 "physical_mesh", None)
    if pm is not None and not pm.empty:
        return pm
    return None


def maybe_shard(x: jax.Array, *axes):
    """with_sharding_constraint that degrades to identity off-mesh.

    ``axes`` entries are mesh axis names (or None); any axis missing from
    the ambient mesh, or not dividing the dim, is dropped. Used by layers
    (e.g. the MoE dispatch buffer) to pin internal activation shardings
    without making the layer mesh-dependent.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = []
    used = set()
    for dim, name in zip(x.shape, axes):
        if (name is None or name not in mesh.axis_names or name in used
                or (mesh.shape[name] and dim % mesh.shape[name] != 0)):
            spec.append(None)
        else:
            spec.append(name)
            used.add(name)
    return jax.lax.with_sharding_constraint(x, P(*spec))

TRAIN_RULES = {
    "embed": "data",
    "vocab": "model",
    "heads": "model",
    "kv": "model",
    "qdim": "model",
    "kvdim": "model",
    "mlp": "model",
    "experts": "model",
    "layers": None,
    None: None,
}

SERVE_RULES = {
    **TRAIN_RULES,
    "embed": None,          # replicate fan-in dims: no gather on decode path
}


def spec_for_axes(axes: tuple, shape: tuple, mesh: Mesh, rules: dict) -> P:
    """Logical axes tuple (+ concrete shape) → PartitionSpec."""
    out = []
    used = set()
    for dim, name in zip(shape, axes):
        mesh_axis = rules.get(name, None)
        if (
            mesh_axis is None
            or mesh_axis not in mesh.axis_names
            or mesh_axis in used
            or dim % mesh.shape[mesh_axis] != 0
        ):
            out.append(None)
        else:
            out.append(mesh_axis)
            used.add(mesh_axis)
    return P(*out)


def tree_pspecs(axes_tree, params_tree, mesh: Mesh, rules: dict):
    """Axes tree + params tree → PartitionSpec tree."""
    return jax.tree.map(
        lambda a, p: spec_for_axes(a, p.shape, mesh, rules),
        axes_tree, params_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree, params_tree, mesh: Mesh, rules: dict):
    specs = tree_pspecs(axes_tree, params_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh) -> P:
    """Batch dimension spec: (pod, data) when the pod axis exists."""
    if "pod" in mesh.axis_names:
        return P(("pod", "data"))
    return P("data")


def _dim_axis(dim: int, mesh: Mesh, axis: str) -> Optional[str]:
    if axis in mesh.axis_names and dim % mesh.shape[axis] == 0:
        return axis
    return None


def cache_pspecs(cache_tree, mesh: Mesh, *, seq_parallel: bool = False,
                 time_shard_model: bool = True):
    """PartitionSpecs for a decode cache pytree (by structural key).

    Leaf layouts (leading L = stacked layers / groups):
      q4:   k_packed/v_packed [L, B, Hkv, T, D/2]; k_scale… [L, B, Hkv, 1, D]
      fp:   k/v [L, B, T, Hkv, D]
      rwkv: s [L, B, H, d, d]; shift_* [L, B, 1, D]
      mamba: ssm [L, B, H, N, P]; conv [L, B, K-1, Ch]
      vlm cross_kv: k/v [L, B, T_img, Hkv, D]
      length [L, B]
      paged q4 (the serving engine's live pools):
            k_pool/v_pool [L, P, Hkv, ps, D/2] — kv heads over "model"
            (page identity is host-global; every shard holds the full
            page set for its head slice), and their static per-channel
            k_scale/k_zero/v_scale/v_zero [Hkv, 1, D] sharded to match
    Batch shards over (pod, data) when divisible; with ``seq_parallel``
    (batch=1 long-context) the cache time axis shards over data instead.

    ``time_shard_model`` (§Perf iteration 1): when the KV-head count does
    not divide the model axis, shard the cache **time** axis over "model"
    instead of replicating — flash-decode over a T-sharded cache is a
    per-shard partial softmax plus an O(B·H·D) combine, and per-device
    cache bytes drop by the model-axis size (the difference between a
    72B 32k-ctx decode cache fitting v5e HBM or not).
    """
    bspec = batch_spec(mesh)
    baxes = bspec[0]

    def t_axis(dim, h_ax):
        axes = []
        if seq_parallel and dim % mesh.shape["data"] == 0:
            axes.append("data")
        if (time_shard_model and h_ax is None
                and "model" in mesh.axis_names
                and dim % (mesh.shape["model"]
                           * (mesh.shape["data"] if axes else 1)) == 0):
            axes.append("model")
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else tuple(axes)

    def leaf_spec(path, leaf):
        name = path[-1] if path else ""
        shape = leaf.shape

        def bdim(i=1):
            if shape[i] % _axes_size(mesh, baxes) == 0 and not seq_parallel:
                return baxes
            return None

        if name in ("k_pool", "v_pool"):
            # paged serving pools [L, P, Hkv, ps, D/2]: ONLY the kv-head
            # dim shards — pages are a host-global namespace (the block
            # tables and work-queue descriptors index physical pages
            # identically on every shard)
            h_ax = _dim_axis(shape[2], mesh, "model")
            return P(None, None, h_ax, None, None)
        if name in ("k_packed", "v_packed"):
            # [L, B, Hkv, T, D/2]
            h_ax = _dim_axis(shape[2], mesh, "model")
            return P(None, bdim(), h_ax, t_axis(shape[3], h_ax), None)
        if name in ("k_scale", "k_zero", "v_scale", "v_zero"):
            if leaf.ndim == 3:
                # paged-pool static scales [Hkv, 1, D]
                h_ax = _dim_axis(shape[0], mesh, "model")
                return P(h_ax, None, None)
            h_ax = _dim_axis(shape[2], mesh, "model")
            return P(None, bdim(), h_ax, None, None)
        if name in ("k", "v"):
            # fp cache or cross_kv: [L, B, T, Hkv, D]
            h_ax = _dim_axis(shape[3], mesh, "model")
            return P(None, bdim(), t_axis(shape[2], h_ax), h_ax, None)
        if name == "s":
            h_ax = _dim_axis(shape[2], mesh, "model")
            return P(None, bdim(), h_ax, None, None)
        if name == "ssm":
            h_ax = _dim_axis(shape[2], mesh, "model")
            return P(None, bdim(), h_ax, None, None)
        if name == "conv":
            c_ax = _dim_axis(shape[3], mesh, "model")
            return P(None, bdim(), None, c_ax)
        if name in ("shift_tm", "shift_cm"):
            return P(None, bdim(), None, None)
        if name == "length":
            return P(None, bdim())
        return P(*([None] * leaf.ndim))

    return _map_with_path(cache_tree, leaf_spec)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _map_with_path(tree, fn, path=()):
    if isinstance(tree, dict):
        return {k: _map_with_path(v, fn, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)
