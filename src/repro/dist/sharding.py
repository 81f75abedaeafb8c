"""Logical-axis sharding rules (intra-iteration partitioning, DESIGN.md §4.1).

Model code never names mesh axes.  Parameters and activations carry tuples
of *logical* axis names (``(FSDP, TENSOR)``, ``(BATCH, None, None)``, …);
a rule table built per mesh maps each logical name to zero or more mesh
axes.  ``spec_for`` resolves a concrete shape against the table with two
safety properties that make every (arch × shape × mesh) cell lowerable:

* **divisibility fallback** — a dimension whose size does not divide the
  mapped mesh-axis product is replicated instead of sharded, so odd vocab
  sizes, head counts, or tiny test shapes never fail GSPMD;
* **no mesh axis used twice** — within one tensor, the first dimension to
  claim a mesh axis wins and later dimensions replicate, so rule tables
  may alias (e.g. ``TENSOR`` and ``VOCAB`` both on ``"model"``) without
  producing invalid specs.

``constrain`` is the activation-side entry point: a no-op outside an
``activation_sharding`` context (pure-CPU tests, single-device examples)
and a ``with_sharding_constraint`` inside one.  The active context is
thread-local and read at trace time.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# --------------------------------------------------------------------------
# logical axis names
# --------------------------------------------------------------------------
BATCH = "batch"          # batch dim of activations (data-parallel axes)
BATCH_DP = "batch_dp"    # batch dim restricted to pod/data axes ONLY, even
#                          under fsdp — leaves "model" free for VOCAB in the
#                          unembed/logits path
FSDP = "fsdp"            # weight dim sharded over the data-parallel axes
TENSOR = "tensor"        # weight/activation dim sharded over "model" (TP)
HEADS = "heads"          # query-head dim (TP)
KV_HEADS = "kv_heads"    # KV-head dim (TP; GQA groups)
KV_SEQ = "kv_seq"        # KV-cache sequence dim (flash-decoding split)
VOCAB = "vocab"          # vocabulary dim (embed table / logits)
EXPERT = "expert"        # MoE expert dim
CAPACITY = "capacity"    # MoE dispatch-buffer capacity dim (data axes)

LOGICAL_AXES = (BATCH, BATCH_DP, FSDP, TENSOR, HEADS, KV_HEADS, KV_SEQ,
                VOCAB, EXPERT, CAPACITY)

STRATEGIES = ("2d", "fsdp", "serve")


# --------------------------------------------------------------------------
# rule tables
# --------------------------------------------------------------------------
def make_rules(mesh, *, strategy: str = "2d") -> dict[str, tuple[str, ...]]:
    """Logical-axis → mesh-axes table for ``mesh`` under ``strategy``.

    * ``"2d"``   — FSDP × TP: weights shard (pod, data) × model, batch
                   shards the data axes.  The production default.
    * ``"fsdp"`` — pure data parallel over the whole mesh: batch and the
                   FSDP weight dim cover every mesh axis, TP axes collapse.
    * ``"serve"``— TP only: weights replicate across data (read-only
                   serving replicas), batch shards the data axes.

    Only axes present in ``mesh.axis_names`` are emitted, so the same code
    drives a ``(pod, data, model)`` production mesh and a ``(data, model)``
    host mesh.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; expected one of "
                         f"{STRATEGIES}")
    names = tuple(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = ("model",) if "model" in names else ()
    everything = dp + tp

    if strategy == "fsdp":
        rules = {
            BATCH: everything, BATCH_DP: dp, FSDP: everything,
            TENSOR: (), HEADS: (), KV_HEADS: (), KV_SEQ: (),
            VOCAB: tp, EXPERT: tp, CAPACITY: dp,
        }
    elif strategy == "serve":
        rules = {
            BATCH: dp, BATCH_DP: dp, FSDP: (),
            TENSOR: tp, HEADS: tp, KV_HEADS: tp, KV_SEQ: tp,
            VOCAB: tp, EXPERT: tp, CAPACITY: dp,
        }
    else:  # "2d"
        rules = {
            BATCH: dp, BATCH_DP: dp, FSDP: dp,
            TENSOR: tp, HEADS: tp, KV_HEADS: tp, KV_SEQ: tp,
            VOCAB: tp, EXPERT: tp, CAPACITY: dp,
        }
    return rules


def _mesh_axes_for(rules: Mapping[str, Sequence[str]], name) -> tuple[str, ...]:
    """Mesh axes for one logical name; unknown names (e.g. "layers") and an
    explicit mesh-axis tuple both pass through."""
    if name is None:
        return ()
    if isinstance(name, tuple):  # pre-resolved mesh axes
        return name
    got = rules.get(name, ())
    if got is None:
        return ()
    return (got,) if isinstance(got, str) else tuple(got)


# --------------------------------------------------------------------------
# mesh construction
# --------------------------------------------------------------------------
def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """The repo's one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes, which
    ``with_sharding_constraint`` (``constrain``) rejects.  ``devices``
    pins the exact device order (a survivor mesh); without it JAX lays
    the mesh over all devices.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=types)


def divisor_mesh(num_items: int, axis: str):
    """1-D mesh over ``axis`` sized to the largest divisor of
    ``num_items`` that fits the available devices.

    The shared auto-mesh policy of the graph middleware (``plug``'s
    ``MeshUpperSystem`` and ``ShardedDaemon``): ``num_items`` stacked
    slots always divide the mesh axis, so the same code runs 4 shards on
    1 CPU device (local fold only) and 4 shards on 4 devices (pure
    collective).
    """
    ndev = len(jax.devices())
    m = 1
    for d in range(min(num_items, ndev), 0, -1):
        if num_items % d == 0:
            m = d
            break
    return make_mesh((m,), (axis,))


# --------------------------------------------------------------------------
# spec construction
# --------------------------------------------------------------------------
def spec_for(shape: Sequence[int], axes, mesh, rules) -> P:
    """PartitionSpec for ``shape`` whose dims carry logical names ``axes``.

    Per-dimension: the rule table maps the logical name to mesh axes; axes
    already claimed by an earlier dimension are dropped, and if the
    remaining mesh-axis product does not divide the dimension size the
    dimension replicates.  Trailing replicated dims are trimmed so
    ``spec_for((4n, 8), (TENSOR, None)) == P("model")``.
    """
    if axes is None:
        axes = (None,) * len(shape)
    axes = tuple(axes)
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} do not match shape {tuple(shape)}")
    used: set[str] = set()
    parts: list[Any] = []
    for dim, name in zip(shape, axes):
        mesh_axes = tuple(a for a in _mesh_axes_for(rules, name)
                          if a not in used)
        prod = 1
        for a in mesh_axes:
            prod *= mesh.shape[a]
        if mesh_axes and dim % prod == 0:
            used.update(mesh_axes)
            parts.append(mesh_axes[0] if len(mesh_axes) == 1 else mesh_axes)
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def sharding_for(shape: Sequence[int], axes, mesh, rules) -> NamedSharding:
    """NamedSharding for one array (see ``spec_for``)."""
    return NamedSharding(mesh, spec_for(shape, axes, mesh, rules))


def tree_shardings(tree, axes, mesh, rules):
    """Maps ``sharding_for`` over a pytree and its parallel axes pytree.

    ``axes`` leaves are tuples of logical names sitting at the leaf
    positions of ``tree`` (tree.map stops descending at ``tree``'s leaves,
    so the tuples are consumed whole).
    """
    return jax.tree.map(
        lambda leaf, ax: sharding_for(leaf.shape, ax, mesh, rules),
        tree, axes)


# --------------------------------------------------------------------------
# activation-sharding context
# --------------------------------------------------------------------------
_local = threading.local()


def active_context():
    """The innermost ``(mesh, rules)`` pushed by ``activation_sharding``,
    or None outside any context."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def activation_sharding(mesh, rules):
    """Makes ``constrain`` live: inside this context (at trace time) every
    ``constrain(x, axes)`` lowers to a ``with_sharding_constraint``."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    stack.append((mesh, rules))
    try:
        yield
    finally:
        stack.pop()


def constrain(x, axes):
    """Constrains activation ``x`` to its logical axes — identity (the very
    same object) when no ``activation_sharding`` context is active."""
    ctx = active_context()
    if ctx is None:
        return x
    mesh, rules = ctx
    return jax.lax.with_sharding_constraint(
        x, sharding_for(x.shape, axes, mesh, rules))
