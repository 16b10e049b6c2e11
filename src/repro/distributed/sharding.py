"""Logical-axis sharding: named axes → mesh axes with safe fallbacks.

Model code annotates parameters and activations with *logical* axis names
("vocab", "ff", "heads", "experts", "batch", "kv_seq", ...).  A rule table
maps logical names to mesh axes; :func:`resolve_pspec` applies the table
with a divisibility check — a dimension that does not divide evenly over
its assigned mesh axes falls back to replication rather than relying on
GSPMD padding (padding waste is opt-in via ``allow_uneven``).

The active (mesh, rules) pair is held in a context (:func:`use_rules`);
model code calls :func:`constrain` freely — it is a no-op outside the
context, so single-device smoke tests run the same code path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "axis_ctx",
    "use_rules",
    "auto_axes",
    "constrain",
    "resolve_pspec",
    "param_shardings",
    "shard_map",
]

MeshAxes = Union[str, Tuple[str, ...], None]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical-name → mesh axis (or tuple of axes) table."""

    table: Dict[str, MeshAxes]
    allow_uneven: Tuple[str, ...] = ()   # logical names where GSPMD padding is OK

    def lookup(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.table.get(logical)

    def override(self, **kw: MeshAxes) -> "AxisRules":
        t = dict(self.table)
        t.update(kw)
        return AxisRules(t, self.allow_uneven)


# The production meshes are (data=16, model=16) and (pod=2, data=16, model=16);
# "batch" spans pod×data so the same rules serve both (missing axes are
# dropped at resolve time).
DEFAULT_RULES = AxisRules(
    table={
        "batch": ("pod", "data"),
        "seq": None,
        "kv_seq": None,          # set to ("data",) for long-context decode
        "d_model": None,
        "ff": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "head_dim": None,
        "vocab": ("model",),
        "experts": ("model",),
        "expert_ff": None,
        "fsdp": ("data",),       # parameter/optimizer-state sharding (ZeRO)
        "layers": None,
        "state": None,
        "window": ("data",),     # SSSJ ring-buffer shards (engine/sharded.py)
    },
    # NOTE: no allow_uneven entries — jit *input* shardings must divide
    # exactly, so an indivisible dim (e.g. 56 heads over model=16, or 8 kv
    # heads over 16) falls back to replication.  The per-arch consequences
    # are recorded in EXPERIMENTS.md §Dry-run.
    allow_uneven=(),
)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes by default; code written
    against GSPMD propagation (``shard_map`` plus ordinary jnp on its
    gathered outputs) keeps its own view of the caller's mesh through this,
    so an explicit-axis mesh and an auto-axis mesh behave the same."""
    return Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(AxisType.Auto,) * len(mesh.axis_names),
    )


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[AxisRules] = None


axis_ctx = _Ctx()


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: AxisRules = DEFAULT_RULES):
    prev = (axis_ctx.mesh, axis_ctx.rules)
    axis_ctx.mesh, axis_ctx.rules = mesh, rules
    try:
        yield
    finally:
        axis_ctx.mesh, axis_ctx.rules = prev


def _mesh_axes_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return size


def _present_axes(mesh: Mesh, axes: MeshAxes) -> MeshAxes:
    """Drop mesh axes that do not exist in this mesh (e.g. 'pod' on 1 pod)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    kept = tuple(a for a in axes if a in mesh.axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def resolve_pspec(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    rules: Optional[AxisRules] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Map a tuple of logical names to a PartitionSpec for ``shape``.

    Dimensions whose size does not divide the assigned mesh-axes product
    are replicated unless the logical name is in ``rules.allow_uneven``.
    """
    rules = rules or axis_ctx.rules
    mesh = mesh or axis_ctx.mesh
    if rules is None or mesh is None:
        return P()
    assert len(shape) == len(logical), (shape, logical)
    out = []
    used: set = set()   # a mesh axis may appear at most once per spec
    for dim, name in zip(shape, logical):
        axes = _present_axes(mesh, rules.lookup(name))
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        # cross-dim conflict resolution: earlier dims win, later dims drop
        # already-claimed mesh axes (e.g. kv_seq→model before kv_heads→model)
        axes = tuple(a for a in axes if a not in used)
        if not axes:
            out.append(None)
            continue
        size = _mesh_axes_size(mesh, axes)
        if size <= 1:
            out.append(None)
        elif dim % size == 0 or (name in rules.allow_uneven):
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return P(*out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical names; no-op without a context."""
    rules, mesh = axis_ctx.rules, axis_ctx.mesh
    if rules is None or mesh is None:
        return x
    spec = resolve_pspec(x.shape, logical, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(spec_tree, shape_tree, mesh: Mesh, rules: AxisRules):
    """Build a NamedSharding pytree from a logical-spec tree.

    ``spec_tree`` mirrors the param tree, with a tuple of logical names
    (or None) per leaf; ``shape_tree`` supplies leaf shapes
    (jax.ShapeDtypeStruct or arrays).
    """

    def one(spec, leaf):
        shape = leaf.shape
        if spec is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, resolve_pspec(shape, spec, rules, mesh))

    return jax.tree.map(
        one, spec_tree, shape_tree, is_leaf=lambda s: s is None or isinstance(s, tuple)
    )
