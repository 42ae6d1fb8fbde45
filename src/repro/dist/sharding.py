"""Logical-axis sharding: named rule sets + the ``ShardCtx`` carried in ``Ctx``.

Models never name mesh axes directly.  Parameters declare *logical* axes
(``embed``, ``heads_flat``, ``mlp``, ...) in their ``PSpec``; activations are
annotated through :meth:`ShardCtx.constrain` with per-dimension logical
names.  A *rule set* — an ordered tuple of ``(logical_axis, mesh_axes)``
pairs — maps those names onto whatever mesh the job actually has.  The same
model code therefore runs unmodified on one device (every method degrades to
a no-op), the 16-fake-device test mesh, and the 512-chip production mesh.

Resolution semantics (applied per tensor, per dimension):

* rules may name mesh axes the current mesh lacks (e.g. ``pod`` on a
  single-pod mesh) — absent axes are silently dropped;
* a mesh axis is used at most once per tensor (first dimension wins);
* an assignment must divide the dimension evenly, else trailing mesh axes
  are peeled off until it does (falling back to unsharded).

``RULE_SETS`` registers the named sets the launcher selects between:
``default`` (TP over ``model``, batch over ``pod``+``data``, and FSDP-style
parameter sharding of the ``embed`` dimension over ``data``) and
``no_fsdp`` (same minus the parameter sharding — every non-TP parameter
dimension stays replicated).
"""
from __future__ import annotations

import dataclasses
import math
from functools import cached_property
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = tuple[tuple[str, tuple[str, ...]], ...]

DEFAULT_RULES: Rules = (
    ("batch", ("pod", "data")),
    ("embed", ("data",)),  # FSDP: shard the param embed dim over data
    ("heads_flat", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("mlp", ("model",)),
    ("vocab", ("model",)),
    ("qseq", ("model",)),
    ("seq_kv", ("model",)),
    ("experts", ()),
    ("layers", ()),
)

NO_FSDP_RULES: Rules = tuple(
    (name, () if name == "embed" else axes) for name, axes in DEFAULT_RULES
)

RULE_SETS: dict[str, Rules] = {
    "default": DEFAULT_RULES,
    "no_fsdp": NO_FSDP_RULES,
}


def rules_without_axis(rules: Rules, mesh_axis: str) -> Rules:
    """Drop one mesh axis from every rule — e.g. strip ``pod`` before
    entering a shard_map that handles the pod axis manually (inside it,
    ``pod`` is no longer a GSPMD axis and must not appear in constraints).
    """
    return tuple(
        (name, tuple(a for a in axes if a != mesh_axis)) for name, axes in rules
    )


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + rule set, with every query safe on a mesh-less context."""

    mesh: Optional[Mesh] = None
    rules: Rules = DEFAULT_RULES

    @cached_property
    def _rule_map(self) -> dict[str, tuple[str, ...]]:
        return dict(self.rules)

    def _mesh_axes(self, logical: Any) -> tuple[str, ...]:
        """Mesh axes (present in this mesh) one logical axis maps to."""
        if self.mesh is None or logical is None:
            return ()
        rule = self._rule_map.get(logical, ())
        return tuple(a for a in rule if a in self.mesh.shape)

    # -- size queries ------------------------------------------------------
    def axis_size(self, *names: str) -> int:
        """Product of the named mesh axes' sizes; 0 if none exist."""
        if self.mesh is None:
            return 0
        present = [self.mesh.shape[n] for n in names if n in self.mesh.shape]
        return math.prod(present) if present else 0

    @property
    def data_axes(self) -> tuple[str, ...]:
        return self._mesh_axes("batch")

    @property
    def model_axes(self) -> tuple[str, ...]:
        return self._mesh_axes("mlp")

    def heads_shardable(self, n: int) -> bool:
        tp = self.axis_size(*self.model_axes) if self.model_axes else 0
        return tp > 1 and n % tp == 0

    # -- spec / sharding construction --------------------------------------
    def spec(self, axes, shape) -> P:
        """PartitionSpec for per-dim logical axis names against a shape."""
        assert self.mesh is not None
        used: set[str] = set()
        parts = []
        for dim, logical in zip(shape, axes):
            cand = tuple(a for a in self._mesh_axes(logical) if a not in used)
            while cand and dim % math.prod(self.mesh.shape[a] for a in cand):
                cand = cand[:-1]  # peel until the assignment divides evenly
            used.update(cand)
            parts.append(cand if len(cand) > 1 else (cand[0] if cand else None))
        return P(*parts)

    def sharding(self, axes, shape) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def param_sharding(self, spec) -> Optional[NamedSharding]:
        """Sharding for one parameter declaration (``PSpec``-like: has
        ``.axes`` logical names and ``.shape``)."""
        if self.mesh is None:
            return None
        return self.sharding(spec.axes, spec.shape)

    def constrain(self, x: jax.Array, *axes) -> jax.Array:
        """``with_sharding_constraint`` under the rule set; identity when
        there is no mesh (or a trivial one)."""
        if self.mesh is None or math.prod(self.mesh.shape.values()) == 1:
            return x
        return jax.lax.with_sharding_constraint(
            x,
            NamedSharding(self.mesh, self.spec(axes, x.shape)),
        )


def converted_shardings(converted, dense, shard: ShardCtx, fsdp_tables: bool = False):
    """Shardings for a TableNet-converted tree (arrays or shape stand-ins).

    ``dense`` is the unconverted tree with ``.sharding`` on each leaf (e.g.
    ``abstract_params(..., sharding_fn=shard.param_sharding)``).  Leaves
    whose path and shape coincide with a dense leaf (embedding, norms,
    biases) keep its sharding.  ``tables`` leaves — weight tables
    ``(..., k, entries, p)`` and TL1 packed indices ``(..., kb, p)`` — are
    sharded on their output dim ``p`` over ``model``, like the weights they
    replace; with ``fsdp_tables`` the weight tables' chunk dim also goes
    over ``data`` (ZeRO-3 tables).  Everything else is replicated.
    """
    assert shard.mesh is not None, "converted_shardings needs a mesh"
    dense_flat = {
        jax.tree_util.keystr(p): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(dense)[0]
    }

    def place(path, leaf):
        same = dense_flat.get(jax.tree_util.keystr(path))
        if same is not None and same.shape == leaf.shape:
            return same.sharding
        # dict levels carry DictKey (.key); LUTLinear/LUTGroup children
        # carry GetAttrKey (.name)
        name = getattr(path[-1], "key", None) or getattr(path[-1], "name", None)
        axes = [None] * leaf.ndim
        if name == "tables":
            n_model = shard.axis_size("model")
            if n_model and leaf.shape[-1] % n_model == 0:
                axes[-1] = "model"
            if fsdp_tables and leaf.ndim >= 3:
                if leaf.shape[-3] % max(shard.axis_size("data"), 1) == 0:
                    axes[-3] = "data"
        return NamedSharding(shard.mesh, P(*axes))

    return jax.tree_util.tree_map_with_path(place, converted)
