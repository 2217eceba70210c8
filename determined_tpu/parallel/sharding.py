"""Logical-axis sharding rules (GSPMD layout policy).

Models annotate arrays with *logical* axis names ("batch", "embed", "heads",
...).  A `LogicalRules` table maps logical names to mesh axes; changing the
parallelism strategy (DP vs FSDP vs TP vs combinations) is purely a rules
swap — model code never mentions mesh axes.  This is the standard t5x/maxtext
style layout system, re-derived for this framework.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple, Union

from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]

# Canonical rules: batch over (data, fsdp); params sharded over fsdp on their
# largest dim and over tensor on the "parallel" dim (Megatron layout); sequence
# over context for ring attention.
DEFAULT_RULES: Tuple[Tuple[str, MeshAxes], ...] = (
    ("batch", ("data", "fsdp")),
    ("seq", "context"),
    ("layers", "pipeline"),  # stacked-layer dim → pipeline stages
    ("embed", "fsdp"),
    ("heads", "tensor"),
    ("kv", None),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("unmodeled", None),
)


class LogicalRules:
    def __init__(self, rules: Sequence[Tuple[str, MeshAxes]] = DEFAULT_RULES):
        self._table: dict = {}
        for name, axes in rules:
            self._table[name] = axes

    def mesh_axes(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        if logical not in self._table:
            raise KeyError(f"no sharding rule for logical axis {logical!r}")
        return self._table[logical]

    def spec(self, logical_axes: Sequence[Optional[str]]) -> PartitionSpec:
        """PartitionSpec for an array whose dims carry these logical names.

        A mesh axis may be consumed at most once per array; later dims that
        would reuse an already-consumed mesh axis fall back to replication.
        """
        used: set = set()
        out = []
        for logical in logical_axes:
            axes = self.mesh_axes(logical)
            if axes is None:
                out.append(None)
                continue
            if isinstance(axes, str):
                axes = (axes,)
            free = tuple(a for a in axes if a not in used)
            used.update(free)
            if not free:
                out.append(None)
            elif len(free) == 1:
                out.append(free[0])
            else:
                out.append(free)
        return PartitionSpec(*out)

    def override(self, **kwargs: MeshAxes) -> "LogicalRules":
        table = dict(self._table)
        table.update(kwargs)
        return LogicalRules(tuple(table.items()))


def logical_to_mesh_spec(
    logical_axes: Sequence[Optional[str]], rules: Optional[LogicalRules] = None
) -> PartitionSpec:
    return (rules or LogicalRules()).spec(logical_axes)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def shard_logical(x, logical_axes: Sequence[Optional[str]], rules: Optional[LogicalRules] = None):
    """`with_sharding_constraint` by logical axis names (no-op outside jit/mesh)."""
    import jax

    spec = logical_to_mesh_spec(logical_axes, rules)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # No mesh context (e.g. pure eager single-device use) — constraint is
        # advisory, skip it.
        return x
