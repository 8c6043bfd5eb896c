"""Sharding annotations on program variables.

The TPU-native successor of the reference's per-device graph surgery: instead
of replicating ops per device and inserting AllReduceOpHandles
(ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:464), variables carry
sharding metadata in their VarDesc; the compiling executor turns it into
jax.NamedSharding on the jitted step, and GSPMD inserts the collectives.

Two annotation tiers (axis_rules.py holds the rule machinery):

* **logical axes** (``set_logical_axes(w, ("embed", "mlp"))``) — the
  T5X-style declarative tier: one process-global rule table maps logical
  names to mesh axes, so the SAME program shards correctly on any mesh
  shape and re-shards when the table changes;
* **explicit specs** (``shard_tensor(w, (None, "mp"))``) — per-tensor
  overrides naming mesh axes (or logical names, translated through the
  table); these always win over rule resolution.

Megatron-style TP = column spec on the first FFN/attention weight, row spec on
the second; grad allreduce for DP = psum emitted by XLA because params are
replicated over 'dp' while batch is sharded.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

SHARDING_ATTR = "sharding_spec"
LOGICAL_AXES_ATTR = "logical_axes"

# conventional mesh-axis vocabulary of this repo (parallel/mesh.py,
# ops/collective_ops.py ring_id map): specs naming these are portable
# across mesh shapes — an absent axis means "replicated here", not a typo
KNOWN_MESH_AXES = frozenset(("dp", "mp", "pp", "sp", "ep", "expert"))


class PartitionSpec(tuple):
    """Thin serialisable stand-in for jax.sharding.PartitionSpec (entries:
    axis name, tuple of names, or None)."""

    def __new__(cls, *specs):
        return super().__new__(cls, specs)

    def to_jax(self):
        from jax.sharding import PartitionSpec as P

        return P(*self)


class ShardingAxisError(ValueError):
    """A partition spec names an axis that is neither a mesh axis of the
    active mesh, a known mesh-axis name, nor a logical axis of the active
    rule table — raised at annotation/compile time instead of surfacing
    as an opaque XLA error inside pjit."""


def _var_desc(var):
    return var.desc if hasattr(var, "desc") else var


def _known_axis_names(mesh=None) -> set:
    from . import axis_rules

    known = set(KNOWN_MESH_AXES)
    if mesh is not None:
        known.update(mesh.shape)
    rules = axis_rules.get_rules()
    if rules is not None:
        known.update(rules.logical_names())
        known.update(rules.mesh_targets())
    return known


def _check_spec_axes(spec, mesh, where: str):
    """Reject axis names that can't mean anything on any mesh this
    process knows about (typo guard — satellite of the rule-table PR)."""
    known = _known_axis_names(mesh)
    for entry in spec:
        names = entry if isinstance(entry, (list, tuple)) else (entry,)
        for a in names:
            if a is None:
                continue
            if not isinstance(a, str) or a not in known:
                active = sorted(mesh.shape) if mesh is not None else None
                raise ShardingAxisError(
                    f"{where}: axis {a!r} in spec {tuple(spec)!r} is not a "
                    f"mesh axis (active mesh: {active}), a known axis name "
                    f"{sorted(KNOWN_MESH_AXES)}, or a logical axis of the "
                    f"active rule table — likely a typo; it would "
                    f"otherwise fail late inside pjit")


def shard_tensor(var, spec: Sequence[Optional[Union[str, tuple]]]):
    """Annotate a program variable with a partition spec, e.g.
    shard_tensor(w, [None, "mp"]) — column-parallel weight. Entries may
    name mesh axes or logical axes (resolved through the rule table).
    Unknown axis names raise ShardingAxisError at annotation time."""
    from .mesh import get_mesh

    spec = tuple(spec)
    _check_spec_axes(spec, get_mesh(), "shard_tensor")
    _var_desc(var).attrs[SHARDING_ATTR] = spec
    return var


shard_parameter = shard_tensor


def get_sharding_spec(var):
    return _var_desc(var).attrs.get(SHARDING_ATTR)


def set_logical_axes(var, axes: Sequence[Optional[str]]):
    """Attach logical axis names (one per dim, None = never sharded) to a
    var; the active rule table resolves them to mesh axes at compile
    time (axis_rules.py). Explicit shard_tensor specs override."""
    _var_desc(var).attrs[LOGICAL_AXES_ATTR] = tuple(axes)
    return var


def get_logical_axes(var):
    return _var_desc(var).attrs.get(LOGICAL_AXES_ATTR)


def _translate_axis(a, mesh, rules, on_missing: str):
    """One spec entry → mesh axis | None. Mesh axes pass through; logical
    names map through the rule table; known-but-absent names drop to None
    (one program runs on any mesh shape) unless on_missing='error'."""
    if a is None:
        return None
    if mesh is not None and a in mesh.shape:
        return a
    if rules is not None and a in rules.logical_names():
        mapped = rules.first_mesh_axis(a, mesh)
        if mapped is not None:
            return mapped
        if on_missing == "error":
            raise ShardingAxisError(
                f"axis {a!r}: no rule of the active table maps it to an "
                f"axis of the active mesh "
                f"({sorted(mesh.shape) if mesh is not None else None})")
        return None
    if isinstance(a, str) and (a in KNOWN_MESH_AXES or
                               (rules is not None and
                                a in rules.mesh_targets())):
        if on_missing == "error":
            raise ShardingAxisError(
                f"axis {a!r} is not in the active mesh "
                f"({sorted(mesh.shape) if mesh is not None else None})")
        return None
    raise ShardingAxisError(
        f"unknown axis {a!r} — not a mesh axis, known axis name, or "
        f"logical axis of the active rule table")


def clean_spec(spec, mesh, on_missing: str = "drop"):
    """Normalise a raw spec tuple against `mesh`: mesh axes kept, logical
    names translated through the active rule table, known-but-absent axes
    dropped (so one program runs on any mesh shape; on_missing='error'
    raises ShardingAxisError instead — the early-failure mode for specs
    that MUST bind, e.g. CompiledProgram feed shardings). Unknown axis
    names always raise ShardingAxisError."""
    if spec is None:
        return None
    from . import axis_rules

    rules = axis_rules.get_rules()
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, (list, tuple)):
            kept = tuple(a for a in
                         (_translate_axis(x, mesh, rules, on_missing)
                          for x in s) if a is not None)
            clean.append(kept if kept else None)
        else:
            clean.append(_translate_axis(s, mesh, rules, on_missing))
    return tuple(clean)


def spec_for_var(var, mesh, default=None, use_rules=True):
    """THE sharding resolution everybody uses (compiled shard_map wrap,
    non-SPMD jit shardings, the SPMD interpreting oracle): explicit
    shard_tensor spec > logical axes resolved through the active rule
    table (divisibility-gated) > `default`. Returns a cleaned concrete
    spec tuple, or None for replicated.

    use_rules=False skips the rule-table tier: inside a shard_map SPMD
    region ops compute on LOCAL shards, so auto-sharding a weight there
    would silently change the math unless the program carries matching
    in-program collectives — shard_map programs therefore take explicit
    specs only (the ZeRO transpile emits them), while the GSPMD path
    (where XLA inserts the collectives) resolves through the table."""
    spec = get_sharding_spec(var)
    if spec is None and use_rules:
        axes = get_logical_axes(var)
        if axes:
            from . import axis_rules

            rules = axis_rules.get_rules()
            if rules is not None:
                shape = getattr(var, "shape", None)
                resolved = rules.resolve(axes, mesh, shape=shape)
                if resolved is not None and any(a is not None
                                                for a in resolved):
                    return resolved
    if spec is None:
        spec = default
    if spec is None:
        return None
    return clean_spec(spec, mesh)


def get_shard_map():
    """shard_map entry point + the kwarg that turns its replication
    check off: (jax.shard_map, {"check_vma": False})."""
    import jax

    return jax.shard_map, {"check_vma": False}


def named_sharding_for(var, mesh, default_spec=None):
    """NamedSharding for a var under `mesh` (None → replicated/default),
    derived through spec_for_var: explicit spec > rule table > default."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    spec = spec_for_var(var, mesh, default=default_spec)
    if spec is None:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(*spec))
