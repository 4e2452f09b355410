"""Logical-axis sharding rules.

The reference has no in-tree tensor/sequence/expert parallelism (SURVEY.md
§2.4: TP/PP/SP/EP are "Absent"); sharded data parallelism is delegated to
DeepSpeed/FSDP via user code over the NCCL group Ray establishes
(reference: train/examples/deepspeed/deepspeed_torch_trainer.py). Here
sharding is declarative: arrays carry *logical* axis names
("batch", "embed", "heads", …) and a `ShardingRules` table maps each
logical name to a mesh axis (or None = replicated). XLA then inserts the
collectives — this is the GSPMD programming model, the TPU-native
equivalent of all of ZeRO-1/2/3 + Megatron TP in one mechanism.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

from ray_tpu.parallel.mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_FSDP,
                                   AXIS_PIPE, AXIS_SEQ, AXIS_TENSOR)

# A logical spec is a tuple of logical axis names (or None) per array dim.
LogicalSpec = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (str | tuple of str | None).

    The default table implements, in one place:
      - DP:    "batch"  -> ("data", "fsdp")  (batch split over both)
      - FSDP:  "embed"  -> "fsdp"            (params reduce-scattered, ZeRO-3)
      - TP:    "heads"/"mlp"/"vocab" -> "tensor" (Megatron-style column/row)
      - SP:    "seq"    -> "seq"             (context parallelism / ring)
      - EP:    "expert" -> "expert"
      - PP:    "layers" -> "pipe"            (stage-stacked scan)
    """

    rules: Dict[str, Union[str, Tuple[str, ...], None]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))

    def mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        if logical not in self.rules:
            raise KeyError(f"unknown logical axis {logical!r}")
        return self.rules[logical]

    def spec(self, logical_spec: LogicalSpec):
        """Build a jax PartitionSpec from a tuple of logical names."""
        import jax
        return jax.sharding.PartitionSpec(
            *[self.mesh_axes(name) for name in logical_spec])

    def replace(self, **updates) -> "ShardingRules":
        new = dict(self.rules)
        new.update(updates)
        return ShardingRules(rules=new)


DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "batch": (AXIS_DATA, AXIS_FSDP),
    "seq": AXIS_SEQ,
    "embed": AXIS_FSDP,
    "heads": AXIS_TENSOR,
    "kv_heads": AXIS_TENSOR,
    "head_dim": None,
    "mlp": AXIS_TENSOR,
    "vocab": AXIS_TENSOR,
    "layers": AXIS_PIPE,
    "expert": AXIS_EXPERT,
    # an expert's own d_model dimension: `embed`'s axis by default, and a
    # name of its own because a layout that lays the experts over that
    # axis (`expert` -> "fsdp": the experts by expert on the axis that
    # holds everything else in shards) must keep it whole
    "expert_embed": AXIS_FSDP,
    "norm": None,
    # Activation axes (distinct from param axes: activations keep their
    # feature dims replicated/tensor-sharded even when params are
    # fsdp-sharded — that's what makes it FSDP rather than naive TP).
    "act_embed": None,
    "act_mlp": AXIS_TENSOR,
    "act_vocab": AXIS_TENSOR,
}


def spec_entry_size(entry, mesh) -> int:
    """Product of mesh-axis sizes behind one PartitionSpec entry
    (str | tuple | None) — the shard count of that dimension."""
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def fit_spec_to_shape(spec, shape, mesh) -> Tuple:
    """Degrade PartitionSpec entries whose shard count doesn't divide
    the dimension to replicated (single source of the divisibility
    rule — used by constraints, param/optimizer shardings, and the
    attention GQA dispatch)."""
    cleaned = []
    for d, entry in enumerate(spec):
        if entry is not None and shape is not None and d < len(shape):
            size = spec_entry_size(entry, mesh)
            if size and shape[d] % size != 0:
                entry = None
        cleaned.append(entry)
    return tuple(cleaned)


def logical_sharding(logical_spec: LogicalSpec, mesh,
                     rules: Optional[ShardingRules] = None,
                     shape: Optional[Tuple[int, ...]] = None):
    """NamedSharding for one array given its logical spec.

    When `shape` is known, entries whose mesh-axis product does not
    divide the dimension degrade to replicated — e.g. 2 kv heads with
    rules mapping kv_heads -> a 4-wide tensor axis keep the kv-head dim
    replicated instead of erroring (the matching compute path then
    widens K/V to query heads; see models/transformer._make_attention).
    """
    import jax
    rules = rules or ShardingRules()
    # Drop mesh axes of size 1 from specs: XLA treats them as replicated
    # anyway, and it keeps specs valid on degenerate meshes (e.g. 1 chip).
    spec = rules.spec(logical_spec)
    cleaned = []
    for entry in spec:
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if mesh.shape.get(a, 1) > 1)
            cleaned.append(kept if kept else None)
        elif entry is not None and mesh.shape.get(entry, 1) <= 1:
            cleaned.append(None)
        else:
            cleaned.append(entry)
    cleaned = fit_spec_to_shape(cleaned, shape, mesh)
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*cleaned))


def shard_pytree(spec_tree: Any, mesh,
                 rules: Optional[ShardingRules] = None):
    """Map a pytree of logical specs to a pytree of NamedShardings.

    `spec_tree` leaves are LogicalSpec tuples (tuple of str|None per dim);
    the result has the same structure with NamedSharding leaves.
    """
    import jax
    rules = rules or ShardingRules()

    def is_spec(x):
        return isinstance(x, tuple) and all(
            isinstance(e, str) or e is None for e in x)

    return jax.tree.map(
        lambda s: logical_sharding(s, mesh, rules), spec_tree,
        is_leaf=is_spec)


def with_logical_constraint(x: Any, logical_spec: LogicalSpec,
                            mesh=None,
                            rules: Optional[ShardingRules] = None):
    """`lax.with_sharding_constraint` by logical names; no-op outside jit
    or when no mesh is available (keeps model code runnable un-sharded)."""
    import jax
    rules = rules or ShardingRules()
    rules.spec(logical_spec)  # KeyError on typo'd names: propagate
    shape = getattr(x, "shape", None)
    if mesh is None:
        try:
            env_mesh = jax.sharding.get_abstract_mesh()
        except AttributeError:
            return x
        if env_mesh is None or not env_mesh.shape:
            return x
        # Inside shard_map every mapped axis is Manual: per-shard code
        # owns its layout and GSPMD constraints are meaningless (and
        # reject manual-mesh shardings) — no-op there.
        types = getattr(env_mesh, "axis_types", None)
        if types is not None and all("Manual" in str(t) for t in types):
            return x
        sharding = logical_sharding(logical_spec, env_mesh, rules,
                                    shape=shape)
    else:
        sharding = logical_sharding(logical_spec, mesh, rules, shape=shape)
    return jax.lax.with_sharding_constraint(x, sharding)
