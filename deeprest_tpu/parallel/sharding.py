"""Sharding rules: ONE ordered regex table, every TrainState leaf path.

Every QuantileGRU parameter carries a leading expert axis (models/qrnn.py),
so EP is uniformly "axis 0 on ``expert``"; TP shards the call-path feature
dimension F where it appears (the mask output and the layer-0 GRU input
projections — the two places that grow with the endpoint vocabulary,
SURVEY.md §7.3); everything else is replicated.  The batch shards on
``data``.  No manual collectives anywhere: the cross-expert mixing sum and
the gradients' reduction are inserted by GSPMD from these annotations.

Under a ``data`` axis the state is whole on every chip and every gradient
is all-reduced, with one exception that lasts a dispatch of the compact
superstep (train/trainer.py): the table's rows of the two layer-0 w_ih
leaves and of their moments, which ride its scan, are split over ``data``
along the row axis (:data:`CARRIED_ROWS_RULES`).  A chip then steps its
own rows only: the bfloat16 folded weight ``bf16(mask * w_ih)`` is
all-gathered for the projection (:func:`pin_folded_rows`), the
projection's bfloat16 weight gradient comes back reduce-scattered, and
Adam runs on ``U_pad / data`` rows a chip; after the scan the six float32
arrays are all-gathered once (pinned by the table below, as every state
is) and put into the whole leaves, so the state a dispatch returns is
whole again.
Every collective is still the partitioner's.

The table below (:data:`PARTITION_RULES`) is the SINGLE owner of those
decisions: an ordered ``(regex, PartitionSpec)`` list matched against
"/"-joined pytree leaf paths (the SNIPPETS.md [2]/[3]
``match_partition_rules`` shape).  Trainer ``pin_state``, checkpoint
restore, and the serving plane all resolve shardings here — there are no
hand-pinned per-leaf spec dicts anywhere else (graftlint JX005 enforces
that NamedSharding literals stay out of other modules).  Optimizer state
needs no rules of its own: Adam's ``mu``/``nu`` mirror the params dict
keyed by the same names, so the param rules match their paths too.

Strict mode errors on any leaf no rule matches: a new TrainState leaf must
be *placed deliberately*, not silently replicated (the silent-collapse
class behind the PR 2 double-executable incident).
"""

from __future__ import annotations

import re
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Ordered: first match wins.  Patterns run (re.search) against "/"-joined
# leaf paths such as ``params/mask_w2`` or ``opt_state/0/mu/gru_fwd_w_ih``,
# so ``(^|/)name$`` anchors on the leaf name wherever it sits in the tree.
PARTITION_RULES: tuple[tuple[str, P], ...] = (
    # -- soft feature mask MLP ------------------------------------------
    (r"(^|/)mask_w1$", P("expert", None)),             # [E, H]
    (r"(^|/)mask_b1$", P("expert", None)),             # [E, H]
    (r"(^|/)mask_w2$", P("expert", None, "model")),    # [E, H, F]  TP out
    (r"(^|/)mask_b2$", P("expert", "model")),          # [E, F]     TP out
    # -- GRU stacks: deep-layer (_lN) w_ih consumes the 2H hidden output
    # of the previous layer, not the TP-sharded feature axis — those
    # replicate like w_hh.  Order matters: the _lN rule must win before
    # the layer-0 w_ih rule below.
    (r"(^|/)gru_(fwd|bwd)_l\d+_w_ih$", P("expert", None, None)),
    (r"(^|/)gru_(fwd|bwd)_w_ih$", P("expert", "model", None)),  # [E, F, 3H]
    (r"(^|/)gru_(fwd|bwd)(_l\d+)?_w_hh$", P("expert", None, None)),
    (r"(^|/)gru_(fwd|bwd)(_l\d+)?_b_(ih|hh)$", P("expert", None)),
    # -- quantile heads --------------------------------------------------
    (r"(^|/)head_w$", P("expert", None, None)),        # [E, 4H, Q]
    (r"(^|/)head_b$", P("expert", None)),              # [E, Q]
    # -- TrainState bookkeeping: replicated everywhere -------------------
    #    step (scalar), the PRNG key, Adam's update counter.
    (r"(^|/)(step|rng|count)$", P()),
)


# The table's rows of a layer-0 w_ih leaf (and of its Adam mirrors) while
# they ride the compact superstep's scan in the leaf's place: ``[E, U_pad,
# 3H]``, split over ``data`` along the rows.  Matched BEFORE the table
# above, for a state whose w_ih leaves are such rows
# (``state_sharding(..., carried_rows=True)``); a compact table lives on a
# mesh whose ``model`` axis is 1 (``Trainer._stage_sparse``).
CARRIED_ROWS_RULES: tuple[tuple[str, P], ...] = (
    (r"(^|/)gru_(fwd|bwd)_w_ih$", P("expert", "data", None)),
)


def carried_rows_split(mesh: Mesh | None, width: int) -> int:
    """Over how many chips the ``width`` carried rows of a w_ih leaf are
    split: the mesh's ``data`` axis where it divides them (a padded live
    set is a power of two of at least 128), else 1 (the rows whole on every
    chip, and the compiled program the one it was without this rule)."""
    data = 1 if mesh is None else mesh.shape["data"]
    return data if data > 1 and width % data == 0 else 1


def pin_folded_rows(mesh: Mesh, rows: jax.Array) -> jax.Array:
    """The folded, cast ``[E, U_pad, 3H]`` weight that the projection
    contracts, whole on every chip of ``data`` where the carried rows are
    split over it: the all-gather of a step (and, transposed, the
    reduce-scatter of the weight's gradient).  For a caller that has asked
    :func:`carried_rows_split`."""
    return jax.lax.with_sharding_constraint(
        rows, NamedSharding(mesh, P("expert", None, None)))


def leaf_path_name(path: Sequence[Any]) -> str:
    """``tree_flatten_with_path`` key path → the "/"-joined rule name
    (``params/mask_w2``, ``opt_state/0/mu/head_w``, ``rng``)."""
    parts = []
    for entry in path:
        for attr in ("name", "key", "idx"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:
            parts.append(str(entry))
    return "/".join(parts)


def _leaf_ndim(leaf: Any) -> int:
    return getattr(leaf, "ndim", np.ndim(leaf))


def _leaf_size(leaf: Any) -> int:
    return int(getattr(leaf, "size", np.size(leaf)))


def match_partition_rules(tree: Any,
                          rules: Sequence[tuple[str, P]] = PARTITION_RULES,
                          strict: bool = True) -> Any:
    """A PartitionSpec pytree mirroring ``tree``, resolved from ``rules``.

    Scalar (and single-element) leaves replicate without consulting the
    table — there is nothing to shard.  Otherwise the FIRST rule whose
    regex ``search``-matches the leaf's "/"-joined path wins.  ``strict``
    raises ``KeyError`` on an unmatched leaf instead of silently
    replicating it: every new TrainState leaf must be placed on the mesh
    deliberately.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def resolve(path, leaf):
        if _leaf_ndim(leaf) == 0 or _leaf_size(leaf) <= 1:
            return P()
        name = leaf_path_name(path)
        for pat, spec in compiled:
            if pat.search(name):
                return spec
        if strict:
            raise KeyError(
                f"no partition rule matches leaf {name!r} "
                f"(shape {tuple(np.shape(leaf))}); add a rule to "
                "parallel/sharding.PARTITION_RULES — strict mode refuses "
                "to replicate unknown state silently")
        return P()

    return jax.tree_util.tree_map_with_path(resolve, tree)


def param_specs(params: Mapping[str, Any]) -> dict[str, P]:
    """PartitionSpec dict mirroring a QuantileGRU param dict (the params
    slice of the rule table; raises KeyError on an unmatched name)."""
    return match_partition_rules(dict(params), strict=True)


def state_specs(state: Any, carried_rows: bool = False) -> Any:
    """PartitionSpec pytree for a full TrainState (params, optimizer
    mirrors, step/rng bookkeeping), strictly rule-resolved.
    ``carried_rows``: the state's layer-0 w_ih leaves are the table's rows
    that ride the compact superstep's scan (:data:`CARRIED_ROWS_RULES`)."""
    rules = (CARRIED_ROWS_RULES if carried_rows else ()) + PARTITION_RULES
    return match_partition_rules(state, rules, strict=True)


def state_sharding(mesh: Mesh, state: Any, carried_rows: bool = False) -> Any:
    """NamedSharding pytree for a full TrainState on ``mesh`` — what the
    trainer's ``pin_state`` constrains every step output to, and what
    checkpoint restore assembles shards into."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        state_specs(state, carried_rows),
                        is_leaf=lambda x: isinstance(x, P))


def param_sharding(mesh: Mesh, params: Mapping[str, Any]) -> dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, spec)
            for k, spec in param_specs(params).items()}


def batch_sharding(mesh: Mesh, ndim: int = 3) -> NamedSharding:
    """Batch arrays shard on ``data`` along axis 0; rest replicated."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def shard_params(mesh: Mesh, params: Mapping[str, Any]) -> dict[str, jax.Array]:
    """Place (replicated-identical) host params onto the mesh.

    On one host a sharded device_put; on a pod each process materializes
    only its addressable shards (``make_array_from_callback``) — init
    with the same PRNGKey makes every host's source params identical.
    """
    shardings = param_sharding(mesh, params)

    def put(v, shd):
        if jax.process_count() == 1:
            return jax.device_put(v, shd)
        host = np.asarray(v)
        return jax.make_array_from_callback(host.shape, shd,
                                            lambda idx: host[idx])

    return {k: put(v, shardings[k]) for k, v in params.items()}


def shard_batch(mesh: Mesh, *arrays: jax.Array | Any) -> tuple[jax.Array, ...]:
    out = tuple(
        jax.device_put(a, batch_sharding(mesh, getattr(a, "ndim", 1))) for a in arrays
    )
    return out if len(out) > 1 else out[0]
