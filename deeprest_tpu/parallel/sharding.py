"""Sharding rules: ONE ordered regex table, every TrainState leaf path.

Every QuantileGRU parameter carries a leading expert axis (models/qrnn.py),
so EP is uniformly "axis 0 on ``expert``"; TP shards the call-path feature
dimension F where it appears (the mask output and the layer-0 GRU input
projections — the two places that grow with the endpoint vocabulary,
SURVEY.md §7.3); everything else is replicated.  The batch shards on
``data``.  The cross-expert mixing sum and the gradients' reduction are
inserted by GSPMD from these annotations; the hand-written collectives
are those of the last paragraph but one.

Under a ``data`` axis the state is whole on every chip and every gradient
is all-reduced, with one exception that lasts a dispatch of the compact
superstep (train/trainer.py): the table's rows of the two layer-0 w_ih
leaves and of their moments, which ride its scan, are split over ``data``
along the row axis (:data:`CARRIED_ROWS_RULES`).  A chip then steps its
own rows only: the bfloat16 folded weight ``bf16(mask * w_ih)`` is
all-gathered for the projection, the projection's bfloat16 weight gradient
comes back reduce-scattered, and Adam runs on ``U_pad / data`` rows a
chip; after the scan the six float32 arrays are all-gathered once (pinned
by the table below, as every state is) and put into the whole leaves, so
the state a dispatch returns is whole again.

At a narrow table those collectives are the partitioner's
(:func:`pin_folded_rows`, autodiff's transpose).  At a wide one XLA:TPU
leaves each standing alone: the first direction's gather is synchronous in
front of the dot that reads it, and the weight gradient's reduce-scatter is
ONE synchronous fusion that needs the whole product first, so the links
idle through the dots and the MXU through the transfers.  Where a hop
carries enough (:func:`ring_scatters`), :func:`project_split_rows` writes
both passes of the layer-0 projection itself, under ``shard_map``.
Forward: the gather cut along the experts (:func:`gather_pieces`), a
group's rows arriving by ``all_gather`` while the group before it is
projected (whole dots over all the rows, nothing summed across chips or
groups, so every element is the dot it was).  Backward: the dot cut into
``data`` chunks of rows whose partial sums travel round the chips by
``ppermute`` while the next chunks' dots run (the same sum of the same
four chips' partials for every element, in the ring's order of addition).

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
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeprest_tpu.parallel.mesh import data_ring

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
    split over it: the all-gather of a step.  For a caller that has asked
    :func:`carried_rows_split`."""
    return jax.lax.with_sharding_constraint(
        rows, NamedSharding(mesh, P("expert", None, None)))


# The least travelling sum, in bytes, for which the ring below replaces the
# partitioner's dot and reduce-scatter: half a chunk's rows of one
# direction's gradient, ``E x U_pad / data / 2 x 3H`` in the compute dtype.
# Read on a v5e 2x2 at E=40, H=128 in bfloat16, parent | ring in steps/s
# (PERF.md section 6, PR 47): a table of 256, 0.98 MB a hop, 170.4 | 160.1
# (sixteen dots over 32 columns each and twelve permutes whose latency
# nothing hides: +0.38 ms on a 5.87 ms step); of 1,024, 3.93 MB, 121.3 |
# 128.6; of 2,048, 7.86 MB, 86.6 | 96.2; of 4,096, 15.7 MB, 54.8 | 64.8.
# The bound lies between the first two; a table of 512 (1.97 MB) was not
# read and stays with the partitioner.
RING_MIN_HOP_BYTES = 2 * 2 ** 20


def _sent_by_a_chip(mesh: Mesh, rows: jax.Array) -> int:
    """The bytes of one direction's split ``rows [E, U_pad, 3H]`` that one
    chip holds, and sends when they are gathered."""
    return rows.size * rows.dtype.itemsize // (
        mesh.shape["expert"] * mesh.shape["data"])


def ring_scatters(mesh: Mesh, rows: jax.Array) -> bool:
    """Whether the gradient of the split ``rows [E, U_pad, 3H]`` goes round
    the ring of :func:`project_split_rows` or is left to the partitioner:
    by the bytes of one hop (:data:`RING_MIN_HOP_BYTES`), half a chunk's
    rows.  For a caller that has asked :func:`carried_rows_split`."""
    return _sent_by_a_chip(mesh, rows) // 2 >= RING_MIN_HOP_BYTES


# Into how many groups of experts the forward's gather is cut, placed by the
# chips' readings (a v5e 2x2 at E=40, H=128, bfloat16, parent | pieces in
# steps/s: PERF.md section 6, PR 49).  A table of 4,096: 64.82 | 2 unread, 4
# 66.78, 8 68.01, 10 66.02, 20 65.43: past eight pieces the chains cost more
# than the shorter first piece saves.  A table of 2,048 in 5 pieces: 96.23 |
# 101.36.  A table of 1,024 in 2: 128.54 | 127.00: with fewer than four
# pieces the weight stays whole.
GATHER_MIN_PIECES, GATHER_MAX_PIECES = 4, 8


def gather_pieces(mesh: Mesh, rows: jax.Array) -> int:
    """In how many pieces a direction's folded ``rows [E, U_pad, 3H]`` reach
    the projection of :func:`project_split_rows` each step: equal groups of
    experts, as many as divide the experts a chip holds, up to
    :data:`GATHER_MAX_PIECES`, of which a chip still sends
    :data:`RING_MIN_HOP_BYTES` a piece.  1, the weight gathered whole by the
    partitioner, where that is fewer than :data:`GATHER_MIN_PIECES` or
    :func:`ring_scatters` leaves the layer to the partitioner."""
    if not ring_scatters(mesh, rows):
        return 1
    experts = rows.shape[0] // mesh.shape["expert"]
    sent = _sent_by_a_chip(mesh, rows)
    pieces = max(g for g in range(1, GATHER_MAX_PIECES + 1)
                 if experts % g == 0 and sent // g >= RING_MIN_HOP_BYTES)
    return pieces if pieces >= GATHER_MIN_PIECES else 1


def project_split_rows(mesh: Mesh, x: jax.Array, rows: jax.Array,
                       bias: jax.Array) -> jax.Array:
    """``einsum("btf,efg->etbg")`` of the windows ``x [B, T, U_pad]`` (split
    over ``data`` along B) with the folded, cast ``rows [E, U_pad, 3H]``
    that are split over ``data`` along U_pad (:func:`carried_rows_split`),
    plus the input ``bias [E, 3H]``.

    Where the table is narrow: the rows pinned whole
    (:func:`pin_folded_rows`, the partitioner's all-gather), the einsum, and
    for the rows' gradient the partitioner's dot and reduce-scatter one
    after the other.  Where :func:`ring_scatters` says so, both passes are
    cut so that the links work while the MXU does: the forward by groups of
    experts (:func:`_gather_and_project`), the backward by chunks of rows
    round a ring (:func:`_ring_rows_gradient`)."""
    project = _ring_project if ring_scatters(mesh, rows) else _project_pinned
    return project(mesh, x, rows, bias)


def _project_pinned(mesh, x, rows, bias):
    return (jnp.einsum("btf,efg->etbg", x, pin_folded_rows(mesh, rows))
            + bias[:, None, None, :])


def _gather_and_project(mesh: Mesh, x: jax.Array, rows: jax.Array,
                        bias: jax.Array) -> jax.Array:
    """:func:`_project_pinned` with the gather cut along the axis the einsum
    neither contracts nor finds split: expert e's projection needs expert
    e's rows only.  A chip gathers the rows of one group of experts
    (:func:`gather_pieces`) and projects its windows against them, whole
    dots over all U_pad rows, while the next group's rows arrive; the
    groups' results are joined along the experts.  Nothing is summed across
    chips or groups: every element is the dot the pinned einsum makes, and
    the links carry the bytes they carried.  The bias is added to each
    group's product, where XLA fuses it into the dot as it does into the
    whole one; added to the joined array it is a pass of its own."""
    groups = gather_pieces(mesh, rows)
    if groups == 1:
        return _project_pinned(mesh, x, rows, bias)

    def by_groups(x, rows, bias):
        size = rows.shape[0] // groups
        projected = []
        for k in range(groups):
            group = slice(k * size, (k + 1) * size)
            whole = jax.lax.all_gather(rows[group], "data", axis=1,
                                       tiled=True)
            projected.append(jnp.einsum("btf,efg->etbg", x, whole)
                             + bias[group, None, None, :])
        return jnp.concatenate(projected, axis=0)

    return jax.shard_map(
        by_groups, mesh=mesh,
        in_specs=(P("data", None, None), P("expert", "data", None),
                  P("expert", None)),
        out_specs=P("expert", None, "data", None), check_vma=False)(
            x, rows, bias)


_ring_project = jax.custom_vjp(_gather_and_project, nondiff_argnums=(0,))


def _ring_project_fwd(mesh, x, rows, bias):
    return _ring_project(mesh, x, rows, bias), (x, rows, bias)


def _ring_project_bwd(mesh, residuals, dxw):
    x, rows, bias = residuals
    # nothing differentiates with respect to the windows, and this dot goes
    # with its cotangent; it is here so that whoever does gets the truth
    dx = jnp.einsum("etbg,efg->btf", dxw, pin_folded_rows(mesh, rows),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    dbias = jnp.sum(dxw, axis=(1, 2), dtype=jnp.float32).astype(bias.dtype)
    return dx, _ring_rows_gradient(mesh, x, dxw.astype(rows.dtype)), dbias


_ring_project.defvjp(_ring_project_fwd, _ring_project_bwd)


def _ring_rows_gradient(mesh: Mesh, x: jax.Array, dxw: jax.Array
                        ) -> jax.Array:
    """``einsum("btf,etbg->efg")`` summed over ``data`` and left split over
    it along f: ``[E, U_pad, 3H]`` as ``P("expert", "data", None)``, in
    ``dxw``'s dtype.

    The collective matmul of Wang et al. (ASPLOS '23) as a reduce-scatter:
    the rows are cut into ``data`` chunks; a chip makes its partial sum of
    one chunk (a dot over its own windows, accumulated in float32), sends
    it to its neighbour on :func:`~deeprest_tpu.parallel.mesh.data_ring`
    and adds its partial of the chunk that arrives to it, in float32,
    rounded once for the wire.  After ``data - 1`` hops every chunk has
    every chip's partial and lies on the chip that owns it.  Each chunk's
    rows go round in two halves, one each way, so both directions of every
    link carry.  XLA fuses a hop's addition into its dot, which therefore
    waits for its arrival: what a transfer hides behind is the dots of the
    other half and of the layer's other direction, whose ring the
    scheduler interleaves with this one (tests/test_chip_compile_dp4.py
    holds every permute to a dot between its start and its done; with the
    dots kept apart from the additions the step was 4% slower on the
    chips: PERF.md section 6, PR 47)."""
    n = mesh.shape["data"]
    ring = np.asarray(data_ring(mesh))
    up = [(int(ring[p]), int(ring[(p + 1) % n])) for p in range(n)]
    down = [(j, i) for i, j in up]
    # the chunk a chip works on at stage s, by the chip's index on `data`:
    # the one that stage n-1 leaves at its owner, s hops on of where it began
    place, stage = np.argsort(ring)[None, :], np.arange(n)[:, None]
    going_up, going_down = ring[(place - 1 - stage) % n], ring[
        (place + 1 + stage) % n]

    def ring_of_dots(x, dxw):
        chunk = x.shape[2] // n
        half = chunk // 2
        me = jax.lax.axis_index("data")

        def chunk_dot(owners, lo, dxw):
            cols = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(owners)[me] * chunk + lo, half, axis=2)
            return jnp.einsum("btf,etbg->efg", cols, dxw,
                              preferred_element_type=jnp.float32)

        sums = [chunk_dot(going_up[0], 0, dxw).astype(dxw.dtype),
                chunk_dot(going_down[0], half, dxw).astype(dxw.dtype)]
        for s in range(1, n):
            # a stage's dots wait for the sums it sends: left free, the
            # scheduler makes every dot first and the permutes stand alone
            # after them
            sums, held = jax.lax.optimization_barrier((sums, dxw))
            arrived = [jax.lax.ppermute(sums[0], "data", up),
                       jax.lax.ppermute(sums[1], "data", down)]
            mine = [chunk_dot(going_up[s], 0, held),
                    chunk_dot(going_down[s], half, held)]
            sums = [(a.astype(jnp.float32) + m).astype(dxw.dtype)
                    for a, m in zip(arrived, mine)]
        return jnp.concatenate(sums, axis=1)

    return jax.shard_map(
        ring_of_dots, mesh=mesh,
        in_specs=(P("data", None, None), P("expert", None, "data", None)),
        out_specs=P("expert", "data", None), check_vma=False)(x, dxw)


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
