"""The jit-compiled training/eval loop.

Replaces the reference driver (reference: resource-estimation/
estimate.py:60-123) with a TPU-native loop: one compiled train step (donated
state, fused forward/backward, optax Adam), static batch shapes via
zero-weight padding of the ragged trailing batch, batches sharded over the
mesh's ``data`` axis and parameters over ``expert``/``model`` — gradient
and mixing collectives all GSPMD-inserted.

Evaluation reproduces the reference's exact semantics before improving on
them: every ``eval_stride``-th test window, capped at ``eval_max_cycles``,
de-normalized, median-quantile point estimates floored at 1e-6, absolute
errors pooled across windows (reference: estimate.py:85-123) — but runs as
one batched jit call instead of batch-1 Python loops.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Mapping

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeprest_tpu.config import Config
from deeprest_tpu.models.qrnn import (
    MASKED_PARAM_NAMES, QuantileGRU, put_columns, put_rows, take_columns,
)
from deeprest_tpu.obs import metrics as obs_metrics
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs import spans as obs_spans
from deeprest_tpu.obs.phases import PhaseClock
from deeprest_tpu.ops import scopes
from deeprest_tpu.ops.densify import (
    SparseBase, compact_rule, compact_table, gather_densify_normalize,
    live_columns,
)
from deeprest_tpu.ops.quantile import pinball_loss
from deeprest_tpu.parallel.distributed import (
    feed_replicated, gather_to_host, prefetch_to_device, stage_plan,
    stage_sparse_base,
)
from deeprest_tpu.parallel.elastic import (
    FaultInjector, RemeshExhaustedError, enumerate_healthy, is_device_loss,
)
from deeprest_tpu.parallel.mesh import (
    AXES, NoValidMeshError, make_mesh, mesh_config_of, shrink_mesh_config,
)
from deeprest_tpu.parallel.sharding import (
    carried_rows_split, gather_pieces, shard_params, state_sharding,
)
from deeprest_tpu.train.data import DatasetBundle, eval_window_indices
from deeprest_tpu.train.kept import KeptJit
from deeprest_tpu.train.metrics import Throughput, mae_report


# The host's phases of one train epoch, in the order they happen
# (`log_readback` inside `dispatch`).  `dispatch`, `log_readback` and
# `device_wait` are waits on the device; during `plan_build`, `plan_h2d`
# and `loss_readback` the device has nothing queued.
EPOCH_PHASES = ("plan_build", "plan_h2d", "dispatch", "log_readback",
                "device_wait", "loss_readback")
# The steps of `Trainer.init_state`, in order.  The host's seconds of each
# (trace, compile or cache load, enqueue); `pin` ends with the one wait on
# the state, so what the device still owed the earlier three is in it.
INIT_PHASES = ("model_init", "shard", "opt_init", "pin")


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array


def _names_param(path) -> bool:
    """A leaf of a params mapping or of a mirror of it (the last key of
    its path is a mapping's), not bookkeeping such as Adam's ``count``."""
    return isinstance(path[-1], jax.tree_util.DictKey)


def _is_w_ih(path) -> bool:
    return _names_param(path) and path[-1].key in MASKED_PARAM_NAMES


def live_cols_of(x_base):
    """The table of a base staged in the compact form, else None."""
    return x_base.live if isinstance(x_base, SparseBase) else None


def w_ih_leaves(tree) -> list:
    """The ``MASKED_PARAM_NAMES`` leaves of a params tree, or of the
    optimizer state's mirrors of it (Adam's ``mu`` and ``nu``)."""
    return [a for path, a in jax.tree_util.tree_leaves_with_path(tree)
            if _is_w_ih(path)]


def take_w_ih(tree, live_cols, mesh=None):
    """``tree`` with :func:`take_columns` of each such leaf in its place:
    ``[E, U_pad, 3H]`` for ``[E, F, 3H]``, every other leaf as it is."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (take_columns(a, live_cols, mesh) if _is_w_ih(path)
                         else a), tree)


def put_w_ih(rows, tree, live_cols, mesh=None):
    """The inverse: ``rows`` (a tree as :func:`take_w_ih` gives it), with
    each such leaf written back into ``tree``'s whole one; ``tree`` need
    hold no other leaf (:func:`only_w_ih`)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, new, a: (put_columns(a, live_cols, new, mesh)
                              if _is_w_ih(path) else new), rows, tree)


def only_leaves(tree, keep):
    """``tree`` with only the leaves of params, or of a mirror of them,
    whose name ``keep`` holds: every other such leaf is None, which a
    pytree does not count; what is no mirror of a parameter (Adam's
    ``count``) stays."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if (not _names_param(path) or keep(path[-1].key))
        else None, tree)


def only_w_ih(tree):
    """:func:`only_leaves` of ``tree``'s w_ih leaves."""
    return only_leaves(tree, MASKED_PARAM_NAMES.__contains__)


def _overlaid(under, over):
    """``under`` with every leaf that ``over`` holds in its place (two
    trees of one structure once None counts as a leaf)."""
    return jax.tree.map(lambda a, b: a if b is None else b, under, over,
                        is_leaf=lambda a: a is None)


def _moments_off_table(opt_state, live_cols) -> jax.Array:
    """[F] booleans: the rows of the w_ih leaves that ``live_cols`` does not
    name and at which a moment of either leaf is not exactly zero (NaN
    counts as nonzero)."""
    off = functools.reduce(jnp.logical_or, [
        jnp.any(a != 0, axis=(0, 2)) for a in w_ih_leaves(opt_state)])  # [F]
    return off.at[live_cols].set(
        False, unique_indices=True, indices_are_sorted=True,
        mode="promise_in_bounds")


def moments_off_table_are_zero(opt_state, live_cols) -> jax.Array:
    """Whether no row of the w_ih leaves that ``live_cols`` does not name
    carries a moment (:func:`stale_rows` is 0).  Adam's step on a row whose
    gradient and moments are zero is zero, so while this holds and the
    gradient lives on the table, Adam on the table's rows IS Adam."""
    return ~jnp.any(_moments_off_table(opt_state, live_cols))


def stale_rows(opt_state, live_cols) -> jax.Array:
    """How many of the F rows of a w_ih leaf are stale: not named by
    ``live_cols`` and yet carrying a nonzero moment, in ``mu`` or ``nu`` of
    either leaf (a row counts once).  They are what another corpus's table
    left behind, and they are the rows the compact superstep's off-table
    pass steps (:func:`stale_chunks`)."""
    return jnp.sum(_moments_off_table(opt_state, live_cols), dtype=jnp.int32)


# Rows of a leaf that one trip of the off-table pass takes, steps and puts
# back.  What a trip costs is its rows' (1.93 ms for 64, 3.95 for 128, at
# two steps; my chip runs, PR 34), so a chunk is sized by what its pad rows
# waste (at most 63 rows, 2.6 ms of a 290 ms dispatch) and by the six
# ``[40, 64, 384]`` arrays of a trip staying in VMEM, where a step on them
# is two fusions of 7 us.  A multiple of the float32 tile's 8 rows.
_CHUNK = 64


def _chunks(rows):
    """How many chunks hold ``rows`` rows (an int or a traced count)."""
    return -(-rows // _CHUNK)


def off_table_bound(capacity: int, width: int, steps: int) -> int:
    """The most stale rows that the off-table pass visits row by row; a
    state with more gets the pass over all ``capacity`` rows.  From the
    shapes alone (F, the table's width, the steps of a dispatch):

    a stale row costs a dispatch its take and put, 27 steps' worth of
    streaming it through the all-rows pass, and then its steps at a fifth
    of that pass's cost a row (its chunk is in VMEM); the all-rows pass
    costs every row its steps and nothing else.  So row by row wins while
    ``count * (27 + steps / 5) < capacity * steps``.  Read on a TPU v5e at
    E = 40, H = 128, F = 10,240 (my chip runs, PR 34): 30.2 us a row to
    take and put, 0.23 us a row and step, against 11.45 ms a step over all
    rows (1.12 us a row and step).  A dispatch of 50 steps with 8,591
    stale rows took 643.9 ms row by row and 860.2 over all rows (290.2 with
    64 stale rows: the crossover, 13,800, is past F); one of 2 real steps
    290.4 and 52.0 (30.9 with 64: the crossover is 750).  Never more than
    leaves a chunk of dead rows without a moment to pad the last trip with
    (:func:`stale_chunks`): a state in which every row carries a moment
    takes the all-rows pass whatever the steps.
    """
    crossover = 5 * capacity * steps // (steps + 5 * 27)
    return max(0, min(crossover, capacity - width - _CHUNK))


def off_table_trips(capacity: int, width: int, steps: int, stale: int) -> int:
    """The chunk trips that the off-table pass of one dispatch makes for a
    state with ``stale`` stale rows: a trip a chunk of them, none with
    none; past :func:`off_table_bound` the pass is the loop over the whole
    leaves, which reads as every chunk of them."""
    if stale > off_table_bound(capacity, width, steps):
        return capacity // _CHUNK
    return _chunks(stale)


def rows_visited(capacity: int, width: int, steps: int, stale: int) -> int:
    """The rows of a w_ih leaf that a dispatch of the compact superstep
    writes for a state with ``stale`` stale rows: the table's, and the
    stale rows to the chunk, or all of them past :func:`off_table_bound`."""
    if stale > off_table_bound(capacity, width, steps):
        return capacity
    return width + _chunks(stale) * _CHUNK


def stale_chunks(off: jax.Array, live_cols: jax.Array, bound: int):
    """``(rows, count)``: the ordered list of the stale rows ``off`` marks
    (:func:`_moments_off_table`), as many chunks of it as ``bound`` rows
    fill, and how many ``off`` marks in all.  The
    list is filled up to a whole chunk with the lowest dead rows that carry
    no moment, whose zero-gradient step is exactly nothing, merged in
    order: every chunk of it that holds a stale row is sorted, without
    repeats and in bounds, which is what :func:`take_columns` is promised,
    and names no row of the table.  Entries past that are ``F`` and for no
    one to read.  While ``count`` is at most
    :func:`off_table_bound`, the dead rows suffice."""
    f = off.shape[0]
    count = jnp.sum(off, dtype=jnp.int32)
    dead = (~off).at[live_cols].set(
        False, unique_indices=True, indices_are_sorted=True,
        mode="promise_in_bounds")
    pad = dead & (jnp.cumsum(dead, dtype=jnp.int32) <= -count % _CHUNK)
    rows = jnp.sort(jnp.where(off | pad, jnp.arange(f, dtype=jnp.int32), f))
    return rows[:_chunks(max(bound, 1)) * _CHUNK], count


@dataclasses.dataclass
class EpochResult:
    epoch: int
    train_loss: float
    test_loss: float | None
    report: dict | None


class Trainer:
    """Owns the model, optimizer, mesh, and compiled steps."""

    def __init__(self, config: Config, feature_dim: int, metric_names: list[str],
                 mesh=None):
        self.config = config
        self.metric_names = list(metric_names)
        self.model_config = dataclasses.replace(
            config.model, feature_dim=feature_dim, num_metrics=len(metric_names)
        )
        self.tx = optax.adam(config.train.learning_rate)
        self.mesh = mesh if mesh is not None else make_mesh(config.mesh)
        self.throughput = Throughput()
        self._global_step = 0      # host-side mirror of state.step for logging
        # Per-step losses of the most recent train_epoch (np [K], one host
        # readback per epoch/superstep) — the superstep-vs-per-step parity
        # tests and callers that want the full curve read this.
        self._last_epoch_losses: np.ndarray | None = None
        # (jitted program, its arguments after the state) of the most
        # recent train_epoch's dispatches: profile_epoch lowers exactly
        # this to name the trace's operations.
        self._dispatched: tuple | None = None
        # How often stage_dataset has run, its last call's compact table
        # (host copy; None for a feed without one) and what the rule of the
        # compact form decided there (empty for a feed that is not sparse):
        # the stage span's tags.  THE PROGRAM OF THIS STAGING is what that
        # call decided of the programs that read the staged base: the form
        # (`compact` or `dense` for a sparse corpus, `base` for a dense
        # staged one, None where nothing was staged) and the width they
        # contract over.  (What a mesh splits of the rows follows from the
        # width and the mesh, and a new mesh builds the programs anew.)  A
        # life that restages may meet several; the first-dispatch books and
        # the gauges of _publish_program are kept by it (_build_programs).
        self._stagings = 0
        self._staged_table: np.ndarray | None = None
        self._staged_form: dict = {}
        self._staged_program: tuple = (None, 0)
        # The tags of the last stage_dataset call's span, for a caller that
        # logs them with the recorder off (train/stream.py).
        self.last_stage: dict = {}
        # Whether a train_epoch has finished (device memory is read once,
        # after the first).
        self._epoch_finished = False
        # The table of the epoch fit(profile_dir=...) ran through
        # profile_epoch, for the caller to print or write.
        self.last_profile: dict | None = None
        # Preemption-safe snapshot state (enable_snapshots / ROADMAP item
        # 7 dynamic half).  The epoch-plan cursor lives here between the
        # fit loop (which pins the epoch index + the shuffle rng's
        # bit-generator state at epoch START) and the epoch drivers
        # (which advance the step offset at step/superstep boundaries).
        self._snapshot_dir: str | None = None
        self._snapshot_every = 0
        self._snapshot_extra_fn = None
        self._steps_since_snapshot = 0
        self._snapshots_written = 0
        self._cursor_epoch: int | None = None
        self._cursor_rng_state: dict | None = None
        self._epoch_steps_done = 0
        self._epoch_num_steps = 0
        # Elastic remeshing (TrainConfig.elastic): the deterministic CPU
        # fault injector (None on hardware — real XlaRuntimeErrors are
        # the detect signal there), the in-flight flag the streaming
        # trainer defers refresh decisions on, and the per-fit remesh
        # ledger (attempt count + the last recovery's facts, which the
        # chaos bench and tests read).
        self._fault_injector: FaultInjector | None = None
        self._remesh_in_flight = False
        self.remesh_count = 0
        self.last_remesh: dict | None = None
        self.remesh_history: list[dict] = []
        self._build_programs()
        self._build_metrics()

    def _build_programs(self) -> None:
        """(Re)build every jitted program against the CURRENT mesh.

        Called from ``__init__`` and again by :meth:`remesh`: the
        programs close over ``self.mesh`` through ``pin_state``'s
        rule-table constraint, and a cached jit wrapper pins its device
        set — dispatching new-mesh arguments into an old-mesh wrapper is
        an "incompatible devices" error, not a retrace.  Rebuilding the
        wrappers keeps the executable story flat: each wrapper holds one
        executable per signature ON THE CURRENT SHAPE (the chaos bench's
        flatness gate), and XLA's persistent compilation cache absorbs
        any recurring shape.  The model is rebuilt with them: it hands
        the mesh to the pallas recurrence (ops/gru.py shard_map).
        """
        self.model = QuantileGRU(config=self.model_config, mesh=self.mesh)
        quantiles = self.model_config.quantiles
        # the epoch span's tag; the staged program whose superstep the
        # gauges of _publish_program describe (None: none yet); the
        # programs built here that have been dispatched once, by name and
        # by the staged program they ran on (_first_dispatch), and of them
        # the supersteps in the order this build met them
        self._mesh_tag = "x".join(str(self.mesh.shape[a]) for a in AXES)
        self._published_program: tuple | None = None
        self._dispatched_once: set[tuple] = set()
        self._superstep_programs: list[tuple] = []

        def pin_state(state: TrainState,
                      carried_rows: bool = False) -> TrainState:
            """Constrain every leaf to its CANONICAL named sharding, all
            resolved from the ONE rule table (parallel/sharding.py
            PARTITION_RULES — params, their optimizer mirrors, and the
            replicated step/rng bookkeeping; strict mode errors at trace
            time on any leaf the table does not place).

            Without this, GSPMD collapses the output params' specs (e.g.
            P('expert', None) → P() on a trivial mesh axis) and flips
            committedness, so the step's output state has a different
            signature than init_state's — the second call then silently
            compiles a SECOND executable whose fusion can round the last
            bit differently.  Pinning both init_state and every step
            output to one signature keeps the jit cache at one executable
            per step function (the no-recompile probe) and is what makes
            the superstep scan bit-identical to the per-step loop.

            ``carried_rows``: the state's w_ih leaves are the table's rows
            that ride the compact superstep's scan, split over ``data``
            (the rule table's CARRIED_ROWS_RULES).
            """
            return jax.tree.map(
                jax.lax.with_sharding_constraint, state,
                state_sharding(self.mesh, state, carried_rows))

        self._pin_state = jax.jit(pin_state)

        def split_rows(live_cols) -> bool:
            """Whether the rows of this table that ride the compact
            superstep's scan are split over the mesh's ``data`` axis."""
            return carried_rows_split(self.mesh, live_cols.shape[0]) > 1

        def adam(params, grads, opt_state):
            updates, opt_state = self.tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state

        @jax.named_scope(scopes.OPTIMIZER)
        def apply_gradients(state: TrainState, grads, rows_split=False):
            """The one ``tx.update`` of a step, ORDERED BY LEAF where the
            layer has two w_ih leaves whose gradients are whole dots: the
            first one's update on its own sub-tree, one
            ``optimization_barrier`` over Adam's ``count`` and that leaf's
            new params, ``mu`` and ``nu``, then every other leaf's with
            the ``count`` that came through it.  The arithmetic is
            ``tx.update``'s, operation for operation (Adam of a leaf reads
            that leaf's gradient and the shared ``count`` alone), and the
            state keeps its tree.  Why: XLA:TPU fuses the two leaves' Adam
            loops as siblings and a fusion takes one convolution, so the
            OTHER direction's weight-gradient dot ran bare on the MXU with
            HBM idle and its bf16 ``[E, F, 3H]`` result was written once
            and read once (1.3–4.1 ms a step in the wide cells: PERF.md
            section 6, PRs 55 and 56).  Behind the barrier the second
            leaf's Adam can share no fusion with the first's: each
            direction's dot, fold backward and Adam are one fusion that
            writes no gradient.  The barrier holds only what the step
            returns anyway; a GRADIENT must never go behind it (it would
            be materialised, which is what this removes).  ``rows_split``:
            the carried rows are split over a `data` axis, where
            `sharding.project_split_rows` owns the backward and its chunk
            dots carry no Adam: the plain update, as for a tree with one
            w_ih leaf or none."""
            w_ih = [k for k in MASKED_PARAM_NAMES if k in grads]
            if len(w_ih) < 2 or rows_split:
                return adam(state.params, grads, state.opt_state)
            trees = state.params, grads, state.opt_state
            first = adam(*(only_leaves(t, w_ih[0].__eq__) for t in trees))
            counts, first = jax.lax.optimization_barrier(
                (only_leaves(state.opt_state, lambda _: False), first))
            params, grads, opt_state = (
                only_leaves(t, w_ih[0].__ne__) for t in trees)
            return _overlaid(
                first, adam(params, grads, _overlaid(opt_state, counts)))

        @jax.named_scope(scopes.DROPOUT)
        def dropout_key(state: TrainState):
            return jax.random.fold_in(state.rng, state.step)

        def microbatch_loss(params, dropout_rng, xb, yb, wb, live_cols, w_ih,
                            allow_empty=False):
            # One forward pass on one batch of B windows under one kept
            # mask; `params` holds the table's rows of the `w_ih` leaves
            # in their place where `w_ih` is given (see train_step).
            preds = self.model.apply(
                {"params": {**params, **w_ih}}, xb, deterministic=False,
                rngs={"dropout": dropout_rng}, live_cols=live_cols,
                live_w_ih={k: params[k] for k in w_ih} or None,
            )
            return pinball_loss(preds, yb, quantiles, sample_weight=wb,
                                allow_empty=allow_empty)

        def train_step(state: TrainState, xb, yb, wb, live_cols=None,
                       full_w_ih=None):
            # With `full_w_ih` (the compact superstep, with live_cols): the
            # state holds the table's rows [E, U_pad, 3H] of the w_ih
            # leaves, and of their moments, in the leaves' place.  The
            # model is handed them as `live_w_ih`, the step differentiates
            # with respect to them and runs the one tx.update on them with
            # the shared count: no [E, F, 3H] array is read or made.  The
            # whole leaves of `full_w_ih` ride along unread (flax holds a
            # supplied leaf to its init shape).  Under a `data` axis that
            # divides them the rows are a chip's own quarter (see
            # train_superstep), and stay so in the state this returns.
            w_ih = full_w_ih or {}
            loss, grads = jax.value_and_grad(microbatch_loss)(
                state.params, dropout_key(state), xb, yb, wb, live_cols, w_ih)
            carried = bool(w_ih) and split_rows(live_cols)
            params, opt_state = apply_gradients(state, grads, carried)
            return (
                pin_state(TrainState(step=state.step + 1, params=params,
                                     opt_state=opt_state, rng=state.rng),
                          carried_rows=carried),
                loss,
            )

        def gather_x(x_base, idx):
            # The one place the staged feed's two forms meet: a dense
            # normalized [T, F] base gathers directly; a SparseBase
            # (padded-COO cols/vals + staged stats) gathers [.., W, K]
            # rows, densifies via one scatter-add, and normalizes ON
            # DEVICE — all inside the caller's existing jit, so the
            # sparse feed adds no executables beyond the per-form
            # signature (ops/densify.py for the numerics contract).  A
            # base staged in the compact form gives windows of its live
            # columns only, for the model to take with live_cols_of().
            if isinstance(x_base, SparseBase):
                return gather_densify_normalize(x_base, idx)
            return x_base[idx]

        @jax.named_scope(scopes.GATHER)
        def gather_windows(x_base, y_base, starts):
            w = self.config.train.window_size
            idx = starts[:, None] + jnp.arange(w)[None, :]    # [B, W]
            return gather_x(x_base, idx), y_base[idx]

        def train_step_indexed(state: TrainState, x_base, y_base, starts, wb,
                               full_w_ih=None):
            # Device-resident feed: the normalized BASE series live in HBM
            # (stage_dataset) and each step gathers its windows by start
            # index — per-step host→device traffic is [B] int32 + weights
            # instead of the [B,W,F] window tensor (windows overlap W−1 of
            # W rows, so materialized shipping re-sends every row W times).
            return train_step(state, *gather_windows(x_base, y_base, starts),
                              wb, live_cols_of(x_base), full_w_ih)

        # G = grad_accum_windows microbatches to one optimizer update, a
        # static of the programs built here.  At G = 1 a step IS an update
        # and every program below traces as it did before the knob existed.
        accum_g = int(self.config.train.grad_accum_windows)

        def train_update(state: TrainState, x_base, y_base, starts, wb,
                         full_w_ih=None):
            # One optimizer update from G microbatches (starts, wb: [G, B]):
            # plain Adam on the gradient of the weighted mean loss over ALL
            # real windows of the group, what one batch of G x B windows
            # gives.  Each microbatch gathers its windows, draws its own
            # kept mask (fold_in(fold_in(rng, step), g)) and differentiates
            # with respect to `state.params` exactly as train_step does (on
            # a compact base the carried [E, U_pad, 3H] rows and the other
            # leaves), its loss weighted by its share n_g / N of the
            # group's real windows, and adds into an accumulator of the
            # params' shapes; tx.update runs ONCE.  The microbatches are a
            # lax.scan UNROLLED G times: the program grows with G (at G = 8
            # on a v5e 54 MB of code for 18, 2.87 GB of temporaries for
            # 1.10, 20 s to compile for 9), and XLA then computes what
            # depends on the parameters alone (the mask's softmax over F,
            # the fold of the carried rows) once an update and not once a
            # microbatch: 307.4 steps/s for the rolled loop's 222.6 in
            # `tenk-train-accum8` (PERF.md section 6, PR 48).  A
            # zero-weight pad microbatch inside a real group has share 0
            # and adds exactly nothing (pinball_loss allow_empty guards its
            # 0/0); a group with no real window is skipped whole by the
            # caller.  `state.step` counts the REAL microbatches (the
            # dropout stream's and the resume cursor's unit); Adam's count
            # counts updates.
            live_cols = live_cols_of(x_base)
            w_ih = full_w_ih or {}
            carried = bool(w_ih) and split_rows(live_cols)
            step_key = dropout_key(state)
            per_micro = jnp.sum(wb, axis=1)
            share = per_micro / jnp.sum(per_micro)               # [G]

            def micro(acc, plan):
                g, starts_g, wb_g, share_g = plan
                xb, yb = gather_windows(x_base, y_base, starts_g)
                with jax.named_scope(scopes.DROPOUT):
                    rng_g = jax.random.fold_in(step_key, g)

                def weighted(params):
                    loss = microbatch_loss(params, rng_g, xb, yb, wb_g,
                                           live_cols, w_ih, allow_empty=True)
                    return loss * share_g.astype(loss.dtype), loss

                (_, loss), grads = jax.value_and_grad(
                    weighted, has_aux=True)(state.params)
                with jax.named_scope(scopes.ACCUMULATE):
                    acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss.astype(jnp.float32)

            with jax.named_scope(scopes.ACCUMULATE):
                zeros = jax.tree.map(
                    jax.lax.with_sharding_constraint,
                    jax.tree.map(jnp.zeros_like, state.params),
                    state_sharding(self.mesh, state, carried).params)
            grads, losses = jax.lax.scan(
                micro, zeros, (jnp.arange(accum_g), starts, wb, share),
                unroll=accum_g)
            params, opt_state = apply_gradients(state, grads, carried)
            n_real = jnp.sum((per_micro > 0).astype(jnp.int32))
            return (
                pin_state(TrainState(step=state.step + n_real, params=params,
                                     opt_state=opt_state, rng=state.rng),
                          carried_rows=carried),
                losses,
            )

        def train_superstep(state: TrainState, x_base, y_base,
                            starts_plan, weights_plan, chunk):
            # One donated dispatch = S train steps via lax.scan.  The
            # whole epoch's [C, S, B] plan is device-resident (stage_plan)
            # and the chunk index is a TRACED scalar, so every chunk of
            # every epoch — including the zero-weight-padded trailing one
            # — reuses one executable.  Padded steps (weights all zero)
            # take lax.cond's skip branch: the prior state passes through
            # untouched (step counter, fold_in(rng, step) dropout stream,
            # params — exactly as if the padding never ran) and the wasted
            # step compute is skipped outright.  cond rather than a
            # select over the state: fusing a where into the loop body
            # changed last-bit rounding of the backward pass, breaking
            # the bit-exactness contract with the per-step loop; the cond
            # sub-computation preserves the standalone step's rounding
            # (verified by tests/test_superstep.py).
            #
            # With G > 1 microbatches an update the chunk [S, B] is
            # [S/G, G, B] (the planner rounds S up to a multiple of G), the
            # scan advances one UPDATE a trip (train_update) and a group
            # with no real window takes the skip branch as a padded step
            # does; everything below (the carried rows, their split over
            # `data`, the off-table pass) is the same code for both.
            starts_c = jax.lax.dynamic_index_in_dim(
                starts_plan, chunk, 0, keepdims=False)       # [S, B]
            weights_c = jax.lax.dynamic_index_in_dim(
                weights_plan, chunk, 0, keepdims=False)      # [S, B]
            if accum_g > 1:
                grouped = (-1, accum_g, starts_c.shape[1])
                starts_c = starts_c.reshape(grouped)         # [S/G, G, B]
                weights_c = weights_c.reshape(grouped)

            # A compact base's gradient lives on its table's rows of the
            # w_ih leaves, and Adam is elementwise.  So the table's rows of
            # the two leaves and of their moments are taken once, here;
            # they ride the scan in the leaves' place, each step
            # differentiating with respect to them and updating them; and
            # they are put back once, into the donated leaves, after it.
            # Nothing [E, F, 3H] is named inside the scan.
            #
            # Under a `data` axis that divides the table's width the six
            # carried arrays are split over it along the rows for the
            # length of the dispatch (parallel/sharding.py owns the spec):
            # a chip takes its own U_pad / data rows from its own whole
            # leaves, and each step folds and casts them, gathers the
            # bfloat16 folded weight for the projection (at a wide table
            # by groups of experts, beside the groups' dots), gets that
            # weight's bfloat16 gradient back reduce-scattered (the same
            # sum of the same addends an all-reduce makes, element by
            # element; at a wide table round a ring of chunk dots,
            # sharding.project_split_rows, and not by the partitioner's
            # collective) and runs the fold's backward and Adam on its rows.
            # After the scan the six arrays are gathered once, so what is
            # put into the donated leaves, and the state the dispatch
            # returns, is whole and the same on every chip.  There is one
            # float32 copy of a carried row inside a dispatch where there
            # were `data`; every update is still plain Adam on the mean
            # gradient over all the windows of the global batch.
            live_cols = live_cols_of(x_base)
            full_w_ih = ({k: v for k, v in state.params.items()
                          if k in MASKED_PARAM_NAMES}
                         if live_cols is not None else {})
            split = bool(full_w_ih) and split_rows(live_cols)

            def body(st, step_plan):
                starts, wb = step_plan

                def run(s):
                    s2, loss = (train_update if accum_g > 1
                                else train_step_indexed)(
                        s, x_base, y_base, starts, wb, full_w_ih)
                    # f32 losses regardless of compute dtype so the skip
                    # branch's zero matches the run branch's aval.
                    return s2, loss.astype(jnp.float32)

                def skip(s):
                    return s, jnp.zeros(wb.shape[:-1], jnp.float32)

                return jax.lax.cond(jnp.any(wb > 0), run, skip, st)

            carry = (take_w_ih(state, live_cols, self.mesh) if full_w_ih
                     else state)
            if split:
                carry = pin_state(carry, carried_rows=True)
            rows, losses = jax.lax.scan(body, carry, (starts_c, weights_c))
            if accum_g > 1:
                losses = losses.reshape(-1)                  # [S] f32
            if not full_w_ih:
                return rows, losses
            if split:
                rows = pin_state(rows)        # whole again: six gathers

            # Off the table the gradient is exactly zero, so what a row
            # there does in a dispatch depends on its moments, the count
            # and the number of real steps alone.  Where its moments are
            # zero (every such row of a state from init_state, or of one
            # trained on this table only) it does nothing, and keeps that
            # true.  A stale row (moments another corpus's table left, a
            # dense-form run's) still moves: that many zero-gradient
            # updates, with the counts the scan used, before the carried
            # rows go on top.  So the pass visits the stale rows, a chunk
            # a trip: taken from the six whole leaves as the table's rows
            # were (stale_chunks keeps take_columns' promises), stepped,
            # put back a row at a time (put_rows: a scatter would pass
            # over the whole leaf every trip).  A state in which they are
            # most of F (past off_table_bound) gets the updates on the
            # whole leaves instead, which costs a row less.  Either rule is
            # a loop's trip count and not a cond round it (a conditional's
            # identity branch copies the six leaves each dispatch), nor a
            # second step in the program (5 s more to load it from the
            # cache).
            rows_ok = moments_off_table_are_zero(state.opt_state, live_cols)
            whole = only_w_ih(state.params), only_w_ih(state.opt_state)
            # The updates the scan ran: at G = 1 a real step is one; under
            # accumulation Adam's own count says (a step is a microbatch).
            real_steps = (rows.step - state.step if accum_g == 1 else
                          rows.opt_state[0].count - state.opt_state[0].count)
            bound = off_table_bound(x_base.capacity, live_cols.shape[0],
                                    starts_c.shape[0])
            stale, count = stale_chunks(
                _moments_off_table(state.opt_state, live_cols), live_cols,
                bound)
            count = jnp.where(rows_ok, 0, count)
            by_row = count <= bound

            @jax.named_scope(scopes.OFF_TABLE)
            def off_table_step(_, leaves):
                params, opt_state = leaves
                return adam(params, jax.tree.map(jnp.zeros_like, params),
                            opt_state)

            @jax.named_scope(scopes.OFF_TABLE)
            def off_table_chunk(i, leaves):
                at = jax.lax.dynamic_slice_in_dim(stale, i * _CHUNK, _CHUNK)
                chunk = jax.lax.fori_loop(
                    0, real_steps, off_table_step,
                    take_w_ih(leaves, at, self.mesh))
                # every chunk steps from the counts the dispatch began with
                return jax.tree_util.tree_map_with_path(
                    lambda path, a, new: (put_rows(a, at, new)
                                          if _is_w_ih(path) else a),
                    leaves, chunk)

            whole = jax.lax.fori_loop(
                0, jnp.where(by_row, _chunks(count), 0),
                off_table_chunk, whole)
            params, opt_state = jax.lax.fori_loop(
                0, jnp.where(by_row, 0, real_steps), off_table_step, whole)
            return pin_state(rows.replace(
                params=put_w_ih(rows.params, params, live_cols, self.mesh),
                opt_state=put_w_ih(rows.opt_state, opt_state, live_cols,
                                   self.mesh),
            )), losses

        def eval_step(params, xb, yb, live_cols=None):
            preds = self.model.apply({"params": params}, xb,
                                     deterministic=True, live_cols=live_cols)
            loss = pinball_loss(preds, yb, quantiles)
            return preds, loss

        def eval_step_indexed(params, x_base, y_base, starts):
            return eval_step(params, *gather_windows(x_base, y_base, starts),
                             live_cols_of(x_base))

        self._train_step = jax.jit(train_step, donate_argnums=0)
        self._train_step_indexed = jax.jit(train_step_indexed, donate_argnums=0)
        # The superstep's executable is kept from one process to the next
        # (train/kept.py: a later process of the same key loads it and
        # traces nothing).  Its key takes the WHOLE config, so that a new
        # field enters by itself, but for the seed: that makes the state
        # and the host's shuffles, both arguments of the program.
        self._superstep = KeptJit(
            train_superstep, self.mesh,
            (dataclasses.replace(
                self.config,
                train=dataclasses.replace(self.config.train, seed=0)),
             self.model_config),
            donate_argnums=0)
        self._stale_rows = jax.jit(stale_rows)
        self._eval_step = jax.jit(eval_step)
        self._eval_step_indexed = jax.jit(eval_step_indexed)
        self._predict_step = jax.jit(
            lambda params, xb: self.model.apply(
                {"params": params}, xb, deterministic=True
            )
        )
        # Their compilations are counted under their own names from here
        # on (obs/setup.py; a lambda's name is nobody's).
        obs_setup.install({fn.__name__ for fn in self._jitted()}
                          - {"<lambda>"})

    def _jitted(self) -> tuple:
        """The trainer's jitted programs."""
        return (self._train_step, self._train_step_indexed, self._superstep,
                self._stale_rows, self._eval_step, self._eval_step_indexed,
                self._predict_step, self._pin_state)

    def _build_metrics(self) -> None:
        # Training-plane obs metrics (process-wide registry singletons —
        # step time itself rides in via Throughput.stop): superstep
        # dispatch counts, the designed host-readback counter, and what
        # set-up cost (obs/setup.py names those; every compilation of the
        # process is counted there).  One touch per epoch/superstep/
        # log-boundary or once a trainer — never per step.
        self._m_dispatches = obs_metrics.REGISTRY.counter(
            "deeprest_train_superstep_dispatches_total",
            "fused lax.scan superstep dispatches")
        self._m_readbacks = obs_metrics.REGISTRY.counter(
            "deeprest_train_readbacks_total",
            "designed device->host readbacks by sink",
            labelnames=("sink",))
        self._epoch_clock = PhaseClock(
            "train.epoch", "deeprest-trainer", EPOCH_PHASES,
            last_seconds=obs_metrics.REGISTRY.gauge(
                "deeprest_train_last_epoch_phase_seconds",
                "the last finished epoch's host seconds by phase (a phase "
                "inside another is timed exclusively)",
                labelnames=("phase",)),
            units_total=obs_metrics.REGISTRY.counter(
                "deeprest_train_epochs_total", "train epochs finished"))
        self._m_projection_columns = obs_metrics.REGISTRY.gauge(
            obs_setup.PROJECTION_COLUMNS,
            "columns of the staged sparse corpus: live (can be nonzero), "
            "padded (the power of two the rule of the compact form "
            "weighed), bound (the widest table it admits; 0 where the "
            "mesh's model axis shards F), contracted (the layer-0 "
            "projection sums over), total (F)",
            labelnames=("kind",))
        self._m_optimizer_rows = obs_metrics.REGISTRY.gauge(
            obs_setup.OPTIMIZER_ROWS,
            "rows of each layer-0 input weight over which the last epoch's "
            "steps on a staged sparse corpus were Adam (updated), and which "
            "its dispatches wrote (visited), of F (total); stale: rows off "
            "the staged table that carried a nonzero moment when the epoch "
            "began; bound: the most stale rows a dispatch of its shapes "
            "visits row by row; trips: the chunk trips its off-table pass "
            "made (all of F's chunks past the bound); the last three "
            "counted on a compact base only; per_chip: the rows whose Adam "
            "ONE chip ran a step (the table's width over the mesh's data "
            "axis where the compact superstep splits its carried rows over "
            "it, else updated)",
            labelnames=("kind",))
        self._m_gather_pieces = obs_metrics.REGISTRY.gauge(
            obs_setup.GATHER_PIECES,
            "pieces in which a direction's folded layer-0 input weight "
            "reaches the projection each step where the compact superstep "
            "splits its carried rows over the mesh's data axis: the groups "
            "of experts gathered beside the projection's dots, 1 where the "
            "partitioner gathers it whole (parallel/sharding.gather_pieces)")
        self._m_updates = obs_metrics.REGISTRY.counter(
            "deeprest_train_optimizer_updates_total",
            "optimizer updates (Adam steps) the finished train epochs ran: "
            "one a real step, one a group of grad_accum_windows microbatches")
        self._m_accumulation = obs_metrics.REGISTRY.gauge(
            obs_setup.ACCUMULATION,
            "gradient accumulation of the dispatched superstep: "
            "microbatches an optimizer update (grad_accum_windows), and "
            "carry_bytes, the gradient accumulator an update carries across "
            "them (the shapes its scan carries; 0 with one microbatch)",
            labelnames=("kind",))
        self._m_stagings = obs_metrics.REGISTRY.counter(
            obs_setup.STAGINGS, "stage_dataset calls of this process")
        self._m_stage_seconds = obs_metrics.REGISTRY.gauge(
            obs_setup.STAGE_SECONDS,
            "host seconds of the last stage_dataset call")
        self._init_clock = PhaseClock(
            "train.init_state", "deeprest-trainer", INIT_PHASES,
            last_seconds=obs_metrics.REGISTRY.gauge(
                obs_setup.INIT_STATE_SECONDS,
                "the last init_state's host seconds by phase; pin ends "
                "with the wait on the state",
                labelnames=("phase",)))
        self._m_first_dispatch = obs_metrics.REGISTRY.gauge(
            obs_setup.FIRST_DISPATCH_SECONDS,
            "host seconds of the first call of a jitted trainer program "
            "(trace, lower, compile or cache load, enqueue; the "
            "superstep's and the per-step programs' with the wait for "
            "that first dispatch)",
            labelnames=("program",))
        self._m_superstep_programs = obs_metrics.REGISTRY.counter(
            obs_setup.SUPERSTEP_PROGRAMS,
            "supersteps a trainer dispatched for the first time, by the "
            "program of the staging they ran on: the form of the staged "
            "base and the width they contract over (a life whose live set "
            "outgrows a table, or the compact form, meets more than one)",
            labelnames=("form", "width"))
        self._m_superstep_first_dispatch = obs_metrics.REGISTRY.gauge(
            obs_setup.SUPERSTEP_FIRST_DISPATCH_SECONDS,
            "host seconds of the first dispatch of the nth superstep the "
            "programs of this build met (trace, lower, compile or load, "
            "enqueue, the wait for it), by its staged program",
            labelnames=("nth", "form", "width"))
        self._m_device_bytes = obs_metrics.REGISTRY.gauge(
            obs_setup.DEVICE_BYTES,
            "device memory of the mesh's fullest device (in_use, peak) as "
            "init_state returned, as stage_dataset returned, and after the "
            "first train_epoch (set where the backend reports it)",
            labelnames=("at", "kind"))
        self._m_program_bytes = obs_metrics.REGISTRY.gauge(
            obs_setup.PROGRAM_BYTES,
            "the dispatched superstep executable's memory_analysis: "
            "arguments, outputs, aliased, temporaries, code",
            labelnames=("kind",))
        self._m_kernel_operand_bytes = obs_metrics.REGISTRY.gauge(
            obs_setup.KERNEL_OPERAND_BYTES,
            "bytes the dispatched superstep hands each pallas kernel a "
            "step (operands and results), by the memory space the "
            "compiler assigned them (hbm, vmem)",
            labelnames=("kernel", "space"))
        self._m_collective_bytes = obs_metrics.REGISTRY.gauge(
            "deeprest_train_collective_bytes",
            "bytes a train step of the compiled superstep hands to its "
            "collectives, by kind (set under a mesh of more than one device)",
            labelnames=("op",))
        self._m_dropout_draws = obs_metrics.REGISTRY.gauge(
            "deeprest_train_dropout_draws",
            "places of the compiled superstep (fusions) that generate the "
            "dropout scope's random bits: 1 a forward pass where the mask "
            "is drawn once and kept for the backward pass (set where the "
            "step draws one)")
        self._m_time_reversals = obs_metrics.REGISTRY.gauge(
            obs_setup.TIME_REVERSALS,
            "arrays a step of the compiled superstep reverses in time under "
            "the recurrence scope (fused or not): 0 where the kernels walk "
            "the reverse direction's time blocks back to front themselves")
        self._m_kernel_edge_passes = obs_metrics.REGISTRY.gauge(
            obs_setup.KERNEL_EDGE_PASSES,
            "instructions of a step of the compiled superstep that only "
            "read or write again a whole operand or result of a recurrence "
            "kernel call (a sum over dproj for the input bias, the split "
            "of the joined cotangent): 0 where the backward kernels do "
            "both themselves")
        self._m_bare_weight_grad_dots = obs_metrics.REGISTRY.gauge(
            obs_setup.BARE_WEIGHT_GRAD_DOTS,
            "fusions of a step of the compiled superstep that compute a "
            "layer-0 input weight's gradient and return it instead of the "
            "updated weight and its moments: 0 where each direction's dot "
            "carries its own leaf's fold backward and Adam")
        # Elastic-remeshing legs (detect -> rebuild -> restore -> resume),
        # one increment per event — never on the step path.
        self._m_device_losses = obs_metrics.REGISTRY.counter(
            "deeprest_train_device_losses_total",
            "device-loss events caught by the elastic fault barrier")
        self._m_remeshes = obs_metrics.REGISTRY.counter(
            "deeprest_train_remeshes_total",
            "elastic remesh outcomes", labelnames=("outcome",))
        self._m_recovery = obs_metrics.REGISTRY.gauge(
            "deeprest_train_remesh_recovery_seconds",
            "wall seconds of the last remesh recovery "
            "(detect through restore; the first post-restore dispatch "
            "additionally pays one compile per new mesh shape)")

    def _jit_cache_size(self) -> int | None:
        """Total compiled-executable count across the trainer's jitted
        programs (None when the running jax version has no cache probe) —
        the no-recompile probes' shared hook."""
        sizes = []
        for fn in self._jitted():
            probe = getattr(fn, "_cache_size", None)
            if callable(probe):
                sizes.append(int(probe()))
        return sum(sizes) if sizes else None

    def _dispatch_key(self, program) -> tuple:
        """(the jitted ``program``'s name, the staged program it runs on):
        ``pin_state`` reads no staged base and is one program a build."""
        on = None if program is self._pin_state else self._staged_program
        return program.__name__, on

    def _dispatched_before(self, program) -> bool:
        """Whether the jitted ``program`` has run on the program of this
        staging since the programs were built."""
        return self._dispatch_key(program) in self._dispatched_once

    @contextlib.contextmanager
    def _first_dispatch(self, program):
        """Round the first call of the jitted ``program`` ONCE A PROGRAM:
        the first since the programs were built on the program of this
        staging (``_staged_program``; a restage that keeps it changes
        nothing, one that comes back to a program this build has
        dispatched is no first dispatch).  A span ``train.first_dispatch``
        tagged with its name, the set-up phase ``first_dispatch`` (so what
        it traces, lowers, compiles or loads is counted there), its
        seconds in ``deeprest_train_first_dispatch_seconds{program}``.
        The superstep's is also tagged ``nth_program`` (which superstep of
        this build, from 1), ``form`` and ``width``, adds one to
        ``deeprest_train_superstep_programs_total{form,width}`` and keeps
        its seconds in
        ``deeprest_train_superstep_first_dispatch_seconds{nth,form,width}``.
        Nothing round a later call."""
        if self._dispatched_before(program):
            yield
            return
        name = program.__name__
        tags = {"program": name}
        labels = None
        if program is self._superstep:
            form, width = self._staged_program
            labels = {"form": str(form), "width": width}
            tags.update(nth_program=len(self._superstep_programs) + 1,
                        **labels)
        clock = obs_metrics.Stopwatch()
        with obs_spans.RECORDER.span("train.first_dispatch",
                                     "deeprest-trainer", tags), \
                obs_setup.phase("first_dispatch"):
            yield
        self._m_first_dispatch.set(clock.elapsed(), program=name)
        self._dispatched_once.add(self._dispatch_key(program))
        if labels is not None:
            if not self._superstep_programs:
                # the gauge lists ONE build's programs: the newest's
                self._m_superstep_first_dispatch.clear()
            self._superstep_programs.append(self._staged_program)
            self._m_superstep_programs.inc(**labels)
            self._m_superstep_first_dispatch.set(
                clock.elapsed(), nth=tags["nth_program"], **labels)

    def _publish_device_bytes(self, at: str) -> None:
        """``deeprest_train_device_bytes{at}``: what the fullest of this
        process's devices of the mesh holds now and its peak so far.  A
        backend that reports no ``memory_stats`` (the CPU) sets nothing."""
        stats = [s for s in (d.memory_stats() for d in self.mesh.local_devices)
                 if s]
        if not stats:
            return
        fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
        self._m_device_bytes.set(fullest.get("bytes_in_use", 0),
                                 at=at, kind="in_use")
        self._m_device_bytes.set(fullest.get("peak_bytes_in_use", 0),
                                 at=at, kind="peak")

    def _publish_program(self, state, steps: int = 1, x_base=None) -> None:
        """What the compiler made of the superstep this epoch dispatched
        (``steps``: the trips of its scan, updates under accumulation),
        ONCE A PROGRAM: the epoch driver calls it whenever the program of
        this staging is not the one the gauges describe (the first epoch
        of a build of the programs, and the first after a restage that
        changed the program, the way back included), so every gauge set
        here describes the superstep being dispatched; first, from the shapes alone,
        ``deeprest_train_accumulation{kind}`` (:meth:`_accum_carry_bytes`
        on the base ``x_base`` the dispatch read); then, from the executable
        the dispatch left in the jit's cache (:meth:`_dispatched_executable`:
        no second compile): its ``memory_analysis`` into
        ``deeprest_train_program_bytes{kind}``; from its text, parsed
        once, where each pallas kernel's operands and results live
        (``deeprest_train_kernel_operand_bytes{kernel,space}``: the
        memory-space assignment is the compiler's, and moves a kernel's
        time with nothing in the kernel changed) and, under a mesh of
        more than one device, what a step hands to each kind of collective
        (``deeprest_train_collective_bytes{op}``: the partitioner decides
        what is reduced or gathered and in which type, so no sum over the
        gradient tree would say it; what stands outside the scan, the
        carried rows' gathers of a dispatch, counts a ``steps``-th) and in how many places a step generates
        the dropout mask's random bits (``deeprest_train_dropout_draws``:
        the program draws the mask once a forward pass, and the compiler
        draws it again wherever it fuses the draw into a consumer) and
        reverses an array in time round the recurrence
        (``deeprest_train_time_reversals``: none since the kernels walk
        the reverse direction themselves) or passes over a kernel's operand
        or result only to cut or to sum it
        (``deeprest_train_kernel_edge_passes``: none since the recurrence's
        VJP spans the bias add and the join) or runs a layer-0
        weight-gradient dot only to hand the gradient over
        (``deeprest_train_bare_weight_grad_dots``: none where
        ``apply_gradients`` orders the update by leaf)."""
        from deeprest_tpu.obs import profiler

        # another program's kernels, collectives and draws say nothing of
        # this one's
        for gauge in (self._m_kernel_operand_bytes, self._m_collective_bytes,
                      self._m_dropout_draws):
            gauge.clear()
        self._published_program = self._staged_program
        self._m_accumulation.set(self.config.train.grad_accum_windows,
                                 kind="microbatches")
        self._m_accumulation.set(self._accum_carry_bytes(state, x_base),
                                 kind="carry_bytes")
        if not hasattr(self._dispatched[0], "lower"):
            # a caller's stand-in round the jitted program (the
            # benchmark's rehearsals wrap `_superstep` in a plain
            # function): there is no executable of it to read
            return
        compiled = self._dispatched_executable(state)
        mem = compiled.memory_analysis()
        if mem is not None:
            for kind, n in (("arguments", mem.argument_size_in_bytes),
                            ("outputs", mem.output_size_in_bytes),
                            ("aliased", mem.alias_size_in_bytes),
                            ("temporaries", mem.temp_size_in_bytes),
                            ("code", mem.generated_code_size_in_bytes)):
                self._m_program_bytes.set(n, kind=kind)
        text = profiler.instruction_lines(compiled.as_text())
        for kernel, spaces in profiler.kernel_operand_spaces(
                text, scopes.KERNELS).items():
            for space, n in spaces.items():
                self._m_kernel_operand_bytes.set(n, kernel=kernel,
                                                 space=space)
        for op, n in profiler.collective_bytes(text, steps).items():
            self._m_collective_bytes.set(n, op=op)
        draws = profiler.threefry_draws(text, scopes.DROPOUT)
        if draws:
            self._m_dropout_draws.set(len(draws))
        self._m_time_reversals.set(
            len(profiler.time_reversals(text, scopes.RECURRENCE)))
        self._m_kernel_edge_passes.set(len(profiler.kernel_edge_passes(
            text, scopes.RECURRENCE, scopes.GRU_KERNEL_BWD)))
        # what a step differentiates with respect to: the w_ih leaves, on
        # a compact base the table's rows of them
        live = live_cols_of(x_base)
        self._m_bare_weight_grad_dots.set(len(profiler.bare_weight_grad_dots(
            text, scopes.IN_PROJ,
            [a.shape if live is None else (a.shape[0], *live.shape, a.shape[2])
             for a in w_ih_leaves(state.params)])))

    def _accum_carry_bytes(self, state, x_base) -> int:
        """The bytes of the gradient accumulator an update of the superstep
        carries across its microbatches: one array a leaf of the params the
        scan carries, which on a compact base are the table's rows of the
        w_ih leaves (:func:`take_w_ih`) and no ``[E, F, 3H]`` array.
        Nothing is carried with one microbatch an update."""
        if self.config.train.grad_accum_windows == 1:
            return 0
        params, live = state.params, live_cols_of(x_base)
        if live is not None and w_ih_leaves(params):
            params = jax.eval_shape(
                lambda tree: take_w_ih(tree, live, self.mesh), params)
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))

    def _publish_optimizer_rows(self, x_base, stale=None, steps=0) -> None:
        """The epoch's ``deeprest_train_optimizer_rows``, for a staged
        sparse corpus, once an epoch and so of the program that epoch
        dispatched.  ``stale`` is :func:`stale_rows` of the state the
        epoch began with on the base's table (a device scalar, read here,
        after the epoch; the superstep keeps it as it finds it), ``steps``
        the optimizer updates of a dispatch (its steps, or its groups of
        microbatches under accumulation: the table is read the same way
        for both).  With no stale row the step is Adam on
        the table's rows: ``updated`` and ``visited`` are its width.  With
        one, it is Adam over all F (``updated``), of which the dispatches
        wrote :func:`rows_visited` (``visited``) in
        :func:`off_table_trips` trips of the off-table pass (``trips``),
        row by row up to :func:`off_table_bound` (``bound``).  ``stale``
        None where no table was consulted (the per-step path, a base in
        its dense form): every step ran over and wrote
        all F rows; on a base in its dense form, which HAS no table,
        ``stale``, ``bound`` and ``trips`` are set to 0 (a life that
        outgrew the compact form would else keep its last table's), on
        the per-step path over a compact base they are left as
        they were.  ``per_chip``: the rows whose Adam one chip ran a step,
        the carried rows' share of the table under a ``data`` axis
        (parallel/sharding.py), else ``updated``; beside it, where they are
        split, the pieces a direction's folded weight is gathered in."""
        if not isinstance(x_base, SparseBase):
            return
        updated = visited = x_base.capacity
        split = 1
        if stale is not None:
            self._m_readbacks.inc(sink="optimizer_rows")
            # graftlint: disable=JX003 -- designed sink: one scalar an epoch, dispatched before its first chunk and read after its last
            stale = int(stale)
            shapes = x_base.capacity, x_base.width, steps
            visited = rows_visited(*shapes, stale)
            if not stale:
                updated = x_base.width
            split = carried_rows_split(self.mesh, x_base.width)
            self._m_optimizer_rows.set(stale, kind="stale")
            self._m_optimizer_rows.set(off_table_bound(*shapes),
                                       kind="bound")
            self._m_optimizer_rows.set(off_table_trips(*shapes, stale),
                                       kind="trips")
        elif live_cols_of(x_base) is None:
            for kind in ("stale", "bound", "trips"):
                self._m_optimizer_rows.set(0, kind=kind)
        self._m_optimizer_rows.set(updated, kind="updated")
        self._m_optimizer_rows.set(
            x_base.width // split if split > 1 else updated, kind="per_chip")
        if split > 1:
            model = self.model_config
            self._m_gather_pieces.set(gather_pieces(
                self.mesh, jax.ShapeDtypeStruct(
                    (model.num_metrics, x_base.width, 3 * model.hidden_size),
                    jnp.dtype(model.compute_dtype))))
        self._m_optimizer_rows.set(visited, kind="visited")
        self._m_optimizer_rows.set(x_base.capacity, kind="total")

    # -- preemption-safe snapshots (ROADMAP item 7, dynamic half) ------

    def enable_snapshots(self, directory: str, every_steps: int,
                         extra_fn=None) -> None:
        """Periodic preemption-safe snapshots: every ``every_steps`` REAL
        train steps (the superstep path fires at the first chunk boundary
        at or past the cadence — its state only exists at boundaries) the
        full TrainState checkpoints atomically (``deeprest-sharded-v1``,
        tmp+fsync+rename) together with the epoch-plan cursor: epoch
        index, steps completed within the epoch, the shuffle rng's
        bit-generator state at epoch start, and the global step.
        :meth:`resume_training` restarts from the newest cursor — onto
        whatever mesh the restarted process has — and is bit-identical
        to the uninterrupted run at the same step (tests/test_chaos.py).

        ``extra_fn`` (optional) supplies extra sidecar keys per snapshot
        (the streaming trainer rides its refresh counter, stats union,
        and retained-ring watermarks here, so a mid-refresh snapshot is
        a complete stream-resume point too).
        """
        if every_steps < 1:
            raise ValueError(
                f"enable_snapshots(every_steps={every_steps}): must be "
                ">= 1 (leave snapshots unconfigured to disable)")
        self._snapshot_dir = directory
        self._snapshot_every = int(every_steps)
        self._snapshot_extra_fn = extra_fn
        self._steps_since_snapshot = 0

    def _begin_epoch_cursor(self, epoch: int,
                            data_rng: np.random.Generator) -> None:
        """Pin the cursor base for one epoch: the epoch index and the rng
        state BEFORE the epoch plan consumes its permutation, so a resume
        regenerates the identical shuffle and skips into it."""
        import copy

        self._cursor_epoch = epoch
        self._cursor_rng_state = copy.deepcopy(data_rng.bit_generator.state)
        self._epoch_steps_done = 0

    def _note_steps(self, state: TrainState, bundle: DatasetBundle,
                    n: int, on_step=None) -> None:
        """Advance the epoch cursor by ``n`` real steps; write a snapshot
        when the cadence is due (never at the epoch's final step — the
        epoch-end snapshot, whose cursor already points at the next
        epoch, covers that boundary without a redundant save)."""
        self._epoch_steps_done += n
        if self._snapshot_every:
            self._steps_since_snapshot += n
            if (self._steps_since_snapshot >= self._snapshot_every
                    and self._epoch_steps_done < self._epoch_num_steps):
                self.snapshot(state, bundle)
        if on_step is not None:
            on_step(self._global_step)

    def snapshot(self, state: TrainState, bundle: DatasetBundle) -> str:
        """One atomic cursor snapshot (see :meth:`enable_snapshots`)."""
        if self._snapshot_dir is None:
            raise RuntimeError("snapshots not enabled (enable_snapshots)")
        extra = dict(self._snapshot_extra_fn()) \
            if self._snapshot_extra_fn is not None else {}
        extra["train_cursor"] = {
            "epoch": self._cursor_epoch,
            "steps_done": int(self._epoch_steps_done),
            "rng_state": self._cursor_rng_state,
            "global_step": int(self._global_step),
        }
        self._steps_since_snapshot = 0
        path = self.save(self._snapshot_dir, state, bundle,
                         extra_host_state=extra)
        self._snapshots_written += 1
        # Retention GC AFTER the durable save: only cursor snapshots are
        # candidates and the newest `snapshot_keep` always survive, so
        # the restore target of any concurrent resume/remesh is never
        # pruned (train/checkpoint.prune_cursor_snapshots).
        keep = self.config.train.snapshot_keep
        if keep:
            from deeprest_tpu.train.checkpoint import prune_cursor_snapshots

            prune_cursor_snapshots(self._snapshot_dir, keep)
        return path

    # -- elastic remeshing (ROADMAP item 7, the last training gap) -----

    def install_fault_injector(self, injector: FaultInjector) -> None:
        """Arm the deterministic synthetic device-loss injector (CPU
        testability for the whole detect→rebuild→restore→resume path;
        on hardware the detect signal is the real ``XlaRuntimeError``
        and no injector is installed)."""
        self._fault_injector = injector

    def _fault_check(self, n: int) -> None:
        """Probe the injector right after a train dispatch covering the
        next ``n`` global steps — before any cursor/snapshot/logging
        bookkeeping, so a raised loss rolls back to the newest durable
        snapshot exactly like a dispatch that failed on hardware."""
        if self._fault_injector is not None:
            self._fault_injector.note_steps(self._global_step, n)

    @property
    def remesh_in_flight(self) -> bool:
        """True while the fault barrier is rebuilding/restoring — the
        streaming trainer defers refresh decisions (never drops them)
        while this holds."""
        return self._remesh_in_flight

    def remesh(self, attempt: int = 1, reason: str = "") -> int:
        """The DETECT + REBUILD legs: re-enumerate healthy devices,
        shrink the mesh (data axis first, expert/model preserved —
        :func:`parallel.mesh.shrink_mesh_config`), and swap
        ``self.mesh`` in place.  Every jitted program re-derives its
        shardings from the one rule table at the first new-mesh trace,
        so the jit caches stay at one executable per program per
        DISTINCT mesh shape — old-shape executables remain cached, new
        shapes compile once.  Returns the healthy-device count; raises
        :class:`NoValidMeshError` (typed, counted) when fewer than
        ``expert * model`` devices survive."""
        import time

        with obs_spans.RECORDER.span("elastic.detect",
                                     component="deeprest-elastic") as sp:
            devices = list(self.mesh.devices.flat)
            if self._fault_injector is not None:
                healthy = self._fault_injector.healthy(devices)
            else:
                healthy = enumerate_healthy(devices)
            sp.tag(attempt=attempt, reason=reason[:200],
                   devices=len(devices), healthy=len(healthy))
        backoff_s = self.config.train.remesh_backoff_ms / 1e3 * attempt
        if backoff_s:
            time.sleep(backoff_s)
        with obs_spans.RECORDER.span("elastic.rebuild",
                                     component="deeprest-elastic") as sp:
            try:
                cfg = shrink_mesh_config(mesh_config_of(self.mesh),
                                         len(healthy))
            except NoValidMeshError:
                self._m_remeshes.inc(outcome="no_valid_mesh")
                raise
            self.mesh = make_mesh(cfg, devices=healthy)
            # Shardings re-derive from the one rule table at the first
            # new-mesh trace; the wrappers must be rebuilt because a
            # cached jit pins its device set (dispatching new-mesh
            # arguments into an old-mesh wrapper raises, it does not
            # retrace).  One program set per live mesh shape.
            self._build_programs()
            sp.tag(mesh=f"{cfg.data}x{cfg.expert}x{cfg.model}")
        return len(healthy)

    def _handle_device_loss(self, bundle: DatasetBundle, directory: str,
                            attempt: int, reason: str):
        """The remesh handler the fault barrier routes every caught
        device loss to: rebuild the mesh over the survivors, restore the
        newest fsync'd cursor snapshot IN-PROCESS through the cross-mesh
        assembly, and hand back the exact resume coordinates
        ``resume_training`` would compute in a fresh process — the
        post-remesh trajectory is the restart-resume trajectory, bit for
        bit (tests/test_chaos.py pins it).

        Returns ``(state, data_rng, start_epoch, skip_steps)``.
        """
        from deeprest_tpu.train.checkpoint import (
            latest_cursor_step, restore_checkpoint,
        )

        sw = obs_metrics.Stopwatch()
        self._remesh_in_flight = True
        try:
            self._m_device_losses.inc()
            self.remesh(attempt=attempt, reason=reason)
            with obs_spans.RECORDER.span(
                    "elastic.restore", component="deeprest-elastic") as sp:
                step = latest_cursor_step(directory)
                template = self.init_state(self.sample_input(bundle))
                if step is None:
                    # Lost before the first durable snapshot: nothing to
                    # restore — re-init on the new mesh, exactly what a
                    # restarted process would be forced to do.
                    state = template
                    data_rng = np.random.default_rng(self.config.train.seed)
                    start_epoch = skip_steps = 0
                    self._global_step = 0
                else:
                    state, extra = restore_checkpoint(directory, template,
                                                      step=step)
                    cursor = extra["train_cursor"]
                    self._global_step = int(cursor["global_step"])
                    data_rng = np.random.default_rng(self.config.train.seed)
                    data_rng.bit_generator.state = cursor["rng_state"]
                    start_epoch = int(cursor["epoch"])
                    skip_steps = int(cursor["steps_done"])
                sp.tag(restored_step=step, epoch=start_epoch,
                       skip_steps=skip_steps)
            self._steps_since_snapshot = 0
            recovery_s = sw.elapsed()
            self.remesh_count += 1
            self.last_remesh = {
                "attempt": attempt,
                "restored_step": step,
                "mesh": {a: int(self.mesh.shape[a])
                         for a in ("data", "expert", "model")},
                "recovery_s": recovery_s,
            }
            self.remesh_history.append(self.last_remesh)
            self._m_recovery.set(recovery_s)
            self._m_remeshes.inc(outcome="ok")
            with obs_spans.RECORDER.span(
                    "elastic.resume", component="deeprest-elastic") as sp:
                # The resume leg proper is the re-entered epoch driver
                # (re-stage + first new-shape compile); this span marks
                # the handoff so the recovery trace is complete.
                sp.tag(global_step=self._global_step,
                       recovery_s=round(recovery_s, 4))
            return state, data_rng, start_epoch, skip_steps
        finally:
            self._remesh_in_flight = False

    def _run_epochs_elastic(self, bundle, state, data_rng, start_epoch,
                            skip_steps, baseline_preds, on_epoch,
                            num_epochs, on_step, profile_dir=None):
        """THE fault barrier (the only sanctioned swallow point for the
        device-loss family — graftlint EX004 keeps it that way): run the
        epochs; on device loss, remesh + restore in-process and
        continue, bounded by ``remesh_max_attempts`` with per-attempt
        backoff."""
        cfg = self.config.train
        directory = self._snapshot_dir or cfg.checkpoint_dir
        if not directory or not cfg.snapshot_every_steps:
            raise ValueError(
                "TrainConfig.elastic=True requires cursor snapshots: set "
                "checkpoint_dir and snapshot_every_steps >= 1 (the "
                "remesh barrier restores from the newest one)")
        attempts = 0
        while True:
            reason = None
            try:
                return self._run_epochs(bundle, state, data_rng,
                                        start_epoch, skip_steps,
                                        baseline_preds, on_epoch,
                                        num_epochs, on_step, profile_dir)
            except Exception as exc:
                if not is_device_loss(exc):
                    raise
                attempts += 1
                if attempts > cfg.remesh_max_attempts:
                    self._m_remeshes.inc(outcome="exhausted")
                    raise RemeshExhaustedError(
                        f"device loss #{attempts} exceeds "
                        f"remesh_max_attempts={cfg.remesh_max_attempts}; "
                        "surfacing the failure instead of respinning"
                    ) from exc
                reason = f"{type(exc).__name__}: {exc}"
            # Recovery runs OUTSIDE the except block: the exception's
            # traceback pins the failed epoch driver's frame (its staged
            # feed and old-mesh state) alive; leaving the handler first
            # releases those buffers before the rebuild re-stages.
            state = None
            state, data_rng, start_epoch, skip_steps = \
                self._handle_device_loss(bundle, directory, attempts,
                                         reason)

    # ------------------------------------------------------------------

    def sample_input(self, bundle: DatasetBundle) -> np.ndarray:
        """A ``[1, W, F]`` init sample for ``init_state``.  Flax parameter
        initialization depends on shapes and the init rng, never on the
        sample's values, so sparse bundles (no dense windows) use zeros —
        identical params to a dense-bundle init of the same shape."""
        if bundle.x_train is not None:
            return bundle.x_train[:1]
        return np.zeros((1, bundle.window_size, bundle.feature_dim),
                        np.float32)

    def init_state(self, sample_x: np.ndarray, seed: int | None = None) -> TrainState:
        """Initialize (and shard) params + optimizer state.

        One span ``deeprest-trainer/train.init_state`` with a child for
        each of :data:`INIT_PHASES`, their seconds (timed as an epoch's
        phases are) in ``deeprest_train_init_state_seconds{phase}``; the
        set-up phase ``init_state`` for what it compiles; closed by one
        wait on the state, which the first step would wait for anyway;
        device memory as it returns in
        ``deeprest_train_device_bytes{at="init_state"}``."""
        seed = self.config.train.seed if seed is None else seed
        with obs_setup.phase("init_state"), \
                self._init_clock.unit() as phase:
            with phase("model_init"):
                rng = jax.random.PRNGKey(seed)
                init_rng, train_rng = jax.random.split(rng)
                variables = self.model.init(init_rng,
                                            jnp.asarray(sample_x[:1]))
            with phase("shard"):
                params = shard_params(self.mesh, dict(variables["params"]))
            with phase("opt_init"):
                opt_state = jax.jit(self.tx.init)(params)
            # Pinned through the same jitted constraint the train step
            # applies to its output, so the first step's input signature
            # equals every later step's — one executable, bit-stable
            # numerics (see pin_state in __init__).
            with phase("pin"):
                with self._first_dispatch(self._pin_state):
                    state = self._pin_state(TrainState(
                        step=jnp.zeros((), jnp.int32), params=params,
                        opt_state=opt_state, rng=train_rng,
                    ))
                jax.block_until_ready(state)
        self._publish_device_bytes("init_state")
        return state

    # ------------------------------------------------------------------

    def _batches(self, n: int, rng: np.random.Generator):
        """Shuffled index batches, trailing batch padded to full size with
        zero-weight duplicates (static shapes → single compilation)."""
        bs = self.config.train.batch_size
        order = rng.permutation(n)
        for lo in range(0, n, bs):
            sel = order[lo:lo + bs]
            weight = np.ones(bs, np.float32)
            if len(sel) < bs:
                weight[len(sel):] = 0.0
                # wrap-pad (resize repeats `order` as needed, so corpora
                # smaller than the batch size still yield full batches)
                sel = np.concatenate([sel, np.resize(order, bs - len(sel))])
            yield sel, weight

    # Per-chunk plan-slice byte cap for steps_per_superstep="auto": at
    # 8 bytes/step/sample (int32 start + f32 weight) this only binds for
    # pathologically long log intervals; it keeps the sliced [S, B] feed
    # buffers (and the per-superstep loss readback) comfortably small.
    _PLAN_CHUNK_MAX_BYTES = 1 << 20

    def _superstep_len(self, num_steps: int) -> int:
        """Resolve ``steps_per_superstep`` to a concrete S for this epoch.

        ``"epoch"`` fuses the whole epoch into one dispatch; ``"auto"``
        balances dispatch amortization against logging granularity
        (log boundaries are reported at most one superstep late) and the
        plan-chunk byte cap.  Ints clamp to the epoch length so a single
        ragged chunk never pads beyond one epoch.
        """
        v = self.config.train.steps_per_superstep
        if v == "epoch":
            s = num_steps
        elif v == "auto":
            log_every = self.config.train.log_every_steps
            s = min(num_steps, log_every if log_every else 32)
        else:
            s = min(int(v), num_steps)
        cap = max(1, self._PLAN_CHUNK_MAX_BYTES
                  // (8 * self.config.train.batch_size))
        s = max(1, min(s, cap))
        g = self.config.train.grad_accum_windows
        if g > 1:
            # Coalesced updates consume G microbatches at a time: round S
            # UP to a multiple of G (the plan's zero-weight padding makes
            # any overhang a cond-skipped group, exactly like ragged
            # chunks at G=1).
            s = -(-s // g) * g
        return s

    def _epoch_plan(self, n: int, rng: np.random.Generator,
                    s: int) -> tuple[np.ndarray, np.ndarray, int]:
        """The epoch's full shuffled batch plan, superstep-chunked.

        Returns ``(starts [C, S, B] int32, weights [C, S, B] float32,
        num_steps)`` where ``num_steps = ceil(n / B)`` is the count of
        REAL steps; the trailing chunk is padded to S with zero-weight
        steps (starts 0 — in-bounds for the gather, skipped by the
        superstep's ``lax.cond`` pass-through branch).  Consumes exactly
        one
        ``rng.permutation`` like the per-step loop, so the two paths see
        identical shuffles from a shared rng stream.
        """
        bs = self.config.train.batch_size
        batches = list(self._batches(n, rng))
        num_steps = len(batches)
        n_chunks = -(-num_steps // s)
        starts = np.zeros((n_chunks * s, bs), np.int32)
        weights = np.zeros((n_chunks * s, bs), np.float32)
        for i, (sel, w) in enumerate(batches):
            starts[i] = sel
            weights[i] = w
        return (starts.reshape(n_chunks, s, bs),
                weights.reshape(n_chunks, s, bs), num_steps)

    def stage_dataset(self, bundle: DatasetBundle):
        """Ship the normalized base series to HBM for index-gather feeding.

        Returns ``(x_base, y_base)`` device arrays (replicated over the
        mesh) or None when staging is off, the bundle predates base-series
        capture, or the series exceed ``device_data_max_bytes`` ("auto").
        For bf16 models ``x_base`` stages in bf16 — the model casts inputs
        there anyway, and it halves both HBM residency and the one-time
        transfer (885 MB for a month at F=10240).

        A trainer stages again whenever its corpus moves (every refresh of
        train/stream.py, a resumed run on another week): each call is one
        span ``deeprest-trainer/train.stage``, tagged ``restage`` (this
        trainer has staged before), ``nth`` (which staging of this
        trainer's life it is, from 1; ``deeprest_train_stagings_total``
        counts the process's), ``width`` (the compact table's, else
        the columns staged, 0 for nothing), for a sparse corpus what the
        rule of the compact form decided (``form`` ``compact`` or
        ``dense``, the ``live`` set's size, the ``padded`` width it
        weighed and the ``bound`` it held it to, ``model_axis`` where the
        mesh's ``model`` axis decided) and, from one sparse staging to the
        next, the rows that ``left`` and ``entered`` the table (across a
        change of form too: against the dense form, which contracts over
        every column, nothing leaves) — the rows that
        left are the ones the carried moments make stale
        (:func:`stale_rows`); ``program`` says what the call decided of the
        programs that read the staged base (``_staged_program``: the form
        and the width), ONCE A PROGRAM: ``new`` (this build of the
        programs has not dispatched on it: the next dispatch is a first
        dispatch, :meth:`_first_dispatch`), ``same`` (the last staging's)
        or ``back`` (an earlier one's: no first dispatch); its host seconds are the gauge
        ``deeprest_train_last_stage_seconds``, and device memory as it
        returns ``deeprest_train_device_bytes{at="stage"}``; what it
        compiles is counted in the set-up phase ``stage``.
        """
        clock = obs_metrics.Stopwatch()
        with obs_spans.RECORDER.span("train.stage",
                                     "deeprest-trainer") as span, \
                obs_setup.phase("stage"):
            before, self._staged_table = self._staged_table, None
            was_sparse, was = bool(self._staged_form), self._staged_program
            self._staged_form = {}
            staged = self._stage(bundle)
            table = self._staged_table
            width = (len(table) if table is not None else
                     0 if staged is None else bundle.feature_dim)
            self._staged_program = (
                self._staged_form.get("form", None if staged is None
                                      else "base"), width)
            tags = {"restage": self._stagings > 0,
                    "nth": self._stagings + 1, "width": width,
                    **self._staged_form,
                    "program": (
                        "same" if self._stagings and was == self._staged_program
                        else "back" if any(on == self._staged_program
                                           for _, on in self._dispatched_once)
                        else "new")}
            if was_sparse and self._staged_form:
                # a staging in the dense form holds every column
                every = np.arange(int(bundle.sparse_capacity
                                      or bundle.feature_dim))
                held, holds = (every if t is None else t
                               for t in (before, table))
                tags["left"] = int(np.setdiff1d(held, holds).size)
                tags["entered"] = int(np.setdiff1d(holds, held).size)
            span.tag(**tags)
        self.last_stage = tags
        self._stagings += 1
        self._m_stagings.inc()
        self._m_stage_seconds.set(clock.elapsed())
        self._publish_device_bytes("stage")
        return staged

    def _stage(self, bundle: DatasetBundle):
        cfg = self.config.train
        if cfg.device_data not in ("auto", "always", "off"):
            raise ValueError(
                f"TrainConfig.device_data={cfg.device_data!r}: must be "
                f"'auto', 'always', or 'off' (an unknown value silently "
                f"skipping the byte budget could OOM the chip)")
        if cfg.sparse_feed and bundle.is_sparse:
            return self._stage_sparse(bundle)
        if bundle.x_base is None and bundle.is_sparse:
            # A sparse-only bundle (streaming 10k tier) has no dense base
            # or windows to fall back to; reaching here means sparse_feed
            # was turned off against a sparse corpus.
            raise ValueError(
                "bundle carries only sparse (padded-COO) traffic but "
                "TrainConfig.sparse_feed is off; enable sparse_feed or "
                "rebuild the bundle with dense traffic")
        if (cfg.device_data == "off" or bundle.x_base is None
                or bundle.y_base is None):
            return None
        if cfg.device_data == "auto" and jax.default_backend() == "cpu":
            # Staging buys nothing on CPU (the "transfer" is a memcpy) and
            # XLA's CPU gather lowers to scalar loops — the staged feed
            # measured ~3× SLOWER than host streaming on the month-scale
            # CPU dossier.  "always" forces it (tests, virtual meshes).
            return None
        x = np.asarray(bundle.x_base)
        bf16 = jnp.dtype(self.model_config.compute_dtype) == jnp.bfloat16
        # Budget check BEFORE the cast: the over-budget case is exactly the
        # multi-GB corpus where a host-side bf16 copy would hurt most.
        staged_x_bytes = x.size * 2 if bf16 else x.nbytes
        total = staged_x_bytes + bundle.y_base.nbytes
        if cfg.device_data == "auto" and total > cfg.device_data_max_bytes:
            return None
        if bf16:
            import ml_dtypes

            x = x.astype(ml_dtypes.bfloat16)
        return (feed_replicated(self.mesh, x),
                feed_replicated(self.mesh, np.asarray(bundle.y_base)))

    def _stage_sparse(self, bundle: DatasetBundle):
        """Stage the padded-COO traffic base + its normalization stats.

        The sparse twin of the dense staging: RAW ``cols``/``vals`` rows
        ship once (~F/(2K) fewer bytes than the dense base at 10k width)
        and every step's gather densifies + normalizes on device
        (ops/densify.py — stats ride as runtime arguments so XLA cannot
        strength-reduce the divide; bit parity with the host-normalized
        dense path is pinned by tests/test_sparse.py).  Unlike the dense
        "auto" rule this stages on the CPU backend too: the sparse feed
        IS the staged feed — there is no host-windowed fallback to
        prefer.

        The form is chosen here, from the corpus and the mesh alone: the
        compact form (ops/densify.py: windows and the layer-0 contraction
        over the live call paths only) when the padded live set is at
        most half of F (``compact_rule``, whose docstring names the chip
        readings behind the bound) and the mesh's ``model`` axis, which
        shards F, is 1; the dense form otherwise."""
        cfg = self.config.train
        if bundle.y_base is None:
            raise ValueError("sparse bundle lacks y_base; the targets "
                             "stay dense and must be stageable")
        total = (bundle.x_cols.nbytes + bundle.x_vals.nbytes
                 + bundle.y_base.nbytes)
        if cfg.device_data == "auto" and total > cfg.device_data_max_bytes:
            raise ValueError(
                f"sparse base ({total} bytes) exceeds "
                f"device_data_max_bytes ({cfg.device_data_max_bytes}); "
                "there is no host-feed fallback for the sparse form — "
                "raise the budget or shrink history_max/nnz_cap")
        x_stats = bundle.x_stats
        mn = np.asarray(x_stats.min, np.float32).reshape(-1)
        rg = np.asarray(x_stats.range, np.float32).reshape(-1)
        cols = np.ascontiguousarray(bundle.x_cols, dtype=np.int32)
        vals = np.ascontiguousarray(bundle.x_vals, dtype=np.float32)
        capacity = int(bundle.sparse_capacity or bundle.feature_dim)
        live = live_columns(cols, vals, mn, rg, capacity)
        padded, bound = compact_rule(len(live), capacity)
        sharded = self.mesh.shape["model"] != 1
        table = None if sharded else compact_table(live, capacity)
        self._staged_table = table
        self._staged_form = {
            "form": "dense" if table is None else "compact",
            "live": len(live), "padded": padded,
            "bound": "model_axis" if sharded else bound}
        contracted = capacity if table is None else len(table)
        for kind, n in (("live", len(live)), ("padded", padded),
                        ("bound", 0 if sharded else bound),
                        ("contracted", contracted), ("total", capacity)):
            self._m_projection_columns.set(n, kind=kind)
        base = stage_sparse_base(self.mesh, cols, vals, mn, rg, capacity,
                                 live=table)
        return base, feed_replicated(self.mesh, np.asarray(bundle.y_base))

    def train_epoch(self, state: TrainState, bundle: DatasetBundle,
                    epoch_rng: np.random.Generator,
                    staged=None, skip_steps: int = 0,
                    on_step=None) -> tuple[TrainState, float]:
        """One epoch.  ``skip_steps`` (resume) fast-forwards past the
        first N REAL steps of the epoch's plan WITHOUT running them — the
        plan rng is still consumed identically, so the remaining steps
        see exactly the batches an uninterrupted run would have; the
        returned epoch-mean loss then covers only the executed remainder
        (the resumed epoch's mean is not comparable to the uninterrupted
        one — state parity is, and is what tests/test_chaos.py pins).
        ``on_step(global_step)`` fires at every real-step (superstep:
        chunk) boundary — the chaos tests' preemption injection point.

        The epoch's host work is timed by phase (:data:`EPOCH_PHASES`,
        obs/phases.py): one ``train.epoch`` span, tagged with the mesh
        ``DxExM`` and ``accum`` (the microbatches an optimizer update,
        ``grad_accum_windows``), with a child per phase when the recorder
        is on, the phase-seconds counters always.  What compiles in it is counted in
        the set-up phase ``epoch`` (after the first epoch: a recompile; the
        first dispatch on a new staged program opens ``first_dispatch``
        inside it);
        device memory after the first one to finish is
        ``deeprest_train_device_bytes{at="first_epoch"}``."""
        with obs_setup.phase("epoch"), \
                self._epoch_clock.unit({
                    "mesh": self._mesh_tag,
                    "accum": self.config.train.grad_accum_windows}) as phase:
            out = self._train_epoch(state, bundle, epoch_rng, staged,
                                    skip_steps, on_step, phase)
        if not self._epoch_finished:
            # its state resident, its temporaries freed
            self._publish_device_bytes("first_epoch")
            self._epoch_finished = True
        return out

    def _train_epoch(self, state, bundle, epoch_rng, staged, skip_steps,
                     on_step, phase) -> tuple[TrainState, float]:
        accum = self.config.train.grad_accum_windows
        if staged is None and bundle.is_sparse:
            raise ValueError(
                "sparse (padded-COO) bundles train only through the "
                "staged device-resident feed — the on-device densify "
                "lives inside the staged executables; call "
                "stage_dataset(bundle) with TrainConfig.sparse_feed=True")
        if staged is None and accum > 1:
            raise ValueError(
                f"grad_accum_windows={accum} requires the staged "
                "(device-resident) feed — the accumulated update consumes "
                "its microbatches from the on-device plan; stage the "
                "dataset (device_data='always' forces it on the CPU "
                "backend) or set grad_accum_windows=1")
        if staged is not None:
            num_steps = -(-bundle.num_train_windows
                          // self.config.train.batch_size)
            s = self._superstep_len(num_steps)
            if s > 1:
                return self._train_epoch_superstep(state, bundle, epoch_rng,
                                                   staged, s, phase,
                                                   skip_steps=skip_steps,
                                                   on_step=on_step)
        self._epoch_num_steps = -(-bundle.num_train_windows
                                  // self.config.train.batch_size)
        self._epoch_steps_done = skip_steps
        if skip_steps >= self._epoch_num_steps:
            raise ValueError(
                f"skip_steps={skip_steps} >= epoch length "
                f"{self._epoch_num_steps}: a finished epoch resumes at "
                "the NEXT epoch's cursor, never by skipping a whole plan")
        log_every = self.config.train.log_every_steps
        losses = []
        steps = 0
        if staged is None:
            def host_batches():
                # feed_global_batch (inside prefetch): sharded device_put on
                # one host; on a pod, each process ships only its
                # process_batch_slice of the (identical, rng-deterministic)
                # global selection.  Resume: the first skip_steps batches
                # of the (identical) shuffle are discarded host-side —
                # never staged, never run.
                for i, (sel, weight) in enumerate(self._batches(
                        bundle.num_train_windows, epoch_rng)):
                    if i < skip_steps:
                        continue
                    yield bundle.x_train[sel], bundle.y_train[sel], weight

            batches = prefetch_to_device(self.mesh, host_batches(),
                                         depth=self.config.train.prefetch_depth)
            program, fixed = self._train_step, ()
        else:
            x_base, y_base = staged

            def index_batches():
                # Train window i starts at base row i (stride-1 windows),
                # so the shuffled selection IS the start-index batch.
                # Prefetch (feed_global_batch's default axes shard the
                # leading axis over "data", same as the old explicit feed)
                # keeps the [B] start/weight copies of step t+1 in flight
                # behind the step on batch t — the superstep-disabled
                # fallback overlaps transfer with compute too.
                for i, (sel, weight) in enumerate(self._batches(
                        bundle.num_train_windows, epoch_rng)):
                    if i < skip_steps:
                        continue
                    yield sel.astype(np.int32), weight

            batches = prefetch_to_device(self.mesh, index_batches(),
                                         depth=self.config.train.prefetch_depth)
            program, fixed = self._train_step_indexed, (x_base, y_base)

        # The program's first step on this staging's program pays jit
        # trace+compile: it is kept out of the throughput window, so
        # steps/sec reflects steady state.
        measuring = self._dispatched_before(program)
        if measuring:
            self.throughput.start()
        # This driver feeds as it dispatches (the prefetch generator
        # builds and ships each batch), so the whole loop is `dispatch`
        # and `plan_build`/`plan_h2d` stay 0.
        with phase("dispatch"), contextlib.ExitStack() as first:
            if not measuring:
                first.enter_context(self._first_dispatch(program))
            for batch in batches:
                state, loss = program(state, *fixed, *batch)
                # Fault barrier probe BEFORE any bookkeeping: a device lost
                # during this dispatch means the step never happened — the
                # cursor must not advance past it and no snapshot may
                # include it (the barrier restores the newest durable one).
                self._fault_check(1)
                losses.append(loss)
                self._global_step += 1
                if not measuring:
                    jax.block_until_ready(loss)
                    first.close()
                    self.throughput.start()
                    measuring = True
                else:
                    steps += 1
                if log_every and self._global_step % log_every == 0:
                    self._m_readbacks.inc(sink="log_boundary")
                    with phase("log_readback"):
                        # graftlint: disable=JX003 -- designed sink: one scalar readback per log_every steps, the logging contract
                        value = float(loss)
                    print(f"step {self._global_step}: loss {value:.6f}")
                self._note_steps(state, bundle, 1, on_step)
        # what this epoch dispatched, for profile_epoch to lower again
        self._dispatched = (program, (*fixed, *batch))
        with phase("device_wait"):
            jax.block_until_ready(state.params)
        if measuring:
            self.throughput.stop(steps)
        self._m_updates.inc(len(losses))         # a step is an update here
        if staged is not None:
            self._publish_optimizer_rows(staged[0])
        # One stacked host readback for the epoch mean instead of a
        # device round-trip per element; f64 accumulation over the f32
        # per-step values reproduces the historical list-of-floats mean
        # bit-for-bit.
        self._m_readbacks.inc(sink="epoch_losses")
        with phase("loss_readback"):
            epoch_losses = np.asarray(jnp.stack(losses))
        self._last_epoch_losses = epoch_losses
        return state, float(np.mean(epoch_losses, dtype=np.float64))

    def _train_epoch_superstep(self, state: TrainState, bundle: DatasetBundle,
                               epoch_rng: np.random.Generator, staged,
                               s: int, phase, skip_steps: int = 0,
                               on_step=None) -> tuple[TrainState, float]:
        """Fused epoch driver: ceil(K/S) donated dispatches instead of K.

        The epoch's whole shuffled plan ships to HBM once (stage_plan);
        each dispatch scans S steps on device and returns the [S] per-step
        loss vector — one readback per superstep (and none until the epoch
        mean / a log boundary needs values).  Numerics are bit-identical
        to the per-step indexed loop: same plan rng, same fold_in(rng,
        step) stream, padded steps select the prior state.

        ``skip_steps`` (resume) must land on a superstep boundary — the
        snapshot cadence only ever fires there, so a cursor that does not
        divide is a corrupted sidecar, not a rounding case.  The whole
        plan is still built (one permutation off ``epoch_rng``, identical
        to the uninterrupted epoch) and the first ``skip_steps/s`` chunks
        are never dispatched.
        """
        cfg = self.config.train
        log_every = cfg.log_every_steps
        x_base, y_base = staged
        with phase("plan_build"):
            starts, weights, num_steps = self._epoch_plan(
                bundle.num_train_windows, epoch_rng, s)
        self._epoch_num_steps = num_steps
        self._epoch_steps_done = skip_steps
        if skip_steps >= num_steps:
            raise ValueError(
                f"skip_steps={skip_steps} >= epoch length {num_steps}: a "
                "finished epoch resumes at the NEXT epoch's cursor")
        if skip_steps % s:
            raise ValueError(
                f"resume cursor steps_done={skip_steps} is not a "
                f"superstep boundary (S={s}): snapshots only fire at "
                "chunk boundaries — the sidecar is inconsistent with "
                "this config's steps_per_superstep/grad_accum_windows")
        skip_chunks = skip_steps // s
        with phase("plan_h2d"):
            starts_d, weights_d = stage_plan(self.mesh, starts, weights)
        superstep = self._superstep
        # What the compact superstep's rule reads in each dispatch, as a
        # count, on the state this epoch begins with: dispatched here, read
        # after the epoch for the gauge, waited on nowhere in between.
        stale = None
        if live_cols_of(x_base) is not None and w_ih_leaves(state.params):
            with self._first_dispatch(self._stale_rows):
                stale = self._stale_rows(state.opt_state, x_base.live)
        # A superstep's first dispatch on this staging's program pays the
        # scan's trace+compile (or its load): kept out of the throughput
        # window, in an epoch that restaged onto a new program as in the
        # trainer's first.
        measuring = self._dispatched_before(superstep)
        if measuring:
            self.throughput.start()
        chunk_losses = []
        steps = updates = 0
        accum = cfg.grad_accum_windows
        with phase("dispatch"), contextlib.ExitStack() as first:
            if not measuring:
                first.enter_context(self._first_dispatch(superstep))
            for c in range(skip_chunks, starts.shape[0]):
                real = min(s, num_steps - c * s)
                updates += -(-real // accum)     # a ragged group is one
                state, losses_c = superstep(state, x_base, y_base,
                                            starts_d, weights_d, c)
                # Mid-superstep (and mid-grad-accum-group) device loss: the
                # whole chunk's dispatch is the unit that fails, so the
                # probe sits before ANY of the chunk's bookkeeping —
                # progress since the last durable snapshot is what the
                # barrier rolls back.
                self._fault_check(real)
                chunk_losses.append(losses_c)
                if not measuring:
                    jax.block_until_ready(losses_c)
                    first.close()
                    self.throughput.start()
                    measuring = True
                else:
                    steps += real
                prev = self._global_step
                self._global_step += real
                if log_every and (prev // log_every
                                  != self._global_step // log_every):
                    self._m_readbacks.inc(sink="log_boundary")
                    # The readback blocks until its chunk is done, so it
                    # is a wait, and the next chunk is not dispatched
                    # while it waits.
                    with phase("log_readback"):
                        # graftlint: disable=JX003 -- designed sink: one [S] readback per superstep, only when a log boundary passed
                        vals = np.asarray(losses_c)   # one readback, ≥1 boundary
                    for gs in range(prev + 1, self._global_step + 1):
                        if gs % log_every == 0:
                            print(f"step {gs}: "
                                  f"loss {vals[gs - prev - 1]:.6f}")
                self._note_steps(state, bundle, real, on_step)
        self._m_dispatches.inc(starts.shape[0] - skip_chunks)
        self._m_updates.inc(updates)
        # what this epoch dispatched, for profile_epoch to lower again
        self._dispatched = (superstep,
                            (x_base, y_base, starts_d, weights_d, 0))
        if self._published_program != self._staged_program:
            # the host reads the executable while the device runs the
            # epoch's last chunk
            self._publish_program(state, s // accum, x_base)
        with phase("device_wait"):
            jax.block_until_ready(state.params)
        if measuring:
            self.throughput.stop(steps)
        # Padding only ever trails the real steps, so clipping the
        # concatenated chunks to the executed real-step count recovers
        # exactly the (remaining) per-step loss curve.
        self._m_readbacks.inc(sink="epoch_losses")
        with phase("loss_readback"):
            epoch_losses = np.asarray(
                jnp.concatenate(chunk_losses))[:num_steps - skip_steps]
            self._publish_optimizer_rows(x_base, stale, s // accum)
        self._last_epoch_losses = epoch_losses
        return state, float(np.mean(epoch_losses, dtype=np.float64))

    def _dispatched_executable(self, state: TrainState):
        """The executable of the program the last epoch dispatched (the
        epoch drivers record it and the arguments beside ``state``).
        ``lower`` on those very arguments finds the trace and the lowering
        the dispatch left in the jit's caches, and ``compile`` the
        executable that ran: nothing is compiled again
        (tests/test_mesh_dp4.py counts the backend's compiles).  Called by
        :meth:`profile_epoch`, and once a program by
        :meth:`_publish_program`."""
        program, args = self._dispatched
        return program.lower(state, *args).compile()

    def _dispatched_program_text(self, state: TrainState) -> str:
        """Its optimized HLO."""
        return self._dispatched_executable(state).as_text()

    def profile_epoch(self, state: TrainState, bundle: DatasetBundle,
                      epoch_rng: np.random.Generator, staged,
                      trace_dir: str, skip_steps: int = 0,
                      on_step=None) -> tuple[TrainState, dict]:
        """:meth:`train_epoch` under a ``jax.profiler`` window (Python
        tracer off) with the span recorder on for its duration, then the
        trace read into the table of obs/profiler.py: the device's time by
        the scopes of the program this epoch dispatched, its idle gaps by
        the epoch's phases, the phases' host seconds, the epoch's mean
        loss (``train_loss``) and what set-up cost this process
        (``setup``: obs/setup.py).  Trace a steady epoch (the program
        compiled, the loss concatenation too): the second, not the
        first."""
        from deeprest_tpu.obs import profiler

        was = obs_spans.RECORDER.enabled
        obs_spans.RECORDER.enabled = True
        try:
            with profiler.trace_window(trace_dir):
                state, loss = self.train_epoch(state, bundle, epoch_rng,
                                               staged=staged,
                                               skip_steps=skip_steps,
                                               on_step=on_step)
        finally:
            obs_spans.RECORDER.enabled = was
        text = self._dispatched_program_text(state)
        names = scopes.STEP_SCOPES + scopes.KERNELS
        table = profiler.layer_table(
            trace_dir, scopes=profiler.scope_table(text, names),
            fused=profiler.fused_scopes(text, names),
            module=profiler.module_name(text),
            steps=len(self._last_epoch_losses))
        table["phases"] = {
            name: self._epoch_clock.last_seconds.value(phase=name)
            for name in EPOCH_PHASES}
        table["setup"] = obs_setup.setup_table()
        table["train_loss"] = loss
        return state, table

    # ------------------------------------------------------------------

    def evaluate(
        self,
        state: TrainState,
        bundle: DatasetBundle,
        baseline_preds: Mapping[str, np.ndarray] | None = None,
        staged=None,
    ) -> tuple[float, dict]:
        """Reference-semantics eval: strided windows, de-normalized MAE.

        ``baseline_preds`` maps method name → *de-normalized* ``[N_test, W, E]``
        predictions aligned with ``bundle.x_test``; errors for those methods
        are computed on the same windows for a comparable report.
        ``staged`` (from :meth:`stage_dataset`) gathers the eval windows
        from the device-resident base series — test window i starts at
        base row ``split + i`` — shipping only start indices per chunk.
        """
        cfg = self.config.train
        if staged is None and bundle.is_sparse:
            raise ValueError(
                "sparse (padded-COO) bundles evaluate only through the "
                "staged device-resident feed (see train_epoch)")
        idx = eval_window_indices(bundle.num_test_windows, cfg.eval_stride,
                                  cfg.eval_max_cycles)
        if len(idx) == 0:
            raise ValueError("no eval windows: test split shorter than stride")
        # Batched, replicated feed (the windows need not divide the data
        # axis, and every process holds the same windows).  One giant batch
        # would OOM at a large ``eval_max_cycles`` on a wide model (the
        # F=10240 flagship at 500 windows), so eval pages through the
        # windows like ``predict`` does; the loss is the window-weighted
        # mean of the per-chunk pinball means.
        bs = cfg.eval_batch_size
        preds_chunks, loss_terms = [], []
        for lo in range(0, len(idx), bs):
            sel = idx[lo:lo + bs]
            if staged is not None:
                starts = feed_replicated(
                    self.mesh, (bundle.split + sel).astype(np.int32))
                p, l = self._eval_step_indexed(state.params, *staged, starts)
            else:
                xb = feed_replicated(self.mesh, bundle.x_test[sel])
                yb = feed_replicated(self.mesh, bundle.y_test[sel])
                p, l = self._eval_step(state.params, xb, yb)
            # graftlint: disable=JX003 -- designed sink: eval pages through windows precisely so only one chunk is device-resident; the loss stays on device (loss_terms)
            preds_chunks.append(np.asarray(gather_to_host(p)))
            # Window-weighted loss accumulates as a DEVICE scalar (f32 even
            # for bf16 models) — no per-chunk float(l) sync; one readback
            # after the paging loop.
            loss_terms.append(l.astype(jnp.float32) * len(sel))
        preds = np.concatenate(preds_chunks, axis=0)
        loss = float(jnp.sum(jnp.stack(loss_terms))) / len(idx)

        # Floor the *normalized* median prediction at 1e-6 before
        # de-normalizing — the reference's clamp order (estimate.py:100-103);
        # flooring after de-normalization gives different MAE for metrics
        # with a large train-split minimum.
        med = self.model.median_index()
        preds_denorm = bundle.denorm_targets(
            np.maximum(np.asarray(preds[..., med]), 1e-6)
        )

        # Delta-trained columns come back as per-bucket increments: report
        # them in LEVEL space — integrate the predictions from each
        # window's first observed level, and swap the labels for the raw
        # level windows (bundle.level_labels / integrate_test_preds, the
        # single owner of that contract).  Baseline predictions (already
        # levels) are re-anchored to the same window anchor, so every
        # method is compared on shape from a shared anchor — the reference
        # demo's semantics for these series (web-demo/dataloader.py:143-156).
        mask = bundle.delta_mask
        labels_denorm = bundle.level_labels(idx)
        preds_denorm = bundle.integrate_test_preds(preds_denorm, idx)

        errors = {"deepr": np.abs(preds_denorm - labels_denorm)}
        if baseline_preds:
            for method, series in baseline_preds.items():
                # graftlint: disable=JX003 -- host data: baseline predictions are numpy arrays, no device sync happens here
                series = np.array(np.asarray(series)[idx], copy=True)
                if bundle._has_delta():
                    series[..., mask] += (labels_denorm[:, :1, mask]
                                          - series[:, :1, mask])
                errors[method] = np.abs(series - labels_denorm)
        return float(loss), mae_report(errors, bundle.metric_names)

    # ------------------------------------------------------------------

    def fit(
        self,
        bundle: DatasetBundle,
        state: TrainState | None = None,
        baseline_preds: Mapping[str, np.ndarray] | None = None,
        on_epoch: Callable[[EpochResult, TrainState], None] | None = None,
        num_epochs: int | None = None,
        on_step=None,
        profile_dir: str | None = None,
    ) -> tuple[TrainState, list[EpochResult]]:
        """``profile_dir``: run the second epoch of this call (the only
        one, if there is one) through :meth:`profile_epoch`, tracing into
        that directory; the table is kept as ``last_profile``."""
        if state is None:
            state = self.init_state(self.sample_input(bundle))
        data_rng = np.random.default_rng(self.config.train.seed)
        run = (self._run_epochs_elastic if self.config.train.elastic
               else self._run_epochs)
        return run(bundle, state, data_rng, 0, 0,
                   baseline_preds, on_epoch, num_epochs, on_step,
                   profile_dir)

    def resume_training(
        self,
        bundle: DatasetBundle,
        directory: str | None = None,
        baseline_preds: Mapping[str, np.ndarray] | None = None,
        on_epoch: Callable[[EpochResult, TrainState], None] | None = None,
        num_epochs: int | None = None,
        on_step=None,
        profile_dir: str | None = None,
    ) -> tuple[TrainState, list[EpochResult]]:
        """Restart a preempted :meth:`fit` from its newest cursor
        snapshot and run to completion, bit-identical to the
        uninterrupted run at every later step.

        The restore lands on WHATEVER MESH this trainer was built with —
        the cross-mesh sharded restore (round 12) assembles by global
        index, so a run preempted on a 2×2×2 slice resumes on the 1×1×1
        that survived.  The epoch plan replays from the cursor: the
        shuffle rng's bit-generator state is restored to the interrupted
        epoch's start, the plan regenerates identically, and the first
        ``steps_done`` steps are skipped without running (subsequent
        steps therefore see exactly the batches, dropout streams, and
        step counters of the uninterrupted run — the kill-at-step-K
        parity contract tests/test_chaos.py pins).
        """
        from deeprest_tpu.train.checkpoint import (
            latest_cursor_step, restore_checkpoint,
        )

        cfg = self.config.train
        directory = directory or self._snapshot_dir or cfg.checkpoint_dir
        if not directory:
            raise ValueError("resume_training needs a snapshot directory "
                             "(TrainConfig.checkpoint_dir or the "
                             "directory argument)")
        step = latest_cursor_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no cursor-bearing snapshot under {directory!r} — "
                "nothing to resume (run fit with "
                "TrainConfig.snapshot_every_steps > 0 first)")
        template = self.init_state(self.sample_input(bundle))
        state, extra = restore_checkpoint(directory, template, step=step)
        cursor = extra["train_cursor"]
        self._global_step = int(cursor["global_step"])
        data_rng = np.random.default_rng(cfg.seed)
        data_rng.bit_generator.state = cursor["rng_state"]
        run = (self._run_epochs_elastic if cfg.elastic
               else self._run_epochs)
        return run(bundle, state, data_rng,
                   int(cursor["epoch"]), int(cursor["steps_done"]),
                   baseline_preds, on_epoch, num_epochs, on_step,
                   profile_dir)

    def _run_epochs(
        self,
        bundle: DatasetBundle,
        state: TrainState,
        data_rng: np.random.Generator,
        start_epoch: int,
        skip_steps: int,
        baseline_preds: Mapping[str, np.ndarray] | None,
        on_epoch: Callable[[EpochResult, TrainState], None] | None,
        num_epochs: int | None,
        on_step=None,
        profile_dir: str | None = None,
    ) -> tuple[TrainState, list[EpochResult]]:
        cfg = self.config.train
        if cfg.snapshot_every_steps and cfg.checkpoint_dir \
                and self._snapshot_dir is None:
            self.enable_snapshots(cfg.checkpoint_dir,
                                  cfg.snapshot_every_steps)
        history: list[EpochResult] = []
        total = num_epochs if num_epochs is not None else cfg.num_epochs
        staged = self.stage_dataset(bundle) if total > start_epoch else None
        # the second epoch is the first steady one (the first compiles)
        profiled = min(start_epoch + 1, total - 1) if profile_dir else None
        for epoch in range(start_epoch, total):
            self._begin_epoch_cursor(epoch, data_rng)
            skip = skip_steps if epoch == start_epoch else 0
            if epoch == profiled:
                state, self.last_profile = self.profile_epoch(
                    state, bundle, data_rng, staged, profile_dir,
                    skip_steps=skip, on_step=on_step)
                train_loss = self.last_profile["train_loss"]
            else:
                state, train_loss = self.train_epoch(
                    state, bundle, data_rng, staged=staged,
                    skip_steps=skip, on_step=on_step)
            test_loss, report = self.evaluate(state, bundle, baseline_preds,
                                              staged=staged)
            result = EpochResult(epoch=epoch, train_loss=train_loss,
                                 test_loss=test_loss, report=report)
            history.append(result)
            if on_epoch is not None:
                on_epoch(result, state)
            # Epoch-boundary cursor: the NEXT epoch at step 0, with the
            # rng state the plan draw left behind — a kill between epochs
            # resumes exactly at the boundary.  The epoch-end snapshot
            # subsumes the plain epoch-cadence save (same full sidecar,
            # plus the cursor); writing the cursorless save AFTER it
            # would overwrite the cursor at the same step directory.
            self._begin_epoch_cursor(epoch + 1, data_rng)
            cadence_due = cfg.checkpoint_dir and (
                (epoch + 1) % cfg.checkpoint_every_epochs == 0
                or epoch + 1 == total)
            if self._snapshot_dir is not None:
                self.snapshot(state, bundle)
            elif cadence_due:
                self.save(cfg.checkpoint_dir, state, bundle)
        return state, history

    def save(self, directory: str, state: TrainState, bundle: DatasetBundle,
             extra_host_state: Mapping[str, Any] | None = None) -> str:
        """Checkpoint the state plus the host-side stats needed to serve.

        ``extra_host_state`` rides in the same sidecar, so caller state
        (e.g. the streaming refresh counter) is atomically bound to the
        step it describes.
        """
        from deeprest_tpu.train.checkpoint import save_checkpoint

        extra = {
            "metric_names": bundle.metric_names,
            "x_stats": bundle.x_stats.to_dict(),
            "y_stats": bundle.y_stats.to_dict(),
            "window_size": bundle.window_size,
            "feature_dim": bundle.feature_dim,
            "model_config": dataclasses.asdict(self.model_config),
            "space": bundle.space_dict,
            # Which metrics the model predicts as per-bucket increments —
            # serving must integrate these back to levels (predictor.py).
            "delta_mask": (np.asarray(bundle.delta_mask, bool).tolist()
                           if bundle.delta_mask is not None else None),
        }
        if extra_host_state:
            clash = set(extra_host_state) & set(extra)
            if clash:
                raise ValueError(
                    f"extra_host_state would overwrite reserved sidecar "
                    f"keys: {sorted(clash)}")
            extra.update(extra_host_state)
        return save_checkpoint(directory, state, int(state.step), extra)

    # ------------------------------------------------------------------

    def predict(self, state: TrainState, x: np.ndarray, batch_size: int = 64) -> np.ndarray:
        """Normalized quantile predictions ``[N, W, E, Q]`` for windows x."""
        outs = []
        for lo in range(0, len(x), batch_size):
            xb = feed_replicated(self.mesh, x[lo:lo + batch_size])
            outs.append(gather_to_host(self._predict_step(state.params, xb)))
        return np.concatenate(outs, axis=0)
