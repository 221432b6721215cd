"""Adam over the rows the corpus can touch (ISSUEs 27 and 32).

On a compact base the superstep takes the table's rows of the two w_ih
leaves and of their moments once a dispatch; they ride the scan in the
leaves' place, each step differentiating with respect to them and running
Adam on them; after the scan they are put back.  Every other row has a zero
gradient, so in a state whose moments are zero off the table it does not
move; in any other state a loop after the scan visits the stale rows (those
off the table that carry a moment), a chunk a trip: taken from the six whole
leaves as the table's rows were, given that many zero-gradient updates, put
back, before the carried rows go on top (ISSUE 34); past a bound computed
from the shapes the updates run on the six whole leaves instead.  Both
loops' trip counts are 0 where the rule holds.  Held here, on the CPU at toy
widths in float32: the row-wise pass against the per-step dense pass on the
same base; a state with moments off the table, with trailing padded steps
too, and by its number of stale rows round the chunk and the bound; the
surface the benchmark drives; the traced program's shapes; the take and the
put over ``(E*F)`` rows against plain indexing; the untouched feeds'
StableHLO; and a ``data`` mesh.

Bit for bit needs care.  The two passes are the same arithmetic, but
XLA:CPU fuses a step's Adam with what feeds it, and which product of
``a*b + c*d`` the backend then folds into an FMA depends on the fusion: a
row gathered inside the step (the per-step pass) and a row carried by the
loop (the superstep) round ``mu`` and ``nu`` differently in the last bit.
With no FMA to contract into (``--xla_cpu_max_isa=SSE4_2``, a flag of the
process, so a subprocess: ``python tests/test_sparse_adam.py --exact``) the
passes are equal in every bit of every leaf; in-process they are held to
one unit in the last place of each leaf's largest magnitude.
"""

import hashlib
import inspect
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_live_columns import B, E, F, H, W, _bundle, _corpus, _trainer

from deeprest_tpu.config import MeshConfig
from deeprest_tpu.models.qrnn import (
    MASKED_PARAM_NAMES, put_columns, put_rows, take_columns,
)
from deeprest_tpu.obs import metrics
from deeprest_tpu.obs.profiler import collective_bytes
from deeprest_tpu.parallel.distributed import stage_plan, stage_sparse_base
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import trainer as trainer_module

S = 4                                   # steps a dispatch


def _plan(trainer, bundle, steps: int, seed: int = 5):
    """A staged ``[C, S, B]`` plan of ``steps`` real steps, the last chunk
    padded with zero-weight steps (S: the trainer's steps a dispatch)."""
    S = trainer.config.train.steps_per_superstep
    chunks = -(-steps // S)
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, bundle.num_train_windows,
                          (chunks * S, B)).astype(np.int32)
    weights = np.zeros((chunks * S, B), np.float32)
    weights[:steps] = 1.0
    starts, weights = (a.reshape(chunks, S, B) for a in (starts, weights))
    return starts, weights, stage_plan(trainer.mesh, starts, weights)


def _through_superstep(trainer, state, staged, plan):
    starts, _, staged_plan = plan
    losses = []
    for c in range(starts.shape[0]):
        state, chunk = trainer._superstep(state, *staged, *staged_plan, c)
        losses.append(np.asarray(chunk))
    return state, np.concatenate(losses)


def _through_per_step(trainer, state, staged, plan):
    """The same steps, one dispatch each, through the pass over all F rows
    (``_train_step_indexed`` differentiates with respect to the leaves)."""
    starts, weights, _ = plan
    losses = []
    for s, w in zip(starts.reshape(-1, B), weights.reshape(-1, B)):
        if w.any():
            state, loss = trainer._train_step_indexed(
                state, *staged, jnp.asarray(s), jnp.asarray(w))
            losses.append(float(loss))
    return state, np.asarray(losses, np.float32)


def _leaves(state):
    return {jax.tree_util.keystr(path): np.asarray(a) for path, a in
            jax.tree_util.tree_leaves_with_path(state)}


def _assert_states_equal(got, want, ulps: int = 0):
    """Every leaf equal; with ``ulps``, a float leaf to within that many
    units in the last place of its largest magnitude (the module
    docstring: what an FMA contracted otherwise moves)."""
    got, want = _leaves(got), _leaves(want)
    assert list(got) == list(want)
    for name, z in want.items():
        if ulps and z.dtype == np.float32:
            np.testing.assert_allclose(
                got[name], z, rtol=0.0, err_msg=name,
                atol=ulps * float(np.spacing(np.abs(z).max())))
        else:
            np.testing.assert_array_equal(got[name], z, err_msg=name)


# Whether this process's XLA:CPU has an FMA to contract into.
EXACT = "--xla_cpu_max_isa=SSE4_2" in os.environ.get("XLA_FLAGS", "")
ULPS = 0 if EXACT else 1


def _gauge(kinds=("updated", "total")):
    g = metrics.REGISTRY.get("deeprest_train_optimizer_rows")
    return {k: g.value(kind=k) for k in kinds}


def _setup(hot: int = 100, mesh=None, steps: int = S):
    cols, vals, y, hot_cols = _corpus(hot)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(mesh=mesh, steps_per_superstep=steps)
    staged = trainer.stage_dataset(bundle)
    assert staged[0].width == 128 and len(hot_cols) <= 128
    return trainer, bundle, staged


# -- (a), (f): the row-wise pass is the dense pass ---------------------------


def _assert_no_leaf_crosses(moved: dict, width: int) -> None:
    """What a compact superstep under a mesh hands its collectives is
    gradients and, since ISSUE 45 under a `data` axis, the table's carried
    rows (the folded weight of each direction a step, the six arrays of a
    dispatch: counted here as if a step made them all), never a leaf."""
    assert set(moved) <= {"all-reduce", "all-gather"}, moved
    assert moved["all-reduce"] < 4 * E * F * 3 * H, moved
    assert moved.get("all-gather", 0) <= (2 + 6) * 4 * E * width * 3 * H


@pytest.mark.parametrize(
    "mesh_config", [None, MeshConfig(data=2), MeshConfig(data=2, expert=2)],
    ids=["one-device", "data2", "data2-expert2"])
def test_rowwise_superstep_is_the_per_step_dense_pass(mesh_config):
    """Seven steps over two dispatches, the last one padded, against the
    same steps through ``_train_step_indexed``, which differentiates with
    respect to the whole leaves and updates all F rows: every leaf of
    ``params``, ``mu``, ``nu``, ``count``, ``step`` and ``rng``, dead rows
    included; the losses bit for bit.  (Under a mesh GSPMD places a
    superstep's reductions and a step's differently: there the dense
    superstep and the per-step loop differ in the last bit at the parent
    commit too, so that case holds a tolerance, and exactness off the
    table.)"""
    mesh = None if mesh_config is None else make_mesh(mesh_config)
    trainer, bundle, staged = _setup(mesh=mesh)
    table = np.asarray(staged[0].live)
    dead = np.setdiff1d(np.arange(F), table)

    def fresh():
        return trainer.init_state(trainer.sample_input(bundle), seed=1)

    init = _leaves(fresh())
    assert int(trainer._stale_rows(fresh().opt_state, staged[0].live)) == 0
    plan = _plan(trainer, bundle, 7)
    got, got_losses = _through_superstep(trainer, fresh(), staged, plan)
    want, want_losses = _through_per_step(trainer, fresh(), staged, plan)
    assert int(trainer._stale_rows(got.opt_state, staged[0].live)) == 0
    assert int(got.step) == int(want.step) == 7 and got_losses[7] == 0.0
    if mesh is not None:
        # the take and the put stay on each shard's own experts: what the
        # superstep hands its collectives is gradients, never a leaf
        moved = collective_bytes(trainer._superstep.lower(
            got, *staged, *plan[2], 0).compile().as_text())
        _assert_no_leaf_crosses(moved, staged[0].width)
    if mesh is None:
        np.testing.assert_array_equal(got_losses[:7], want_losses)
        _assert_states_equal(got, want, ULPS)
    got, want = _leaves(got), _leaves(want)
    np.testing.assert_allclose(got_losses[:7], want_losses, rtol=1e-6)
    for name, z in want.items():
        np.testing.assert_allclose(got[name], z, rtol=2e-4, atol=1e-7,
                                   err_msg=name)
        if any(k in name for k in MASKED_PARAM_NAMES):
            # off the table: the parameter never moved, the moments never
            # left zero, in either pass
            assert np.abs(z[:, table]).max() > 0, name
            for side in (got[name], z):
                np.testing.assert_array_equal(
                    side[:, dead],
                    init[name][:, dead] if ".params" in name else 0.0,
                    err_msg=name)


# -- (b): moments off the table still move their rows -------------------------


def _two_corpora():
    """A trainer, corpus A staged, corpus B staged (a table that leaves
    out rows of A's), and a maker of states three steps into corpus A:
    their moments lie on A's table, so off B's."""
    trainer, bundle_a, staged_a = _setup(hot=100)
    cols, vals, y, _ = _corpus(60)
    bundle_b = _bundle(cols, vals, y)
    staged_b = trainer.stage_dataset(bundle_b)
    table_a, table_b = (np.asarray(s[0].live) for s in (staged_a, staged_b))
    assert staged_b[0].width == 128 and np.setdiff1d(table_a, table_b).size

    def after_corpus_a():
        state = trainer.init_state(trainer.sample_input(bundle_a), seed=1)
        return _through_superstep(trainer, state, staged_a,
                                  _plan(trainer, bundle_a, 3))[0]

    state = after_corpus_a()
    assert int(trainer._stale_rows(state.opt_state, staged_a[0].live)) == 0
    assert int(trainer._stale_rows(state.opt_state, staged_b[0].live)) > 0
    return (trainer, (bundle_a, staged_a), (bundle_b, staged_b),
            after_corpus_a, np.setdiff1d(table_a, table_b))


def test_moments_off_the_table_move_their_rows_as_the_dense_pass_does():
    trainer, (bundle_a, staged_a), (bundle_b, staged_b), after_corpus_a, \
        only_a = _two_corpora()
    plan = _plan(trainer, bundle_b, 5, seed=6)
    got, got_losses = _through_superstep(trainer, after_corpus_a(), staged_b,
                                         plan)
    want, want_losses = _through_per_step(trainer, after_corpus_a(),
                                          staged_b, plan)
    np.testing.assert_array_equal(got_losses[:5], want_losses)
    _assert_states_equal(got, want, ULPS)
    # rows of corpus A's table that B's does not name still stepped: their
    # moments decay and Adam moves them with no gradient
    before = _leaves(after_corpus_a())
    name = f".params['{MASKED_PARAM_NAMES[0]}']"
    assert (_leaves(got)[name][:, only_a] != before[name][:, only_a]).any()

    # the gauge, from whole epochs: all F rows here, the table's on a state
    # whose moments lie on the corpus's table
    trainer.train_epoch(got, bundle_b, np.random.default_rng(0),
                        staged=staged_b)
    assert _gauge() == {"updated": F, "total": F}
    trainer.train_epoch(after_corpus_a(), bundle_a, np.random.default_rng(0),
                        staged=staged_a)
    assert _gauge() == {"updated": 128, "total": F}


def test_a_padded_dispatch_moves_rows_off_the_table_by_its_real_steps_only():
    """One dispatch of two real steps and two padded ones on a state with
    moments off the table: the loop after the scan runs twice, with the
    counts the two real steps used, so every leaf (dead rows included) is
    what two steps of ``_train_step_indexed`` leave, and not what four
    zero-gradient updates would."""
    trainer, _, (bundle_b, staged_b), after_corpus_a, only_a = _two_corpora()
    plan = _plan(trainer, bundle_b, 2, seed=7)
    assert plan[1].shape == (1, S, B) and (plan[1].sum(axis=2) > 0).tolist() \
        == [[True, True, False, False]]
    got, got_losses = _through_superstep(trainer, after_corpus_a(), staged_b,
                                         plan)
    want, want_losses = _through_per_step(trainer, after_corpus_a(),
                                          staged_b, plan)
    count = ".opt_state[0].count"
    assert int(got.step) == 3 + 2 and _leaves(got)[count] == 3 + 2
    np.testing.assert_array_equal(got_losses, [*want_losses, 0.0, 0.0])
    _assert_states_equal(got, want, ULPS)
    # a row only corpus A named moved, and two padded steps more would
    # have moved it further
    name = f".params['{MASKED_PARAM_NAMES[0]}']"
    before = _leaves(after_corpus_a())[name][:, only_a]
    moved = _leaves(got)[name][:, only_a]
    further = _leaves(_through_per_step(
        trainer, after_corpus_a(), staged_b,
        _plan(trainer, bundle_b, 4, seed=7))[0])[name][:, only_a]
    assert (moved != before).any() and (moved != further).any()


# -- (b'): by the number of stale rows, round the chunk and the bound ----------

S_LONG = 8          # steps a dispatch at which the bound clears a chunk
CHUNK = trainer_module._CHUNK
BOUND = trainer_module.off_table_bound(F, 128, S_LONG)


def _stale_rows_for(case, table) -> np.ndarray:
    """The rows to make stale: ``case`` of them spread over the dead rows,
    or for ``"neighbours"`` the lowest dead rows next to a row of the table,
    three short of a chunk (the pad slots are then the lowest dead rows left,
    which lie next to the table's own pad slots)."""
    dead = np.setdiff1d(np.arange(F), table)
    if case == "neighbours":
        return np.intersect1d(
            dead, np.concatenate([table - 1, table + 1]))[:CHUNK - 3]
    return np.sort(np.random.default_rng(case).choice(dead, case,
                                                      replace=False))


def _with_moments_at(state, rows, seed: int = 11):
    """``state`` with Adam moments of a real size at ``rows`` of both w_ih
    leaves (``mu`` of either sign, ``nu`` positive), as a table that named
    them would have left, placed as the leaves were."""
    rng = np.random.default_rng(seed)
    adam = state.opt_state[0]
    mu, nu = dict(adam.mu), dict(adam.nu)
    for name in MASKED_PARAM_NAMES:
        for tree, draw in ((mu, lambda n: rng.standard_normal(n) * 1e-3),
                           (nu, lambda n: rng.random(n) * 1e-6 + 1e-9)):
            a = np.array(tree[name])
            a[:, rows] = draw((E, len(rows), 3 * H)).astype(np.float32)
            tree[name] = jax.device_put(a, tree[name].sharding)
    return state.replace(opt_state=(adam._replace(mu=mu, nu=nu),
                                    *state.opt_state[1:]))


@pytest.mark.parametrize("case", [
    1, CHUNK - 1, CHUNK, CHUNK + 1, BOUND, BOUND + 1, "neighbours"])
def test_the_off_table_pass_by_its_number_of_stale_rows(case):
    """Ten steps over two dispatches (the second: two real steps, six
    padded) on a state with ``case`` stale rows, against the same steps one
    dispatch each through the pass over all F rows: every leaf, so the
    stale rows as Adam steps them, the table's rows, and the dead rows that
    pad a chunk (which neighbour the table's and must not move).  A chunk
    less one row, a chunk, a chunk and a row (two trips), the bound (the
    last count row by row) and one past it (the all-rows loop)."""
    assert CHUNK + 1 < BOUND < F - 128 - CHUNK
    trainer, bundle, staged = _setup(steps=S_LONG)
    table = np.asarray(staged[0].live)
    rows = _stale_rows_for(case, table)
    count = len(rows)
    assert count == (CHUNK - 3 if case == "neighbours" else case)

    def stale_state():
        return _with_moments_at(
            trainer.init_state(trainer.sample_input(bundle), seed=1), rows)

    assert int(trainer._stale_rows(stale_state().opt_state,
                                   staged[0].live)) == count
    plan = _plan(trainer, bundle, S_LONG + 2, seed=8)
    got, got_losses = _through_superstep(trainer, stale_state(), staged, plan)
    want, want_losses = _through_per_step(trainer, stale_state(), staged,
                                          plan)
    np.testing.assert_array_equal(got_losses[:S_LONG + 2], want_losses)
    # ten steps contract one unit further apart than the seven above
    _assert_states_equal(got, want, 2 * ULPS)
    # the stale rows moved, every one; a dead row without a moment did not
    before, after = _leaves(stale_state()), _leaves(got)
    untouched = np.setdiff1d(np.arange(F), np.concatenate([table, rows]))
    for name in MASKED_PARAM_NAMES:
        key = f".params['{name}']"
        assert (after[key][:, rows] != before[key][:, rows]).any(axis=(0, 2)
                                                                 ).all()
        np.testing.assert_array_equal(after[key][:, untouched],
                                      before[key][:, untouched])
    # the gauge, from a whole epoch: `updated` and `stale` as ever, and the
    # rows the dispatches wrote
    trainer.train_epoch(stale_state(), bundle, np.random.default_rng(0),
                        staged=staged)
    visited = (F if count > BOUND else 128 + -(-count // CHUNK) * CHUNK)
    assert _gauge(("stale", "updated", "visited", "total")) == {
        "stale": count, "updated": F, "visited": visited, "total": F}
    assert visited == trainer_module.rows_visited(F, 128, S_LONG, count)


@pytest.mark.parametrize(
    "mesh_config", [MeshConfig(data=2), MeshConfig(data=2, expert=2)],
    ids=["data2", "data2-expert2"])
def test_the_off_table_pass_under_a_mesh(mesh_config):
    """A chunk and a row of stale rows under a mesh: with an ``expert``
    axis the take indexes each shard's own ``(E/2 * F)`` rows and the put
    writes each shard's own slabs.  Against the per-step pass to the
    tolerance a mesh asks for (see the row-wise case above), the stale
    rows having moved; and what the superstep hands its collectives is
    still gradients, never a leaf or a chunk of one."""
    trainer, bundle, staged = _setup(mesh=make_mesh(mesh_config),
                                     steps=S_LONG)
    table = np.asarray(staged[0].live)
    rows = _stale_rows_for(CHUNK + 1, table)

    def stale_state():
        return _with_moments_at(
            trainer.init_state(trainer.sample_input(bundle), seed=1), rows)

    plan = _plan(trainer, bundle, S_LONG + 2, seed=8)
    got, got_losses = _through_superstep(trainer, stale_state(), staged, plan)
    want, want_losses = _through_per_step(trainer, stale_state(), staged,
                                          plan)
    moved = collective_bytes(trainer._superstep.lower(
        got, *staged, *plan[2], 0).compile().as_text())
    _assert_no_leaf_crosses(moved, staged[0].width)
    np.testing.assert_allclose(got_losses[:S_LONG + 2], want_losses,
                               rtol=1e-6)
    before, got, want = (_leaves(s) for s in (stale_state(), got, want))
    for name, z in want.items():
        np.testing.assert_allclose(got[name], z, rtol=2e-4, atol=1e-7,
                                   err_msg=name)
        if any(k in name for k in MASKED_PARAM_NAMES):
            # off the table no gradient enters: the same steps in both
            # passes (to what an FMA contracts), whatever the mesh did to
            # the table's rows
            dead = np.setdiff1d(np.arange(F), table)
            np.testing.assert_allclose(
                got[name][:, dead], z[:, dead], rtol=0.0, err_msg=name,
                atol=2 * float(np.spacing(np.abs(z).max())))
            assert (got[name][:, rows] != before[name][:, rows]).any()


# -- (c): what the benchmark drives -------------------------------------------


def test_the_benchmarks_surface_is_kept_and_seeded_weights_run_rowwise():
    trainer, bundle, staged = _setup()
    assert list(inspect.signature(trainer._superstep).parameters) == [
        "state", "x_base", "y_base", "starts_plan", "weights_plan", "chunk"]
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    shapes = {k: v.shape for k, v in state.params.items()}
    # as chipbench/runners/train.py installs its seeded weights
    placement = {k: v.sharding for k, v in state.params.items()}
    rng = np.random.default_rng(9)
    state = state.replace(params={})
    state = state.replace(params={
        k: jax.device_put(rng.standard_normal(shape).astype(np.float32) / 8,
                          placement[k]) for k, shape in shapes.items()})
    reads0 = metrics.REGISTRY.get("deeprest_train_readbacks_total").value(
        sink="optimizer_rows")
    state, loss = trainer.train_epoch(state, bundle, np.random.default_rng(0),
                                      staged=staged)
    assert _gauge() == {"updated": 128, "total": F}
    assert metrics.REGISTRY.get("deeprest_train_readbacks_total").value(
        sink="optimizer_rows") == reads0 + 1
    out = trainer._superstep(state, *staged, *_plan(trainer, bundle, 2)[2], 0)
    assert isinstance(out, tuple) and len(out) == 2
    state, losses = out
    assert losses.shape == (S,) and losses.dtype == jnp.float32
    mu, nu = state.opt_state[0].mu, state.opt_state[0].nu
    for tree in (state.params, mu, nu):
        assert {k: v.shape for k, v in tree.items()} == shapes
        assert all(v.dtype == jnp.float32 for v in tree.values())
    for name in MASKED_PARAM_NAMES:
        assert shapes[name] == (E, F, 3 * H)
        live = np.asarray(staged[0].live)
        assert np.asarray(mu[name])[:, live].any()
        assert not np.delete(np.asarray(mu[name]), live, axis=1).any()


# -- (d): the traced program's shapes ------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _floats_of_shape(variables, shapes) -> bool:
    return any(getattr(v.aval, "shape", None) in shapes
               and v.aval.dtype == jnp.float32 for v in variables)


def _compact_program():
    """One trace of the compact superstep: its equations, and the widths."""
    trainer, bundle, staged = _setup()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    traced = jax.make_jaxpr(trainer._superstep)(
        state, *staged, *_plan(trainer, bundle, 2)[2], 0)
    (program,) = traced.jaxpr.eqns
    return program.params["jaxpr"].jaxpr.eqns, staged[0].width


def test_the_rows_ride_the_scan_and_nothing_of_a_leafs_shape_is_inside_it():
    """One trace of the compact superstep.  Before the scan six gathers of
    ``(E*F)`` rows (two leaves, their ``mu`` and ``nu``); inside its body
    no equation with an ``[E, F, 3H]`` (or ``[E*F, 3H]``) float operand or
    result, and no such operand of the scan itself: the step names the
    carried ``[E, U_pad, 3H]`` rows only; after it the two loops over the
    six whole leaves for a state with stale rows (a chunk of them a trip;
    all rows past the bound), then the six scatters that put the rows
    back, and nothing else that makes a leaf."""
    eqns, width = _compact_program()
    names = [e.primitive.name for e in eqns]
    assert names.count("scan") == 1 and names.count("while") == 2
    scan_at, chunks_at = names.index("scan"), names.index("while")
    loop_at = names.index("while", chunks_at + 1)
    assert scan_at < chunks_at
    leaf, flat = (E, F, 3 * H), (E * F, 3 * H)
    rows, flat_rows = (E, width, 3 * H), (E * width, 3 * H)
    n = 3 * len(MASKED_PARAM_NAMES)

    scan = eqns[scan_at]
    inside = list(_eqns(scan.params["jaxpr"].jaxpr))
    assert len(inside) > 200
    assert not _floats_of_shape(scan.invars, {leaf, flat})
    assert not [e for e in inside
                if _floats_of_shape([*e.invars, *e.outvars], {leaf, flat})]
    assert sum(_floats_of_shape(scan.outvars[i:i + 1], {rows})
               for i in range(len(scan.outvars))) == n

    before = eqns[:scan_at]
    takes = [e for e in before if e.primitive.name == "gather"]
    assert len(takes) == n and all(
        _floats_of_shape(e.invars[:1], {flat})
        and _floats_of_shape(e.outvars, {flat_rows}) for e in takes)
    assert not [e for e in before if "scatter" in e.primitive.name]

    # the all-rows case: Adam's arithmetic on whole leaves, in the loop only
    loop = eqns[loop_at]
    body = [e for e in _eqns(loop.params["body_jaxpr"].jaxpr)
            if _floats_of_shape(e.outvars, {leaf})]
    assert sum(e.primitive.name == "mul" for e in body) > n
    puts = [e for e in eqns[loop_at + 1:] if e.primitive.name == "scatter"]
    assert len(puts) == n and all(
        _floats_of_shape(e.invars[:1], {flat})
        and _floats_of_shape(e.invars[2:3], {flat_rows}) for e in puts)
    layout_only = {"reshape", "sharding_constraint", "scatter", "gather",
                   "scan", "while"}
    makers = {e.primitive.name for i, e in enumerate(eqns) if i != loop_at
              and _floats_of_shape(e.outvars, {leaf, flat})}
    assert makers <= layout_only, makers


def test_the_chunk_loop_names_a_leaf_only_to_take_and_put_its_rows():
    """The first loop after the scan (ISSUE 34): a trip takes one chunk's
    rows of the six carried leaves through their ``[E*F, 3H]`` views (six
    gathers), steps them in a loop of its own whose equations are all
    ``[E, CHUNK, 3H]`` or smaller, and puts them back a row at a time (six
    loops of ``dynamic_update_slice``, ``scan`` s here: ``put_rows``).  Nothing else in the
    body has a leaf's shape as operand or result: Adam's arithmetic never
    sees a whole leaf here, and no scatter passes over one."""
    eqns, _ = _compact_program()
    chunk = trainer_module._CHUNK
    leaf, flat = (E, F, 3 * H), (E * F, 3 * H)
    rows, flat_rows = (E, chunk, 3 * H), (E * chunk, 3 * H)
    n = 3 * len(MASKED_PARAM_NAMES)
    loop = next(e for e in eqns if e.primitive.name == "while")
    body = loop.params["body_jaxpr"].jaxpr
    assert sum(_floats_of_shape([v], {leaf}) for v in body.outvars) == n
    names = [e.primitive.name for e in body.eqns]
    assert names.count("gather") == names.count("scan") == n
    assert names.count("while") == 1
    assert "scatter" not in {e.primitive.name for e in _eqns(body)}
    takes, steps = names.index("gather"), names.index("while")
    assert takes < steps
    assert all(_floats_of_shape(e.invars[:1], {flat})
               and _floats_of_shape(e.outvars, {flat_rows})
               for e in body.eqns if e.primitive.name == "gather")
    inner = body.eqns[steps]
    puts = [e for e in body.eqns if e.primitive.name == "scan"]
    assert not _floats_of_shape([*inner.invars, *inner.outvars],
                                {leaf, flat})
    stepped = [e for e in _eqns(inner.params["body_jaxpr"].jaxpr)
               if _floats_of_shape(e.outvars, {rows})]
    assert sum(e.primitive.name == "mul" for e in stepped) > n
    for put in puts:
        assert sum(_floats_of_shape([v], {leaf}) for v in put.outvars) == 1
        assert {e.primitive.name for e in
                _eqns(put.params["jaxpr"].jaxpr)
                if _floats_of_shape([*e.invars, *e.outvars], {leaf})
                } == {"dynamic_update_slice"}
    touching = {e.primitive.name for e in _eqns(body)
                if _floats_of_shape([*e.invars, *e.outvars], {leaf, flat})}
    assert touching == {"reshape", "gather", "scan",
                        "dynamic_update_slice"}, touching


# -- (g): the take and the put over (E*F) rows ---------------------------------


@pytest.mark.parametrize("f", [512, 2048, 10240])
def test_take_and_put_over_flat_rows_are_plain_indexing(f):
    """``take_columns`` / ``put_columns`` of a 3-D leaf index rows
    ``e*F + live[u]`` of its ``[E*F, C]`` view: the values of
    ``a[:, live]`` and ``a.at[:, live].set(rows)``, and the take's
    transpose a gradient that is zero off the table."""
    rng = np.random.default_rng(f)
    e, c, u = 3, 24, f // 8
    a = rng.standard_normal((e, f, c)).astype(np.float32)
    live = np.sort(rng.choice(f, u, replace=False)).astype(np.int32)
    new = rng.standard_normal((e, u, c)).astype(np.float32)
    np.testing.assert_array_equal(
        jax.jit(take_columns)(a, live), a[:, live])
    want = a.copy()
    want[:, live] = new
    np.testing.assert_array_equal(
        jax.jit(put_columns)(a, live, new), want)
    grad = jax.grad(lambda w: jnp.sum(take_columns(w, live) * new))(
        jnp.asarray(a))
    want = np.zeros_like(a)
    want[:, live] = new
    np.testing.assert_array_equal(grad, want)
    # the 2-D mask keeps its plain form
    np.testing.assert_array_equal(
        take_columns(jnp.asarray(a[:, :, 0]), live), a[:, live, 0])


def test_put_rows_is_plain_indexing_and_promises_nothing():
    """``put_rows`` writes ``a[:, cols[i]] = rows[:, i]`` a row at a time:
    the values of ``put_columns`` on a sorted table, and on one out of
    order or with a repeat still plain assignment in order."""
    rng = np.random.default_rng(3)
    e, f, c = 3, 64, 24
    a = rng.standard_normal((e, f, c)).astype(np.float32)
    for cols in ([2, 5, 17, 40], [40, 2, 17, 5], [7, 9, 7, 63]):
        cols = np.asarray(cols, np.int32)
        new = rng.standard_normal((e, len(cols), c)).astype(np.float32)
        want = a.copy()
        for i, col in enumerate(cols):
            want[:, col] = new[:, i]
        np.testing.assert_array_equal(jax.jit(put_rows)(a, cols, new), want)
    np.testing.assert_array_equal(
        jax.jit(put_rows)(a, cols[:2], new[:, :2]),
        jax.jit(put_columns)(a, cols[:2], new[:, :2]))


# -- (e): the other feeds' supersteps are the parent's ------------------------


def _dense_feed():
    """A hashed dense corpus on the staged dense feed (no SparseBase)."""
    from conftest import make_series_buckets
    from deeprest_tpu.config import (
        Config, FeaturizeConfig, ModelConfig, TrainConfig,
    )
    from deeprest_tpu.data.featurize import featurize_buckets
    from deeprest_tpu.train import Trainer, prepare_dataset

    tc = TrainConfig(batch_size=B, window_size=W, device_data="always",
                     steps_per_superstep=S, log_every_steps=0)
    data = featurize_buckets(make_series_buckets(60, seed=5),
                             FeaturizeConfig(hash_features=True, capacity=F))
    bundle = prepare_dataset(data, tc)
    trainer = Trainer(Config(model=ModelConfig(hidden_size=H), train=tc),
                      bundle.feature_dim, bundle.metric_names)
    return trainer, bundle, trainer.stage_dataset(bundle)


def _sparse_dense_form():
    """A sparse corpus whose live set pads over half of F: no table.  The
    lowered text has the corpus's shapes and none of its values, so its
    digest does not depend on which live set over the bound this is."""
    cols, vals, y, _ = _corpus(257)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(steps_per_superstep=S)
    staged = trainer.stage_dataset(bundle)
    assert staged[0].live is None
    return trainer, bundle, staged


# sha1 of the lowered superstep's StableHLO, from these very builders.
# ISSUE 36 moved all three: every training program now holds the dropout
# mask behind an ``optimization_barrier`` (models/qrnn.KeptMaskDropout), one
# more operation in each lowered step and nothing else (tests/
# test_dropout_mask.py holds the step to flax's Dropout bit for bit).
# Before it the two feeds without a table had stood since a159714 (ISSUE
# 27's parent: 0d7001e2..., 088b19fa...) and the compact base (``_setup``: a
# table, the rows riding the scan, the off-table pass by chunks of stale
# rows) as ISSUE 34 left it (f4785d4d...).  ISSUE 56 moved all three again
# (from c6b63920..., 026af2c7..., 4a167ee4...): the update is ordered by
# leaf, so each lowered step holds a second ``optimization_barrier`` and two
# ``tx.update``s over disjoint leaves where it held one, the same arithmetic
# (section (g) below and tests/test_ordered_update.py hold the states equal).
PARENT_SHA1 = {
    "dense-feed": "b533b5505a245ad94bf44fb1baccd57d12bc7029",
    "sparse-dense-form": "788dff3dac637d787b6b28eeac8322fd9d437ef0",
    "sparse-compact": "9057f599c5bdb9b4805e717dc59e0114adf2672f",
}
BUILDERS = dict(zip(PARENT_SHA1,
                    (_dense_feed, _sparse_dense_form, _setup)))


def superstep_sha1(build) -> str:
    trainer, bundle, staged = build()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    text = trainer._superstep.lower(
        state, *staged, *_plan(trainer, bundle, 2)[2], 0).as_text()
    return hashlib.sha1(text.encode()).hexdigest()


def test_the_compact_base_lowers_to_the_pinned_superstep():
    """The compact superstep's program, pinned: a refactor that moves no
    instruction leaves it, and one that does says so here."""
    assert superstep_sha1(_setup) == PARENT_SHA1["sparse-compact"]


@pytest.mark.parametrize("build", [_dense_feed, _sparse_dense_form],
                         ids=list(PARENT_SHA1)[:2])
def test_feeds_without_a_table_lower_to_the_parents_superstep(
        build, request, monkeypatch):
    """The branch is taken at trace time: a dense feed and a sparse base in
    its dense form lower to the program they lowered to before, and set no
    row gauge (dense feed) or ``updated == total`` (sparse, dense form)."""
    assert superstep_sha1(build) == PARENT_SHA1[request.node.callspec.id]
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    trainer, bundle, staged = build()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    trainer.train_epoch(state, bundle, np.random.default_rng(0),
                        staged=staged)
    assert _gauge() == ({"updated": 0, "total": 0} if build is _dense_feed
                        else {"updated": F, "total": F})


# -- (g): the update ordered by leaf IS tx.update (ISSUE 56) ------------------


def plain_step(trainer, window: int = W):
    """A train step written out with the PLAIN update (one ``tx.update``
    over the whole gradient tree, then ``apply_updates``), differentiating
    with respect to the whole leaves: what `apply_gradients` was before it
    ordered the update by leaf.  The model, the loss and ``tx`` are the
    trainer's own; tests/test_chip_compile.py compiles it for the chip."""
    import optax
    from deeprest_tpu.ops.densify import (
        SparseBase, gather_densify_normalize,
    )
    from deeprest_tpu.ops.quantile import pinball_loss

    def step(state, x_base, y_base, starts, wb):
        idx = starts[:, None] + jnp.arange(window)[None, :]
        sparse = isinstance(x_base, SparseBase)
        xb = gather_densify_normalize(x_base, idx) if sparse else x_base[idx]

        def loss_of(params):
            preds = trainer.model.apply(
                {"params": params}, xb, deterministic=False,
                rngs={"dropout": jax.random.fold_in(state.rng, state.step)},
                live_cols=x_base.live if sparse else None)
            return pinball_loss(preds, y_base[idx],
                                trainer.model_config.quantiles,
                                sample_weight=wb)

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        updates, opt_state = trainer.tx.update(grads, state.opt_state)
        return state.replace(
            step=state.step + 1, opt_state=opt_state,
            params=optax.apply_updates(state.params, updates)), loss

    return step


def _plain_steps(trainer, state, staged, plan):
    """The plan's real steps through :func:`plain_step`: the state and the
    steps' losses."""
    step = jax.jit(plain_step(trainer))
    starts, weights, _ = plan
    losses = []
    for s, w in zip(starts.reshape(-1, B), weights.reshape(-1, B)):
        if w.any():
            state, loss = step(state, *staged, jnp.asarray(s), jnp.asarray(w))
            losses.append(float(loss))
    return state, np.asarray(losses, np.float32)


def ordered_update_is_the_plain_update(form):
    """The dense feed, the sparse feed's dense form and the compact form:
    three steps of the superstep (whose update runs the first w_ih leaf's
    Adam, a barrier, then every other leaf's) and three of the per-step
    program (the same `apply_gradients` on whole leaves) leave the params,
    ``mu``, ``nu``, ``count`` and the losses of three plain ``tx.update``s,
    and the optimizer state's tree with its keys in the order ``tx.init``
    gives them (a checkpoint of the parent restores).  The cases are
    tests/test_ordered_update.py's (a file of its own: this one is near the
    330 s a file may take), in-process and, to the bit, in its SSE4.2
    subprocess."""
    trainer, bundle, staged = BUILDERS[form]()
    plan = _plan(trainer, bundle, 3)

    def fresh_state():
        return trainer.init_state(trainer.sample_input(bundle), seed=1)

    want, want_losses = _plain_steps(trainer, fresh_state(), staged, plan)
    for through in (_through_superstep, _through_per_step):
        got, losses = through(trainer, fresh_state(), staged, plan)
        assert int(got.opt_state[0].count) == 3
        _assert_states_equal(got, want, ULPS)
        np.testing.assert_allclose(
            losses[:3], want_losses, rtol=0.0, err_msg=through.__name__,
            atol=ULPS * float(np.spacing(np.float32(1.0))))
    fresh = trainer.tx.init(got.params)
    assert (jax.tree.structure(got.opt_state)
            == jax.tree.structure(fresh))
    for mirror in ("mu", "nu"):
        assert (list(getattr(got.opt_state[0], mirror))
                == list(getattr(fresh[0], mirror)) == list(got.params))


# -- (h): without an FMA to contract into, every bit ---------------------------

EXACT_CASES = {
    "rowwise": lambda: test_rowwise_superstep_is_the_per_step_dense_pass(None),
    "off-table":
        test_moments_off_the_table_move_their_rows_as_the_dense_pass_does,
    "padded-off-table":
        test_a_padded_dispatch_moves_rows_off_the_table_by_its_real_steps_only,
    "two-chunks":
        lambda: test_the_off_table_pass_by_its_number_of_stale_rows(CHUNK + 1),
    "past-the-bound":
        lambda: test_the_off_table_pass_by_its_number_of_stale_rows(BOUND + 1),
}


@pytest.fixture(scope="module")
def exact_run():
    """This file as a script in a process whose XLA:CPU may use no FMA
    (the module docstring): the cases above with ``ULPS`` 0."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_cpu_max_isa=SSE4_2",
           "PYTHONPATH": os.pathsep.join(
               [root, os.path.join(root, "tests")])}
    return subprocess.run([sys.executable, __file__, "--exact"], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("case", list(EXACT_CASES))
def test_without_fma_contraction_the_passes_are_equal_in_every_bit(
        exact_run, case):
    assert f"exact {case}: equal" in exact_run.stdout, (
        exact_run.stdout[-2000:] + exact_run.stderr[-4000:])


if __name__ == "__main__":
    if "--exact" in sys.argv:
        assert EXACT and ULPS == 0
        for _name, _case in EXACT_CASES.items():
            try:
                _case()
                print(f"exact {_name}: equal", flush=True)
            except AssertionError as err:
                print(f"exact {_name}: NOT equal: {err}", flush=True)
        sys.exit(0)
    # the digests, from a checkout on PYTHONPATH
    for _name, _build in BUILDERS.items():
        print(_name, superstep_sha1(_build))
