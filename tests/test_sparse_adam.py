"""Adam over the rows the corpus can touch (ISSUE 27).

On a compact base the superstep differentiates with respect to the table's
rows of the two w_ih leaves and runs Adam on those rows of the leaves and of
their moments; every other row has a zero gradient and, in a state whose
moments are zero off the table, a zero step.  The program checks that itself
once a dispatch and scatters the gradient and updates all F rows otherwise.
Held here, on the CPU at toy widths in float32: the row-wise pass against
the per-step dense pass on the same base, bit for bit; the dense branch for
a state with moments off the table; the surface the benchmark drives; the
traced step's shapes; the untouched feeds' StableHLO; and a ``data`` mesh.
"""

import hashlib
import inspect
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_live_columns import B, E, F, H, W, _bundle, _corpus, _trainer

from deeprest_tpu.config import MeshConfig
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES
from deeprest_tpu.obs import metrics
from deeprest_tpu.parallel.distributed import stage_plan, stage_sparse_base
from deeprest_tpu.parallel.mesh import make_mesh

S = 4                                   # steps a dispatch


def _plan(trainer, bundle, steps: int, seed: int = 5):
    """A staged ``[C, S, B]`` plan of ``steps`` real steps, the last chunk
    padded with zero-weight steps."""
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


def _assert_states_equal(got, want):
    got, want = _leaves(got), _leaves(want)
    assert list(got) == list(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _gauge():
    g = metrics.REGISTRY.get("deeprest_train_optimizer_rows")
    return {k: g.value(kind=k) for k in ("updated", "total")}


def _setup(hot: int = 100, mesh=None):
    cols, vals, y, hot_cols = _corpus(hot)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(mesh=mesh, steps_per_superstep=S)
    staged = trainer.stage_dataset(bundle)
    assert staged[0].width == 128 and len(hot_cols) <= 128
    return trainer, bundle, staged


# -- (a), (f): the row-wise pass is the dense pass ---------------------------


@pytest.mark.parametrize("mesh_config", [None, MeshConfig(data=2)],
                         ids=["one-device", "data2"])
def test_rowwise_superstep_is_the_per_step_dense_pass(mesh_config):
    """Seven steps over two dispatches, the last one padded, against the
    same steps through ``_train_step_indexed``, which differentiates with
    respect to the whole leaves and updates all F rows: every leaf of
    ``params``, ``mu``, ``nu``, ``count``, ``step`` and ``rng`` bit for
    bit, dead rows included.  (Under a mesh GSPMD places a superstep's
    reductions and a step's differently: there the dense superstep and
    the per-step loop differ in the last bit at the parent commit too, so
    that case holds a tolerance, and exactness off the table.)"""
    mesh = None if mesh_config is None else make_mesh(mesh_config)
    trainer, bundle, staged = _setup(mesh=mesh)
    table = np.asarray(staged[0].live)
    dead = np.setdiff1d(np.arange(F), table)

    def fresh():
        return trainer.init_state(trainer.sample_input(bundle), seed=1)

    init = _leaves(fresh())
    assert bool(trainer._moments_off_table_are_zero(fresh().opt_state,
                                                    staged[0].live))
    plan = _plan(trainer, bundle, 7)
    got, got_losses = _through_superstep(trainer, fresh(), staged, plan)
    want, want_losses = _through_per_step(trainer, fresh(), staged, plan)
    assert bool(trainer._moments_off_table_are_zero(got.opt_state,
                                                    staged[0].live))
    assert int(got.step) == int(want.step) == 7 and got_losses[7] == 0.0
    if mesh is None:
        np.testing.assert_array_equal(got_losses[:7], want_losses)
        _assert_states_equal(got, want)
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


# -- (b): moments off the table take the pass over all F rows -----------------


def test_moments_off_the_table_take_the_dense_branch_bit_for_bit():
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
    assert bool(trainer._moments_off_table_are_zero(state.opt_state,
                                                    staged_a[0].live))
    assert not bool(trainer._moments_off_table_are_zero(state.opt_state,
                                                        staged_b[0].live))
    plan = _plan(trainer, bundle_b, 5, seed=6)
    got, got_losses = _through_superstep(trainer, after_corpus_a(), staged_b,
                                         plan)
    want, want_losses = _through_per_step(trainer, state, staged_b, plan)
    np.testing.assert_array_equal(got_losses[:5], want_losses)
    _assert_states_equal(got, want)
    # rows of corpus A's table that B's does not name still stepped: their
    # moments decay and Adam moves them with no gradient
    only_a = np.setdiff1d(table_a, table_b)
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


# -- (d): the traced step's shapes --------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _leaf_makers(eqns, leaf):
    """primitive -> count, over the equations with a float result of a
    leaf's shape."""
    made = {}
    for eqn in eqns:
        if any(getattr(v.aval, "shape", None) == leaf
               and v.aval.dtype == jnp.float32 for v in eqn.outvars):
            made[eqn.primitive.name] = made.get(eqn.primitive.name, 0) + 1
    return made


def test_the_rowwise_branch_holds_no_gradient_or_zero_fill_of_a_leafs_shape():
    """One trace of the compact superstep.  The step makes the w_ih
    gradient at the table's rows; the only equations with an ``[E, F, 3H]``
    float result sit in the conditional round the optimizer: on its
    row-wise side the three write-backs a leaf and nothing else, on the
    other the zeros the rows are scattered into and Adam over all rows."""
    trainer, bundle, staged = _setup()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    traced = jax.make_jaxpr(trainer._superstep)(
        state, *staged, *_plan(trainer, bundle, 2)[2], 0)
    leaf = (E, F, 3 * H)
    wrappers = {"pjit", "jit", "sharding_constraint", "closed_call", "cond",
                "scan", "while"}
    conds = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "cond"]
    # the innermost conditional that holds the write-backs
    optimizer = min(
        (e for e in conds if any(
            "scatter" in _leaf_makers(_eqns(b.jaxpr), leaf)
            for b in e.params["branches"])),
        key=lambda e: sum(1 for b in e.params["branches"]
                          for _ in _eqns(b.jaxpr)))
    on_all, on_rows = (_leaf_makers(_eqns(b.jaxpr), leaf)
                       for b in optimizer.params["branches"])  # false, true
    assert set(on_rows) - wrappers == {"scatter"}, on_rows
    assert on_rows["scatter"] == 3 * len(MASKED_PARAM_NAMES)
    assert on_all.get("broadcast_in_dim", 0) >= 2, on_all
    assert on_all.get("scatter-add", 0) == 2 and on_all.get("mul", 0) > 6
    inside = {id(e) for b in optimizer.params["branches"]
              for e in _eqns(b.jaxpr)}
    outside = _leaf_makers(
        (e for e in _eqns(traced.jaxpr) if id(e) not in inside), leaf)
    assert not set(outside) - wrappers, outside


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
    """A sparse corpus whose live set is over a quarter of F: no table."""
    cols, vals, y, _ = _corpus(129)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(steps_per_superstep=S)
    staged = trainer.stage_dataset(bundle)
    assert staged[0].live is None
    return trainer, bundle, staged


# sha1 of the lowered superstep's StableHLO at a parent commit, from these
# very builders run against a checkout of it: the two feeds without a table
# at a159714 (ISSUE 27), the compact base (``_setup``: a table, the row-wise
# superstep) at 29db32f (ISSUE 28)
PARENT_SHA1 = {
    "dense-feed": "0d7001e28a87175cec6d84c804c7eab4ef1e62b7",
    "sparse-dense-form": "088b19fac794d650f0639c88651a4697758deea4",
    "sparse-compact": "2564abe58bf57ef47346861c28cefdb5b22bc957",
}
BUILDERS = dict(zip(PARENT_SHA1,
                    (_dense_feed, _sparse_dense_form, _setup)))


def superstep_sha1(build) -> str:
    trainer, bundle, staged = build()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    text = trainer._superstep.lower(
        state, *staged, *_plan(trainer, bundle, 2)[2], 0).as_text()
    return hashlib.sha1(text.encode()).hexdigest()


def test_the_compact_base_lowers_to_the_parents_superstep():
    """ISSUE 28 took options away and moved no instruction: the row-wise
    superstep of a base with a table is the one 29db32f lowered."""
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


if __name__ == "__main__":       # the digests, from a checkout on sys.path
    for _name, _build in BUILDERS.items():
        print(_name, superstep_sha1(_build))
