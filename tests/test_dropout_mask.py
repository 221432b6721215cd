"""The dropout layer of models/qrnn.py (ISSUE 36): ``flax.linen.Dropout``'s
arithmetic on ``flax.linen.Dropout``'s mask, with the mask held behind an
``optimization_barrier`` so that the compiled step draws it once and the
backward pass reads it.  Held here, on the CPU: the step's loss and every
gradient bit for bit against the same step with ``flax.linen.Dropout`` in
the layer's place; the mask against the benchmark's reference
(``chipbench/reference/qrnn_ref.dropout_keep``) on the same key; one mask a
microbatch under gradient accumulation; the identity, and the parent's
program, with ``deterministic=True``; the gauge that says how often the
compiled step draws.  What the TPU's compiler makes of it is
tests/test_chip_compile.py's.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_series_buckets

from chipbench.reference import qrnn_ref
from deeprest_tpu.config import (
    Config, FeaturizeConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.models import qrnn
from deeprest_tpu.obs import metrics, profiler
from deeprest_tpu.ops import scopes
from deeprest_tpu.ops.quantile import pinball_loss
from deeprest_tpu.parallel.distributed import stage_plan
from deeprest_tpu.train import Trainer, prepare_dataset

B, W, F, H = 4, 6, 16, 8
RATE = 0.5
QUANTILES = (0.05, 0.5, 0.95)
TRAIN = TrainConfig(batch_size=16, window_size=12, seed=0,
                    device_data="always", log_every_steps=0)


@pytest.fixture(scope="module")
def bundle():
    data = featurize_buckets(make_series_buckets(160, seed=2),
                             FeaturizeConfig(round_to=8))
    return prepare_dataset(data, TRAIN)


def _staged_trainer(bundle, rate=RATE, **train_kw):
    """(a trainer on the module's corpus, a state, the staged corpus)."""
    t = Trainer(Config(model=ModelConfig(hidden_size=H, dropout_rate=rate),
                       train=dataclasses.replace(TRAIN, **train_kw)),
                bundle.feature_dim, bundle.metric_names)
    return t, t.init_state(bundle.x_train, seed=3), t.stage_dataset(bundle)


@pytest.fixture
def flax_dropout(monkeypatch):
    """``flax.linen.Dropout`` under the layer's name in the layer's place:
    the parent's model."""
    monkeypatch.setattr(
        qrnn, "KeptMaskDropout",
        lambda rate, name: nn.Dropout(rate=rate, name=name))


def _model(dtype="float32", e=3, rate=RATE):
    return qrnn.QuantileGRU(config=ModelConfig(
        feature_dim=F, num_metrics=e, hidden_size=H, compute_dtype=dtype,
        dropout_rate=rate, quantiles=QUANTILES))


def _batch(e):
    x = jax.random.normal(jax.random.PRNGKey(1), (B, W, F))
    y = jax.random.uniform(jax.random.PRNGKey(2), (B, W, e))
    return x, y


def _params(e):
    return _model(e=e).init(jax.random.PRNGKey(0), _batch(e)[0])["params"]


def _step(dtype, e, key):
    """Loss and gradients of one training step's forward and backward."""
    model, (x, y) = _model(dtype, e), _batch(e)

    def loss_fn(params):
        preds = model.apply({"params": params}, x, deterministic=False,
                            rngs={"dropout": key})
        return pinball_loss(preds, y, QUANTILES)

    return jax.jit(jax.value_and_grad(loss_fn))(_params(e))


def _assert_bit_equal(got, want):
    got, want = (jax.tree_util.tree_leaves_with_path(t) for t in (got, want))
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("e", [1, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_step_is_the_step_with_flax_dropout(dtype, e, request):
    key = jax.random.PRNGKey(7)
    got = _step(dtype, e, key)
    request.getfixturevalue("flax_dropout")
    want = _step(dtype, e, key)
    assert float(want[0]) > 0
    _assert_bit_equal(got, want)


def _layer_io(model, params, x, **apply):
    """What the model's dropout layer returned in one forward pass."""
    _, found = model.apply({"params": params}, x, capture_intermediates=True,
                           mutable=["intermediates"], **apply)
    return found["intermediates"]["Dropout_0"]["__call__"][0]


@pytest.mark.parametrize("e", [1, 3])
def test_the_mask_is_the_references_on_the_same_key(e):
    """The layer sits where ``flax.linen.Dropout`` sat, so its rng path is
    "the first Dropout of a compact module" and the benchmark's reference
    rebuilds its mask from the step's key alone."""
    model, params, (x, _) = _model(e=e), _params(e), _batch(e)
    key = jax.random.PRNGKey(11)
    rnn_out = _layer_io(model, params, x, deterministic=True)
    assert rnn_out.shape == (e, B, W, 2 * H)
    dropped = _layer_io(model, params, x, deterministic=False,
                        rngs={"dropout": key})
    keep = qrnn_ref.dropout_keep(key, rnn_out.shape, RATE)
    assert 0.3 < float(jnp.mean(keep)) < 0.7
    np.testing.assert_array_equal(
        np.asarray(dropped),
        np.asarray(jnp.where(keep, rnn_out / (1.0 - RATE), 0.0)))


def test_accumulation_draws_one_mask_a_microbatch(bundle, request):
    """G = 4: the update's four passes draw from ``fold_in(step_key, g)``,
    four unlike masks, each the reference's; and the whole update, through
    the trainer's accumulation program, is the one ``flax.linen.Dropout``
    gives."""
    g = 4
    model, params, (x, _) = _model(), _params(3), _batch(3)
    step_key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    masks = []
    for i in range(g):
        key = jax.random.fold_in(step_key, i)
        dropped = _layer_io(model, params, x, deterministic=False,
                            rngs={"dropout": key})
        masks.append(np.asarray(dropped != 0))
        np.testing.assert_array_equal(
            masks[-1], np.asarray(qrnn_ref.dropout_keep(
                key, dropped.shape, RATE)))
    assert all((masks[i] != masks[j]).mean() > 0.3
               for i in range(g) for j in range(i))

    def update():
        t, state, staged = _staged_trainer(bundle, grad_accum_windows=g,
                                           steps_per_superstep=g)
        starts = np.random.default_rng(1).integers(
            0, bundle.num_train_windows,
            (1, g, TRAIN.batch_size)).astype(np.int32)
        plan = stage_plan(t.mesh, starts, np.ones(starts.shape, np.float32))
        return t._superstep(state, *staged, *plan, 0)

    got = update()
    request.getfixturevalue("flax_dropout")
    _assert_bit_equal(got, update())


def test_deterministic_is_the_identity_and_the_parents_program(request):
    """Eval, ``serve/`` and the AOT exports: no mask, no barrier, no
    operation, and the lowered text ``flax.linen.Dropout`` gives."""
    model, params, (x, _) = _model(), _params(3), _batch(3)

    def lowered():
        return jax.jit(lambda p, x: _model().apply(
            {"params": p}, x, deterministic=True)).lower(params, x).as_text()

    text = lowered()
    assert "optimization_barrier" not in text and "threefry" not in text
    request.getfixturevalue("flax_dropout")
    assert lowered() == text
    np.testing.assert_array_equal(
        np.asarray(_layer_io(model, params, x, deterministic=True)),
        np.asarray(_layer_io(_model(rate=0.0), params, x,
                             deterministic=False)))


@pytest.mark.parametrize("rate, compiler_draws", [(RATE, True), (0.0, False)])
def test_publish_program_sets_the_draws_gauge(bundle, monkeypatch, rate,
                                              compiler_draws):
    """``deeprest_train_dropout_draws`` is what ``threefry_draws``
    finds in the text of the executable the epoch dispatched (XLA:CPU's
    count, here: it rolls the rounds into a loop); a step that draws no
    mask sets nothing."""
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    t, state, staged = _staged_trainer(bundle, rate, steps_per_superstep=2)
    state, _ = t.train_epoch(state, bundle, np.random.default_rng(7),
                             staged=staged)
    gauge = metrics.REGISTRY.get("deeprest_train_dropout_draws")
    counted = profiler.threefry_draws(
        t._dispatched_program_text(state), scopes.DROPOUT)
    if compiler_draws:
        assert counted and gauge.value() == len(counted)
    else:
        assert not counted and not gauge.series()


_HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_draw (p: u32[4,8]) -> pred[4,8] {
  %p = u32[4,8]{1,0} parameter(0)
  %x.1 = u32[4,8]{1,0} xor(%p, %p), metadata={op_name="jit(step)/jvp(M)/dropout/Dropout_0/jit(_bernoulli)/jit(_uniform)/xor"}
  %x.2 = u32[4,8]{1,0} xor(%x.1, %p), metadata={op_name="jit(step)/jvp(M)/dropout/Dropout_0/jit(_bernoulli)/jit(_uniform)/xor"}
  ROOT %lt = pred[4,8]{1,0} compare(%x.2, %p), direction=LT
}

%fused_again (p: u32[4,8]) -> pred[4,8] {
  %p = u32[4,8]{1,0} parameter(0)
  %x.3 = u32[4,8]{1,0:T(8,128)S(1)} xor(%p, %p), metadata={op_name="jit(step)/transpose(jvp(M))/dropout/Dropout_0/jit(_bernoulli)/jit(_uniform)/xor"}
  ROOT %lt.1 = pred[4,8]{1,0} compare(%x.3, %p), direction=LT
}

%fused_elsewhere (p: u32[4,8]) -> u32[4,8] {
  %p = u32[4,8]{1,0} parameter(0)
  ROOT %x.4 = u32[4,8]{1,0} xor(%p, %p), metadata={op_name="jit(step)/init_dropout/jit(_uniform)/xor"}
}

ENTRY %main () -> f32[] {
  %k = u32[] constant(1)
  %a = u32[4,8]{1,0} constant(0)
  %fusion.1 = pred[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_draw
  %fusion.2 = pred[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_draw
  %fusion.3 = pred[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_again
  %fusion.4 = u32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_elsewhere
  %x.7 = u32[4,8]{1,0} xor(%a, %a), metadata={op_name="jit(step)/unfused_dropout/jit(_uniform)/xor"}
  %x.5 = u32[] xor(%k, %k), metadata={op_name="jit(step)/dropout/jit(_threefry_fold_in)/xor"}
  %x.6 = u32[1]{0} xor(%k, %k), metadata={op_name="jit(step)/dropout/jit(_threefry_fold_in)/xor"}
  ROOT %z = f32[] constant(0)
}
"""


def test_threefry_draws_names_the_places_under_the_scope():
    """Three fusions call the two computations that hold a round on an
    array under ``dropout`` (identical fusions share a computation; each
    call draws); a key's derivation runs on scalars; a scope whose name
    only contains the word is another scope; a round outside every fusion
    is its computation's."""
    assert profiler.threefry_draws(_HLO, "dropout") == [
        "fusion.1", "fusion.2", "fusion.3"]
    assert profiler.threefry_draws(_HLO, "init_dropout") == ["fusion.4"]
    assert profiler.threefry_draws(_HLO, "unfused_dropout") == ["main"]
    assert profiler.threefry_draws(_HLO, "mixing") == []
