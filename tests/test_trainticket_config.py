"""`trainticket-e200` (ISSUE 26): the TrainTicket-scale estimator, E=200
experts on the dense feed, held to the plain reference on the CPU at a
TrainTicket-shaped small size, and its configuration file held to what it
says of itself.

On the chip the benchmark's cell `tt-train-dense` makes comparison (i) at
the configuration's own widths in bfloat16 (chipbench/limits/); here it is
float32 at toy widths.  No number of this file is a device number.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.generators import corpus
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train as runner
from deeprest_tpu.config import Config, FeaturizeConfig, ModelConfig, TrainConfig
from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
from deeprest_tpu.models.qrnn import QuantileGRU
from deeprest_tpu.ops import pallas_gru
from deeprest_tpu.ops.quantile import pinball_loss
from deeprest_tpu.parallel.distributed import stage_plan
from deeprest_tpu.train import Trainer, prepare_dataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs", "trainticket-e200.json")
MIX = os.path.join(REPO, "chipbench", "traffic", "week-dense.json")
RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_026           # as large as the driver's


def _load(path):
    with open(path) as fh:
        return json.load(fh)


# -- (i) three Adam steps through the dense-feed superstep --------------------

# 5 components x 5 resources, every call path hot, float32
E, F, H, W, B = 25, 48, 8, 6, 4

# Program and reference both compute in float32 here, the reference at
# `highest`; what is left is the order of the sums (the program's batched
# einsum over all experts against the reference's map over one expert at a
# time, the hoisted projection, XLA's fusions).  Read at this size: 1.2e-7,
# 7.4e-7 and 8.9e-8.  The limits leave ten times that for another BLAS or
# thread count and no more: the reference with bfloat16 operands reads
# 1.2e-4, 4.3e-4 and 6.7e-4 here (forty times each limit and more), a
# wrong batch or a skipped step 1e-1 and more.
TOLERANCE = {"loss_rel_gap": 2e-6, "grad_norm_gap": 1e-5,
             "delta_norm_gap": 2e-6}


@pytest.fixture(scope="module")
def three_steps():
    """The runner's phases 1 to 3 and 6 at the small size, with the
    runner's own functions for the rows, the batches and the numbers."""
    model = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                        quantiles=QUANTILES, dropout_rate=0.5,
                        compute_dtype="float32")
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), device_data="always",
                       steps_per_superstep=8, log_every_steps=0)
    assert not tcfg.sparse_feed                      # the dense feed
    raw = corpus.generate(
        {"buckets": 400, "hot_paths": F, "nnz_lo": 3, "nnz_hi": 12,
         "day": 100, "resources": RESOURCES}, SEED,
        {"feature_dim": F, "num_metrics": E})
    assert (raw["traffic"].sum(axis=0) > 0).all()    # every column live
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=F)).freeze()
    data = FeaturizedData(
        traffic=raw["traffic"], resources=raw["resources"],
        invocations={"general": np.ones(len(raw["traffic"]), np.float32)},
        space=space)
    bundle = prepare_dataset(data, tcfg)
    starts = runner.check_starts(raw, tcfg, SEED, bundle)

    trainer = Trainer(Config(model=model, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    state = trainer.init_state(trainer.sample_input(bundle))
    key = jax.random.PRNGKey(tcfg.seed)
    seeded = ref.init_params(key, E, F, H, len(QUANTILES))
    assert ({k: v.shape for k, v in state.params.items()}
            == {k: v.shape for k, v in seeded.items()})
    state = state.replace(params={
        k: jax.device_put(seeded[k], state.params[k].sharding)
        for k in state.params})
    staged = trainer.stage_dataset(bundle)
    assert staged is not None and not hasattr(staged[0], "cols")
    assert staged[0].shape == (400, F)               # the dense base

    num_steps = -(-bundle.num_train_windows // B)
    s_len = trainer._superstep_len(num_steps)
    chunks = -(-num_steps // s_len)
    plan_starts = np.zeros((chunks, s_len, B), np.int32)
    plan_weights = np.zeros((chunks, s_len, B), np.float32)
    plan_starts[0, 0], plan_starts[1, 0], plan_starts[1, 1] = starts
    plan_weights[0, 0] = plan_weights[1, 0] = plan_weights[1, 1] = 1.0
    plan = stage_plan(trainer.mesh, plan_starts, plan_weights)

    state, losses0 = trainer._superstep(state, *staged, *plan, 0)
    grad_norm = {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
                 / (1 - ref.ADAM["b1"])
                 for k, v in state.opt_state[0].mu.items()}
    state, losses1 = trainer._superstep(state, *staged, *plan, 1)
    start = ref.init_params(key, E, F, H, len(QUANTILES))
    delta = {k: float(v) for k, v in ref.leaf_norms(
        {k: state.params[k] - start[k] for k in start}).items()}
    program = {"losses": [float(losses0[0]), float(losses1[0]),
                          float(losses1[1])],
               "grad_norm": grad_norm, "delta_norm": delta}
    assert int(state.step) == runner.STEPS_CHECKED

    reference = ref.train_three_steps(
        ref.init_params(key, E, F, H, len(QUANTILES)),
        runner.check_batches(raw, tcfg, starts), tcfg.seed, QUANTILES, 0.5,
        "f32")
    return runner.compare(program, reference), program, reference


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_dense_feed_superstep_against_the_reference(three_steps, number):
    gaps, program, reference = three_steps
    assert gaps[number] <= TOLERANCE[number], (gaps, program, reference)


# -- (ii) forward and gradients through a three-block expert grid -------------

# The kernel wants a lane-aligned hidden size, so H is the configuration's
# own; E=24 is three expert blocks of 8, as E=200 is twenty-five.
E_K, F_K, H_K, W_K, B_K = 24, 16, 128, 6, 4

# Interpret mode computes the kernel's float32 arithmetic on the CPU: the
# gap to the reference is the order of sums again (read: 1.9e-7 of the
# largest prediction, 8.4e-8 of a leaf's gradient norm); an expert block
# walked twice or left out would read 1e-2 and more.
KERNEL_TOLERANCE = {"forward": 5e-6, "gradients": 5e-6}


@pytest.fixture(scope="module")
def kernel_grid():
    assert pallas_gru.block_plan(E_K, W_K, B_K, H_K, jnp.float32)["e_blk"] == 8
    key = jax.random.PRNGKey(26)
    params = ref.init_params(key, E_K, F_K, H_K, len(QUANTILES))
    kx, ky = jax.random.split(jax.random.PRNGKey(27))
    x = jax.random.uniform(kx, (B_K, W_K, F_K), jnp.float32)
    y = jax.random.uniform(ky, (B_K, W_K, E_K), jnp.float32)
    model = QuantileGRU(config=ModelConfig(
        feature_dim=F_K, num_metrics=E_K, hidden_size=H_K,
        quantiles=QUANTILES, compute_dtype="float32",
        rnn_backend="pallas_interpret"))

    def program_loss(p):
        preds = model.apply({"params": p}, x, deterministic=True)
        return pinball_loss(preds, y, QUANTILES), preds

    def reference_loss(p):
        preds = ref.forward(p, x)
        return ref.pinball(preds, y, QUANTILES), preds

    (_, preds), grads = jax.value_and_grad(program_loss, has_aux=True)(params)
    (_, want), want_grads = jax.value_and_grad(
        reference_loss, has_aux=True)(params)
    norms = {k: float(v) for k, v in ref.leaf_norms(want_grads).items()}
    off = ref.leaf_norms({k: grads[k] - want_grads[k] for k in norms})
    median = float(np.median(list(norms.values())))
    return {
        "forward": float(jnp.max(jnp.abs(preds - want))
                         / jnp.max(jnp.abs(want))),
        "gradients": max(float(off[k]) / max(norms[k], median)
                         for k in norms),
    }


@pytest.mark.parametrize("what", sorted(KERNEL_TOLERANCE))
def test_three_block_expert_grid_against_the_reference(kernel_grid, what):
    assert kernel_grid[what] <= KERNEL_TOLERANCE[what], kernel_grid


# -- (iii) the configuration file ----------------------------------------------


def _parameters(model: ModelConfig) -> int:
    shapes = ref.param_shapes(model.num_metrics, model.feature_dim,
                              model.hidden_size, len(model.quantiles))
    return sum(int(np.prod(shape)) for shape, _ in shapes.values())


def _configs():
    cfg = _load(CONFIG)
    model = dict(cfg["model"], quantiles=tuple(cfg["model"]["quantiles"]))
    return cfg, ModelConfig(**model), TrainConfig(**cfg["train"])


def _check_parses():
    cfg, model, train = _configs()
    assert (model.num_metrics, model.feature_dim, model.hidden_size,
            model.num_layers, model.bidirectional, model.quantiles,
            model.dropout_rate, model.compute_dtype) == (
                200, 2048, 128, 1, True, QUANTILES, 0.5, "bfloat16")
    assert (train.batch_size, train.window_size, train.learning_rate,
            train.train_split) == (32, 60, 1e-3, 0.4)
    # the dense feed, and every other knob the program's default
    assert "sparse_feed" not in cfg["train"] and not train.sparse_feed
    assert train == TrainConfig(batch_size=32, window_size=60,
                                learning_rate=1e-3, train_split=0.4)
    assert cfg["chips"] == 1 and cfg["runners"] == ["train"]


def _check_parameter_count():
    cfg, model, _ = _configs()
    n = _parameters(model)
    assert f"{n:,} parameters" in cfg["deployment"]
    seen = jax.eval_shape(
        lambda: QuantileGRU(config=model).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 60, model.feature_dim))))
    assert sum(int(np.prod(v.shape))
               for v in jax.tree.leaves(seen["params"])) == n


def _check_deployment_bytes():
    cfg, model, _ = _configs()
    n = _parameters(model)
    # float32: the parameters; with gradients and Adam's two moments
    assert f"{4 * n / 1e9:.3f} GB in float32" in cfg["deployment"]
    assert f"{16 * n / 1e9:.3f} GB" in cfg["deployment"]
    assert 16 * n > 0.25 * 16.9e9                # over the driver's floor


def _check_reduced_and_assumed():
    cfg, _, _ = _configs()
    assert cfg["reduced"] == [] and cfg["reduced_why"] == {}
    assert set(cfg["assumed"]) == {"num_metrics", "feature_dim",
                                   "compute_dtype"}
    assert "TrainTicket" in cfg["source"] and "DeepRest" in cfg["source"]
    entry = {c["name"]: c for c in _load(
        os.path.join(REPO, "BENCHMARK.json"))["configs"]}[cfg["name"]]
    assert entry["reduced"] == [] and entry["file"] == os.path.relpath(
        CONFIG, REPO)


def _check_mix_follows():
    cfg, model, _ = _configs()
    params = _load(MIX)["params"]
    assert params["hot_paths"] == model.feature_dim      # every path live
    assert params["buckets"] == cfg["corpus_days"] * params["day"] == 10080
    assert params["resources"] == RESOURCES
    assert model.num_metrics % len(params["resources"]) == 0
    assert (params["nnz_lo"], params["nnz_hi"]) == (128, 512)


@pytest.mark.parametrize("check", [
    _check_parses, _check_parameter_count, _check_deployment_bytes,
    _check_reduced_and_assumed, _check_mix_follows],
    ids=lambda f: f.__name__[len("_check_"):])
def test_configuration_file(check):
    check()


# -- (iv) the kernels' plan at E=200 ---------------------------------------------


@pytest.mark.parametrize("experts, rows, training", [
    (200, 32, True),        # the cell's step
    (200, 128, False),      # the serving rungs of a bf16 predictor
    (200, 256, False),
    (40, 32, True),         # the cell beside it, unchanged
])
def test_block_plan_fits_and_divides(experts, rows, training):
    plan = pallas_gru.block_plan(experts, 60, rows, 128, jnp.bfloat16,
                                 training=training)
    assert plan["fits"], plan
    assert experts % plan["e_blk"] == 0 and plan["e_blk"] % 8 == 0, plan
    assert 60 % plan["t_blk"] == 0, plan
    if training:
        # 25 (or 5) expert blocks x 10 time blocks a call
        assert (plan["e_blk"], plan["t_blk"]) == (8, 6), plan
