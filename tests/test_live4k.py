"""`endpoints-10k-live4k` (ISSUE 38): the sparse feed on both sides of the
rule of its compact form, held to the plain reference on the CPU at a small
size, and the rule itself held to what it was.

The deployment's guarantee: every nonzero call-path count reaches the
layer-0 sum and every step is plain Adam on every row of every leaf, so the
result is the dense model's to float tolerance whichever form of the feed
computes it.  Here the same seeded corpus is staged with a live set that pads
to exactly `F // 4` (the compact form) and with one that pads over it (the
dense form: the scatter `densify_coo` into F columns, the F-wide
projection, whole-leaf Adam), and three steps of the window's own compiled
superstep are compared with `chipbench/reference/qrnn_ref.py`.  On the chip
the benchmark's cell `tenk-train-live4k` makes the dense side's comparison
at the configuration's widths in bfloat16 (chipbench/limits/); here it is
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
from deeprest_tpu.config import (
    Config, FeaturizeConfig, MeshConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.ops.densify import (
    MIN_COMPACT_WIDTH, SparseBase, compact_rule, compact_table,
)
from deeprest_tpu.parallel.distributed import stage_plan
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import Trainer, prepare_dataset
from test_obs_layers import _recorded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_038           # as large as the driver's

# 2 components x 5 resources over 512 hashed call paths, float32.  128 live
# paths pad to 128 = F // 4, the widest table the rule admits; 200 pad to
# 256, over it.
E, F, H, W, B, K = 10, 512, 8, 6, 4, 16
DIMS = (E, F, H, len(QUANTILES))
SIDES = {"compact": 128, "dense": 200}
KINDS = ("live", "padded", "bound", "contracted", "total")

# Program and reference both compute in float32 here, the reference at
# `highest`: what is left is the order of the sums (the compact form leaves
# exact zeros out of the layer-0 sum, the dense form sums over all F).  Read
# at this size: 1.3e-7 / 1.6e-7 (loss), 3.3e-7 / 3.4e-7 (first gradient),
# 6.6e-8 / 6.2e-8 (the leaves' change), compact / dense.  The limits leave
# ten times that and no more, test_trainticket_config.py's rule: the
# reference with bfloat16 operands reads 1e-4 and more at such a size.
TOLERANCE = {"loss_rel_gap": 2e-6, "grad_norm_gap": 5e-6,
             "delta_norm_gap": 1e-6}


def _gauge():
    gauge = REGISTRY.get(obs_setup.PROJECTION_COLUMNS)
    return {k: int(gauge.value(kind=k)) for k in KINDS}


def _staged_with_tags(trainer, bundle):
    """``stage_dataset(bundle)`` with the span recorder on: what it staged
    and its one ``train.stage`` span's tags."""
    staged = []
    spans = _recorded(lambda: staged.append(trainer.stage_dataset(bundle)))
    (span,) = [s for s in spans if s.name == "train.stage"]
    return staged[0], span.tags


@pytest.fixture(scope="module", params=sorted(SIDES))
def side(request):
    """The `train` runner's phases 1 to 3 and 6 at the small size, with the
    runner's own functions for the rows, the batches and the numbers."""
    hot = SIDES[request.param]
    mcfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=K, steps_per_superstep=8,
                       log_every_steps=0)
    raw = corpus.generate(
        {"buckets": 400, "hot_paths": hot, "nnz_lo": 3, "nnz_hi": 12,
         "day": 100, "resources": RESOURCES}, SEED,
        {"feature_dim": F, "num_metrics": E})
    live = int(raw["traffic"].any(axis=0).sum())
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=F)).freeze()
    data = FeaturizedData(
        traffic=raw["traffic"], resources=raw["resources"],
        invocations={"general": np.ones(len(raw["traffic"]), np.float32)},
        space=space)
    bundle = prepare_dataset(data, tcfg)
    assert bundle.is_sparse
    starts = runner.check_starts(raw, tcfg, SEED, bundle)

    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    state = trainer.init_state(trainer.sample_input(bundle))
    key = jax.random.PRNGKey(tcfg.seed)
    seeded = ref.init_params(key, *DIMS)
    state = state.replace(params={
        k: jax.device_put(seeded[k], state.params[k].sharding)
        for k in state.params})
    staged, tags = _staged_with_tags(trainer, bundle)
    gauge, line = _gauge(), obs_setup.format_setup(obs_setup.setup_table())

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
    start = ref.init_params(key, *DIMS)
    delta = {k: float(v) for k, v in ref.leaf_norms(
        {k: state.params[k] - start[k] for k in start}).items()}
    program = {"losses": [float(losses0[0]), float(losses1[0]),
                          float(losses1[1])],
               "grad_norm": grad_norm, "delta_norm": delta}
    assert int(state.step) == runner.STEPS_CHECKED

    reference = ref.train_three_steps(
        ref.init_params(key, *DIMS), runner.check_batches(raw, tcfg, starts),
        tcfg.seed, QUANTILES, 0.5, "f32")
    return {"form": request.param, "live": live, "base": staged[0],
            "tags": tags, "gauge": gauge, "line": line,
            "gaps": runner.compare(program, reference),
            "numbers": (program, reference)}


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_superstep_against_the_reference_on_either_side(side, number):
    assert side["gaps"][number] <= TOLERANCE[number], (side["gaps"],
                                                       side["numbers"])


def test_the_corpus_was_staged_in_the_form_its_side_names(side):
    base = side["base"]
    assert isinstance(base, SparseBase) and base.capacity == F
    assert side["live"] == SIDES[side["form"]]      # every hot path was hit
    if side["form"] == "compact":
        assert base.width == F // 4 == len(np.asarray(base.live))
    else:
        assert base.live is None and base.width == F


def test_the_stage_span_says_what_the_rule_weighed(side):
    tags = side["tags"]
    padded = {"compact": 128, "dense": 256}[side["form"]]
    assert {k: tags[k] for k in ("form", "live", "padded", "bound")} == {
        "form": side["form"], "live": side["live"], "padded": padded,
        "bound": F // 4}
    assert tags["width"] == (padded if side["form"] == "compact" else F)
    assert tags["restage"] is False


def test_the_gauge_has_five_kinds(side):
    compact = side["form"] == "compact"
    assert side["gauge"] == {
        "live": side["live"], "padded": 128 if compact else 256,
        "bound": F // 4, "contracted": 128 if compact else F, "total": F}


def test_the_set_up_line_carries_the_form(side):
    line, live = side["line"], side["live"]
    want = {"compact": f"sparse feed compact ({live} live call paths of 512, "
                       "padded to 128, bound 128, 128 contracted)",
            "dense": f"sparse feed dense ({live} live call paths of 512, "
                     "padded to 256, bound 128, 512 contracted)"}
    assert line.startswith("set-up: ") and "\n" not in line
    assert want[side["form"]] in line
    assert line.index("stage ") < line.index("sparse feed ")


def test_the_model_axis_decides_before_the_rule():
    """F sharded over the mesh's `model` axis: the dense form whatever the
    live set, and the span and the gauge say who decided."""
    from test_live_columns import _bundle, _corpus, _trainer

    narrow = _corpus(100)
    sharded = _trainer(mesh=make_mesh(MeshConfig(data=1, model=2)))
    staged, tags = _staged_with_tags(sharded, _bundle(*narrow[:3]))
    assert staged[0].live is None
    assert (tags["form"], tags["live"], tags["padded"], tags["bound"]) == (
        "dense", 100, 128, "model_axis")
    assert _gauge() == {"live": 100, "padded": 128, "bound": 0,
                        "contracted": 512, "total": 512}
    assert "bound the model axis" in obs_setup.format_setup(
        obs_setup.setup_table())


def test_a_dense_corpus_has_no_form():
    """The dense staged feed sets none of it: no tag, and a fresh registry
    gives a table without `sparse_feed`."""
    from conftest import make_series_buckets
    from deeprest_tpu.data.featurize import featurize_buckets

    tc = TrainConfig(batch_size=B, window_size=W, device_data="always")
    data = featurize_buckets(make_series_buckets(60, seed=5),
                             FeaturizeConfig(hash_features=True, capacity=F))
    bundle = prepare_dataset(data, tc)
    trainer = Trainer(Config(model=ModelConfig(hidden_size=H), train=tc),
                      bundle.feature_dim, bundle.metric_names)
    _, tags = _staged_with_tags(trainer, bundle)
    assert not {"form", "live", "padded", "bound"} & set(tags)


def test_no_gauge_no_sparse_feed_in_the_table(monkeypatch):
    from deeprest_tpu.obs import metrics

    monkeypatch.setattr(obs_setup, "REGISTRY", metrics.MetricsRegistry())
    assert "sparse_feed" not in obs_setup.setup_table()
    obs_setup.REGISTRY.gauge(obs_setup.PROJECTION_COLUMNS,
                             labelnames=("kind",))
    assert "sparse_feed" not in obs_setup.setup_table()


# -- the rule is what it was ----------------------------------------------


def _parents_table(live, capacity):
    """`compact_table` as the parent commit had it, line for line."""
    u_pad = max(MIN_COMPACT_WIDTH, 1 << max(len(live) - 1, 0).bit_length())
    if u_pad > capacity // 4:
        return None
    dead = np.setdiff1d(np.arange(capacity, dtype=np.int32), live,
                        assume_unique=True)
    return np.sort(np.concatenate([live, dead[:u_pad - len(live)]])
                   ).astype(np.int32)


@pytest.mark.parametrize("capacity, sizes", [
    (512, range(0, 513)),
    (2048, range(0, 2049, 7)),
    (10240, (1, 127, 128, 129, 256, 257, 2047, 2048, 2049, 2560, 2561,
             4095, 4096, 4097, 8192, 10239, 10240)),
    (4 * MIN_COMPACT_WIDTH - 1, (1, 64, 128)),
])
def test_compact_table_is_the_parents_for_every_live_set(capacity, sizes):
    rng = np.random.default_rng(capacity)
    order = rng.permutation(capacity).astype(np.int32)
    for n in sizes:
        live = np.sort(order[:n])
        want, got = _parents_table(live, capacity), compact_table(live,
                                                                  capacity)
        padded, bound = compact_rule(n, capacity)
        assert bound == capacity // 4 and padded >= max(n, MIN_COMPACT_WIDTH)
        assert padded & (padded - 1) == 0
        if want is None:
            assert got is None and padded > bound, (capacity, n)
        else:
            assert got.dtype == np.int32 and np.array_equal(got, want)
            assert len(got) == padded <= bound


def test_the_bound_at_the_10k_width_is_where_the_cell_says():
    """`tenk-train-live4k`: 4,096 live paths of 10,240 pad to 4,096, over the
    bound of 2,560; 2,048 is the widest live set that stays compact."""
    assert compact_rule(4096, 10240) == (4096, 2560)
    assert compact_rule(2048, 10240) == (2048, 2560)
    assert compact_rule(2049, 10240) == (4096, 2560)
    assert compact_rule(256, 10240) == (256, 2560)


# -- the configuration and the cell, as files ------------------------------


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as fh:
        return json.load(fh)


def test_the_configuration_is_endpoints_10k_with_a_wide_live_set():
    mine = _load("chipbench", "configs", "endpoints-10k-live4k.json")
    base = _load("chipbench", "configs", "endpoints-10k.json")
    assert mine["model"] == base["model"] and mine["train"] == base["train"]
    assert mine["runners"] == ["train"]
    assert mine["reduced"] == ["chips", "corpus_days"] == base["reduced"]
    assert set(mine["assumed"]) == set(base["assumed"]) | {"live_paths"}
    for words in ("no column dropped", "plain Adam on every row"):
        assert words in mine["deployment"]
    ModelConfig(**dict(mine["model"],
                       quantiles=tuple(mine["model"]["quantiles"])))
    TrainConfig(**mine["train"])


def test_the_mix_is_week_sparse_but_for_the_live_set():
    mine = _load("chipbench", "traffic", "week-live4k.json")
    base = _load("chipbench", "traffic", "week-sparse.json")
    assert (mine["runner"], mine["generator"]) == ("train", "corpus")
    assert mine["params"] == dict(base["params"], hot_paths=4096)
    model = _load("chipbench", "configs", "endpoints-10k-live4k.json")["model"]
    padded, bound = compact_rule(mine["params"]["hot_paths"],
                                 model["feature_dim"])
    assert padded > bound               # the rule sends it to the dense form
    assert mine["params"]["nnz_hi"] - 1 <= 64


def test_the_cell_and_its_metric_are_in_the_contract():
    bench = _load("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}["tenk-train-live4k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "endpoints-10k-live4k", "week-live4k", 1)
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("train_steps_per_s", "hbm_peak_gb", "proj_columns_pct.train",
                 "adam_rows_pct.train", "init_state_s.train",
                 "compile_s.train", "compilations.train",
                 "init_state_peak_gb.train", "steady_hbm_gb.train",
                 "gru_kernel_vmem_pct.train", "dropout_draws_per_step.train",
                 "proj_dead_columns_pct.train"):
        assert metrics[name]["workloads"][-1] == "tenk-train-live4k", name
    dead = metrics["proj_dead_columns_pct.train"]
    assert dead["workloads"] == ["tenk-train-sparse", "tenk-train-dp4",
                                 "tenk-retrain-drift", "tenk-train-live4k"]
    assert (dead["unit"], dead["better"], dead["moves"], dead["source"]) == (
        "%", "lower", "train_steps_per_s", "program_counter")
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    limits = _load("chipbench", "limits", "tenk-train-live4k.json")
    assert set(limits["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                     "delta_norm_gap"}


@pytest.mark.parametrize("live, contracted, expected", [
    (256, 256, 0.0), (192, 256, 0.625), (4096, 10240, 60.0),
    (10240, 10240, 0.0)])
def test_the_dead_columns_reader(monkeypatch, live, contracted, expected):
    from chipbench.readers import proj_dead_columns
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    assert proj_dead_columns.dead_pct({}) is None           # no gauge
    gauge = fresh.gauge(obs_setup.PROJECTION_COLUMNS, labelnames=("kind",))
    assert proj_dead_columns.dead_pct({}) is None           # never set
    # the three kinds a program has had since PR 25, and no other
    for kind, n in (("live", live), ("contracted", contracted),
                    ("total", 10240)):
        gauge.set(n, kind=kind)
    assert proj_dead_columns.dead_pct({}) == pytest.approx(expected)
