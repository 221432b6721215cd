"""`endpoints-10k-live4k` (ISSUEs 38 and 39): the sparse feed on both sides
of the rule of its compact form, held to the plain reference on the CPU at a
small size, and the rule itself held to where ISSUE 39 moved it.

The deployment's guarantee: every nonzero call-path count reaches the
layer-0 sum and every step is plain Adam on every row of every leaf, so the
result is the dense model's to float tolerance whichever form of the feed
computes it.  Here the same seeded corpus is staged with a live set that pads
to exactly the rule's bound, `F // 2` (the compact form at its widest
table), and with one path more, which pads over it (the dense form: the
scatter `densify_coo` into F columns, the F-wide projection, whole-leaf
Adam), and three steps of the window's own compiled superstep are compared
with `chipbench/reference/qrnn_ref.py`.  On the chip the benchmark's cell
`tenk-train-live4k` makes the COMPACT side's comparison since ISSUE 39 (a
table of 4,096 of 10,240, under the bound of 5,120) at the configuration's
widths in bfloat16 (chipbench/limits/), and since ISSUE 44 the cell
`tenk-train-alllive` makes the DENSE side's (every one of the 10,240 columns
live: the `alllive` side here, with the control that cell's limits are held
by); `test_the_model_axis_decides_before_the_rule` guards the dense form's
other cause, which no cell runs.  Here it is float32 at toy widths.  No
number of this file is a device number.
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

# 2 components x 5 resources over 512 hashed call paths, float32.  256 live
# paths pad to 256 = F // 2, the widest table the rule admits; 257 pad to
# 512, over it.
E, F, H, W, B, K = 10, 512, 8, 6, 4, 16
DIMS = (E, F, H, len(QUANTILES))
BOUND = F // 2
# `alllive` (ISSUE 44, the cell `tenk-train-alllive`): every column of F is
# hot and carries traffic, the plainest corpus that takes the dense form
SIDES = {"compact": 256, "dense": 257, "alllive": F}
PADDED = {"compact": 256, "dense": 512, "alllive": 512}
FORM = {"compact": "compact", "dense": "dense", "alllive": "dense"}
# distinct call paths a bucket: all 512 columns are hit in 400 buckets only
# with more of them a bucket (8 to 15, under K)
NNZ = {"compact": (3, 12), "dense": (3, 12), "alllive": (8, 16)}
KINDS = ("live", "padded", "bound", "contracted", "total")

# Program and reference both compute in float32 here, the reference at
# `highest`: what is left is the order of the sums (the compact form leaves
# exact zeros out of the layer-0 sum, the dense form sums over all F).  Read
# at this size: 1.2e-7 / 1.2e-7 (loss), 5.2e-7 / 8.7e-7 (first gradient),
# 8.2e-8 / 8.8e-8 (the leaves' change), compact / dense.  The limits leave
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


def _three_steps(name):
    """The `train` runner's phases 1 to 3 and 6 at the small size, with the
    runner's own functions for the rows, the batches and the numbers."""
    hot = SIDES[name]
    mcfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=K, steps_per_superstep=8,
                       log_every_steps=0)
    raw = corpus.generate(
        {"buckets": 400, "hot_paths": hot, "nnz_lo": NNZ[name][0],
         "nnz_hi": NNZ[name][1], "day": 100, "resources": RESOURCES}, SEED,
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
    return {"name": name, "form": FORM[name], "live": live,
            "base": staged[0], "tags": tags, "gauge": gauge, "line": line,
            "gaps": runner.compare(program, reference),
            "numbers": (program, reference)}


_SOUND = {}         # what `side` made, by name, for the case that needs one


@pytest.fixture(scope="module", params=sorted(SIDES))
def side(request):
    _SOUND[request.param] = _three_steps(request.param)
    return _SOUND[request.param]


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_superstep_against_the_reference_on_either_side(side, number):
    assert side["gaps"][number] <= TOLERANCE[number], (side["gaps"],
                                                       side["numbers"])


def test_the_corpus_was_staged_in_the_form_its_side_names(side):
    base = side["base"]
    assert isinstance(base, SparseBase) and base.capacity == F
    assert side["live"] == SIDES[side["name"]]      # every hot path was hit
    if side["form"] == "compact":
        assert base.width == BOUND == len(np.asarray(base.live))
    else:
        assert base.live is None and base.width == F


def test_the_stage_span_says_what_the_rule_weighed(side):
    tags = side["tags"]
    padded = PADDED[side["name"]]
    assert {k: tags[k] for k in ("form", "live", "padded", "bound")} == {
        "form": side["form"], "live": side["live"], "padded": padded,
        "bound": BOUND}
    assert tags["width"] == (padded if side["form"] == "compact" else F)
    assert tags["restage"] is False


def test_the_gauge_has_five_kinds(side):
    compact = side["form"] == "compact"
    assert side["gauge"] == {
        "live": side["live"], "padded": PADDED[side["name"]],
        "bound": BOUND, "contracted": BOUND if compact else F, "total": F}


def test_the_set_up_line_carries_the_form(side):
    line, live = side["line"], side["live"]
    want = {"compact": f"sparse feed compact ({live} live call paths of 512, "
                       "padded to 256, bound 256, 256 contracted)",
            "dense": f"sparse feed dense ({live} live call paths of 512, "
                     f"padded to {PADDED[side['name']]}, bound 256, 512 "
                     "contracted)"}
    assert line.startswith("set-up: ") and "\n" not in line
    assert want[side["form"]] in line
    assert line.index("stage ") < line.index("sparse feed ")


def test_the_dense_forms_dropped_columns_control_is_seen(monkeypatch):
    """ISSUE 44's control of `tenk-train-alllive` at this size: the feed
    thresholds before the program's guard (the counts of the less-hit half
    of the columns never reach the staged rows) and the form stays dense,
    which `control_on_chip_live4k.py`'s table of the most-hit half cannot
    show where half of F pads over the bound.  The dropped columns' rows of
    the w_ih leaves never move: the leaves' change reads a thousand times
    the sound gap, at a w_ih leaf."""
    import deeprest_tpu.train.trainer as T
    from chipbench.tests import control_on_chip_alllive

    side = _SOUND.get("alllive") or _three_steps("alllive")
    monkeypatch.setattr(T, "stage_sparse_base", T.stage_sparse_base)
    control_on_chip_alllive.most_hit_half_only_dense()
    dropped = _three_steps("alllive")
    assert dropped["base"].live is None and dropped["gauge"]["live"] == F
    assert dropped["gaps"]["delta_norm_gap"] > 1000 * side["gaps"][
        "delta_norm_gap"], (dropped["gaps"], side["gaps"])
    assert dropped["gaps"]["delta_norm_gap_leaf"] in ("gru_fwd_w_ih",
                                                      "gru_bwd_w_ih")


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


# -- either side of the bound through `stage_dataset`, and across a restage --


@pytest.mark.parametrize("hot, form, padded", [(256, "compact", 256),
                                               (257, "dense", 512)])
def test_a_live_set_at_the_bound_and_one_path_over_it(hot, form, padded):
    """ISSUE 39's edge on another corpus than the `side` fixture's: the
    widest live set the rule admits and one path more, and the four places
    that say which form the staging took."""
    from test_live_columns import _bundle, _corpus, _trainer

    staged, tags = _staged_with_tags(_trainer(),
                                     _bundle(*_corpus(hot)[:3]))
    contracted = padded if form == "compact" else F
    assert (staged[0].live is None) == (form == "dense")
    assert staged[0].width == contracted
    assert {k: tags[k] for k in ("form", "live", "padded", "bound",
                                 "width")} == {
        "form": form, "live": hot, "padded": padded, "bound": BOUND,
        "width": contracted}
    assert _gauge() == {"live": hot, "padded": padded, "bound": BOUND,
                        "contracted": contracted, "total": F}
    assert (f"sparse feed {form} ({hot} live call paths of {F}, padded to "
            f"{padded}, bound {BOUND}, {contracted} contracted)"
            ) in obs_setup.format_setup(obs_setup.setup_table())


def test_a_restage_to_the_widest_table_and_back_reuses_both_executables():
    """What `stream` meets when a tenant's live set grows past the old bound
    and shrinks again, on ONE trainer and state: a table of 128, one of 256
    (the widest the rule admits at this F: `tenk-train-live4k`'s 4,096 at
    F = 10,240 after a week at 256), then the first corpus again.  Each
    width compiles the superstep once and the way back compiles nothing;
    the rows that left the table keep their moments and are stepped by the
    off-table pass; a row no corpus ever lit stays where `init_state` put
    it, bit for bit."""
    from test_live_columns import W as w_lc
    from test_live_columns import _bundle, _corpus, _trainer

    narrow, wide = _corpus(100), _corpus(200)
    bundles = {"narrow": _bundle(*narrow[:3]), "wide": _bundle(*wide[:3])}
    trainer = _trainer(steps_per_superstep=8)
    state = trainer.init_state(np.zeros((1, w_lc, F), np.float32), seed=0)
    start = np.asarray(state.params["gru_fwd_w_ih"])
    rows = REGISTRY.get("deeprest_train_optimizer_rows")
    seen = []
    for name in ("narrow", "wide", "narrow"):
        staged, tags = _staged_with_tags(trainer, bundles[name])
        before = np.asarray(state.params["gru_fwd_w_ih"])
        state, loss = trainer.train_epoch(state, bundles[name],
                                          np.random.default_rng(0),
                                          staged=staged)
        assert np.isfinite(loss)
        moved = np.flatnonzero(
            (np.asarray(state.params["gru_fwd_w_ih"]) != before
             ).any(axis=(0, 2)))
        seen.append({
            "form": tags["form"], "width": tags["width"],
            "restage": tags["restage"],
            "executables": trainer._superstep._cache_size(),
            "stale": int(rows.value(kind="stale")),
            "table": np.asarray(staged[0].live), "moved": moved})
    assert [(e["form"], e["width"], e["restage"], e["executables"])
            for e in seen] == [("compact", 128, False, 1),
                               ("compact", 256, True, 2),
                               ("compact", 128, True, 2)]
    first, second, third = seen
    assert first["stale"] == 0 and set(first["moved"]) <= set(first["table"])
    # the narrow week's rows that the wide table does not hold
    left = np.setdiff1d(narrow[3], second["table"])
    assert second["stale"] == len(left) > 0
    assert set(left) <= set(second["moved"])
    # back on the narrow table: the wide week's rows are stale and stepped
    left = np.setdiff1d(np.union1d(narrow[3], wide[3]), third["table"])
    assert third["stale"] == len(left) > 0
    assert set(left) <= set(third["moved"])
    never = np.setdiff1d(np.arange(F), np.union1d(first["table"],
                                                  second["table"]))
    assert len(never) and np.array_equal(
        np.asarray(state.params["gru_fwd_w_ih"])[:, never], start[:, never])


# -- the rule is where ISSUE 39 put it --------------------------------------


def _table_by_the_rule(live, capacity):
    """`compact_table` written out again, with the bound as a number and
    not through `compact_rule`: a half of F since ISSUE 39."""
    u_pad = max(MIN_COMPACT_WIDTH, 1 << max(len(live) - 1, 0).bit_length())
    if u_pad > capacity // 2:
        return None
    dead = np.setdiff1d(np.arange(capacity, dtype=np.int32), live,
                        assume_unique=True)
    return np.sort(np.concatenate([live, dead[:u_pad - len(live)]])
                   ).astype(np.int32)


@pytest.mark.parametrize("capacity, sizes", [
    (512, range(0, 513)),
    (2048, range(0, 2049, 7)),
    (10240, (1, 127, 128, 129, 256, 257, 2047, 2048, 2049, 2560, 2561,
             4095, 4096, 4097, 5120, 5121, 8192, 10239, 10240)),
    (4 * MIN_COMPACT_WIDTH - 1, (1, 64, 128, 129)),
    (2 * MIN_COMPACT_WIDTH - 1, (1, 64, 128)),
])
def test_compact_table_is_the_parents_for_every_live_set(capacity, sizes):
    rng = np.random.default_rng(capacity)
    order = rng.permutation(capacity).astype(np.int32)
    for n in sizes:
        live = np.sort(order[:n])
        want = _table_by_the_rule(live, capacity)
        got = compact_table(live, capacity)
        padded, bound = compact_rule(n, capacity)
        assert bound == capacity // 2 and padded >= max(n, MIN_COMPACT_WIDTH)
        assert padded & (padded - 1) == 0
        if want is None:
            assert got is None and padded > bound, (capacity, n)
        else:
            assert got.dtype == np.int32 and np.array_equal(got, want)
            assert len(got) == padded <= bound
            assert 2 * len(got) <= capacity     # never a table as wide as F


def test_the_bound_at_the_10k_width_is_where_the_cell_says():
    """`tenk-train-live4k`: 4,096 live paths of 10,240 pad to 4,096, under
    the bound of 5,120, and that is the widest table the rule admits: one
    path more pads to 8,192, the first width that is not compact."""
    assert compact_rule(4096, 10240) == (4096, 5120)
    assert compact_rule(2049, 10240) == (4096, 5120)
    assert compact_rule(4097, 10240) == (8192, 5120)
    assert compact_rule(256, 10240) == (256, 5120)
    order = np.random.default_rng(39).permutation(10240).astype(np.int32)
    assert len(compact_table(np.sort(order[:4096]), 10240)) == 4096
    assert compact_table(np.sort(order[:4097]), 10240) is None
    assert compact_table(np.sort(order[:8192]), 10240) is None
    assert compact_table(np.arange(10240, dtype=np.int32), 10240) is None


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
    assert padded <= bound              # the rule sends it to the compact form
    assert compact_rule(mine["params"]["hot_paths"] + 1,
                        model["feature_dim"])[0] > bound    # its widest table
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
        assert "tenk-train-live4k" in metrics[name]["workloads"], name
    dead = metrics["proj_dead_columns_pct.train"]
    # later cells are appended (ISSUE 40's); what was there keeps its place
    assert dead["workloads"][:4] == ["tenk-train-sparse", "tenk-train-dp4",
                                     "tenk-retrain-drift", "tenk-train-live4k"]
    assert (dead["unit"], dead["better"], dead["moves"], dead["source"]) == (
        "%", "lower", "train_steps_per_s", "program_counter")
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    limits = _load("chipbench", "limits", "tenk-train-live4k.json")
    assert set(limits["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                     "delta_norm_gap"}


def test_the_all_live_cell_is_endpoints_10k_on_a_mix_with_no_dead_column():
    """`tenk-train-alllive` (ISSUE 44): the accepted configuration
    `endpoints-10k` x a mix that is `week-sparse`'s in everything but the
    live set, through the `train` runner and the `corpus` generator as they
    stand; the rule sends it to the dense form; the cell is in the lists of
    the metrics it reports, and the benchmark holds ten cells (ISSUE 54's
    the tenth), two of them on four chips."""
    bench = _load("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}["tenk-train-alllive"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "endpoints-10k", "week-alllive", 1)
    assert len(cell["why"]) <= 200
    mix = _load("chipbench", "traffic", "week-alllive.json")
    base = _load("chipbench", "traffic", "week-sparse.json")
    assert (mix["runner"], mix["generator"]) == ("train", "corpus")
    model = _load("chipbench", "configs", "endpoints-10k.json")["model"]
    f = model["feature_dim"]
    assert mix["params"] == dict(base["params"], hot_paths=f) and f == 10240
    padded, bound = compact_rule(f, f)
    assert (padded, bound) == (16384, 5120) and padded > bound
    # a few columns short of F still pads over the bound: never a table
    assert compact_rule(f - 64, f)[0] > bound
    assert compact_table(np.arange(f - 64, dtype=np.int32), f) is None
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    from test_mesh_dp4 import LISTED_IN_EVERY_TRAIN_CELL

    listed = ("train_steps_per_s",
              "hbm_peak_gb") + LISTED_IN_EVERY_TRAIN_CELL
    for name in listed:
        # ISSUE 44's two cells, in the order appended, then ISSUE 48's and
        # ISSUE 54's (whose window runs this cell's dense form)
        assert metrics[name]["workloads"][-4:] == [
            "tenk-train-live4k-dp4", "tenk-train-alllive",
            "tenk-train-accum8", "tenk-retrain-growing"], name
    for name, m in metrics.items():
        # (ISSUE 48's `updates_per_epoch.train` lists every cell)
        if name not in listed + ("updates_per_epoch.train",):
            assert "tenk-train-alllive" not in m.get("workloads", ()), name
    assert len(bench["workloads"]) == 10
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    limits = _load("chipbench", "limits", "tenk-train-alllive.json")
    assert limits["cell"] == "tenk-train-alllive"
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
