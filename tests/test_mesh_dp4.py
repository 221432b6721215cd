"""`endpoints-10k-dp4` (ISSUE 31): the estimator trained data-parallel over a
mesh ``data=4``, held on the CPU's virtual devices to the plain one-device
reference on the GLOBAL batch and to the same program at ``data=1``; what
the program records of its collectives; the benchmark's three readers of
them on a recorded slice of a four-chip trace; and the cell's files.  Since
ISSUE 44 (`endpoints-10k-live4k-dp4`) the comparisons with the reference also
run at the widest table the compact form's rule admits (`HOT["widest"]`).

On the chips the benchmark's cell `tenk-train-dp4` makes the comparison at
the configuration's own widths in bfloat16 (chipbench/limits/); here it is
float32 at toy widths.  No number of this file is a device number.
"""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import trace_reduce
from chipbench.generators import corpus
from chipbench.readers import collectives
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train_mesh as runner
from deeprest_tpu import obs
from deeprest_tpu.config import (
    Config, FeaturizeConfig, MeshConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
from deeprest_tpu.obs import profiler
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.parallel.distributed import stage_plan
from deeprest_tpu.train import Trainer, prepare_dataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLICE = os.path.join(REPO, "chipbench", "tests", "data",
                     "recorded_v5e_dp4_step.json")
RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_031           # as large as the driver's

# one component x 5 resources over 512 hashed call paths of which 16 are
# live (so the feed takes the compact form), a global batch of 16
E, F, H, W, B = 5, 512, 16, 8, 16

# Program and reference both compute in float32 here, the reference at
# `highest`; what is left is the order of the sums.  Beside the one-device
# comparison's causes (the batched einsum over experts against a map over
# one at a time, the hoisted projection, XLA's fusions) the mesh adds one:
# each chip sums its quarter of the batch and the all-reduce adds the four
# partial sums.  Read at this size: 1.9e-7, 8.4e-7 and 8.8e-8 under
# `data=4` (9.4e-8, 8.4e-7, 9.2e-8 under `data=1`; the two against each
# other 9.4e-8, 1.6e-7, 9.2e-8).  The limits leave about ten times that for
# another BLAS or thread count and no more: the last chip's rows left out
# of the mean read 4.2e-2, 1.3e-2 and 3.6e-2, the reference with bfloat16
# operands 5.5e-5, 5.1e-4 and 1.9e-4 (27, 51 and 97 times the limits).
TOLERANCE = {"loss_rel_gap": 2e-6, "grad_norm_gap": 1e-5,
             "delta_norm_gap": 2e-6}


# ISSUE 44 (`endpoints-10k-live4k-dp4`): the same program at the widest table
# the rule of the compact form admits, F // 2 columns, where the comparisons
# above run at a table of 128 (16 live paths padded to the least width): as
# `tenk-train-live4k-dp4`'s 4,096 of 10,240 is to `tenk-train-dp4`'s 256.
HOT = {"narrow": 16, "widest": F // 2}


def _corpus(hot=HOT["narrow"]):
    model = {"feature_dim": F, "num_metrics": E}
    raw = corpus.generate({"buckets": 400, "hot_paths": hot, "nnz_lo": 2,
                           "nnz_hi": 6, "day": 100, "resources": RESOURCES},
                          SEED, model)
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=F)).freeze()
    data = FeaturizedData(
        traffic=raw["traffic"], resources=raw["resources"],
        invocations={"general": np.ones(len(raw["traffic"]), np.float32)},
        space=space)
    return raw, data


def _three_steps(data_axis: int, raw, data, leave_out_a_chip=False):
    """The runner's phases 2 and 3 at the small size under ``data``:
    (the numbers `correct` compares, the trainer, its state, the bundle,
    what was staged)."""
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=8, log_every_steps=0)
    config = Config(
        model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                          quantiles=QUANTILES, dropout_rate=0.5,
                          compute_dtype="float32"),
        train=tcfg, mesh=MeshConfig(data=data_axis))
    bundle = prepare_dataset(data, tcfg)
    starts = runner.check_starts(raw, tcfg, SEED, bundle)
    trainer = Trainer(config, bundle.feature_dim, bundle.metric_names)
    assert trainer.mesh.shape["data"] == data_axis
    state = trainer.init_state(trainer.sample_input(bundle))
    key = jax.random.PRNGKey(tcfg.seed)
    seeded = ref.init_params(key, E, F, H, len(QUANTILES))
    state = state.replace(params={
        k: jax.device_put(seeded[k], state.params[k].sharding)
        for k in state.params})
    staged = trainer.stage_dataset(bundle)
    assert staged[0].live is not None                # the compact form
    num_steps = -(-bundle.num_train_windows // B)
    plans = runner.check_plans(trainer, starts, num_steps)
    if leave_out_a_chip:                             # the last chip's rows
        for _, weights in plans:
            weights[..., -B // data_axis:] = 0.0
    plans = [stage_plan(trainer.mesh, *plan) for plan in plans]
    state, losses0 = trainer._superstep(state, *staged, *plans[0], 0)
    grad_norm = {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
                 / (1 - ref.ADAM["b1"])
                 for k, v in state.opt_state[0].mu.items()}
    state, losses1 = trainer._superstep(state, *staged, *plans[1], 0)
    assert int(state.step) == runner.STEPS_CHECKED
    start = ref.init_params(key, E, F, H, len(QUANTILES))
    delta = {k: float(v) for k, v in ref.leaf_norms(
        {k: state.params[k] - start[k] for k in start}).items()}
    program = {"losses": [float(losses0[0]), float(losses1[0]),
                          float(losses1[1])],
               "grad_norm": grad_norm, "delta_norm": delta}
    return program, trainer, state, bundle, staged, (tcfg, starts, key)


def _runs(hot, meshes):
    raw, data = _corpus(hot)
    out = {d: _three_steps(d, raw, data) for d in meshes}
    tcfg, starts, key = out[4][5]
    out["reference"] = ref.train_three_steps(
        ref.init_params(key, E, F, H, len(QUANTILES)),
        runner.check_batches(raw, tcfg, starts), tcfg.seed, QUANTILES, 0.5,
        "f32")
    out["corpus"] = raw, data
    return out


@pytest.fixture(scope="module")
def runs():
    return _runs(HOT["narrow"], (4, 1))


def _ring_at_any_width(patch):
    """`tenk-train-live4k-dp4`'s side of `sharding.ring_scatters` at this
    file's toy widths: the w_ih gradients go round the ring (ISSUE 47)."""
    from deeprest_tpu.parallel import sharding

    patch.setattr(sharding, "RING_MIN_HOP_BYTES", 0)


@pytest.fixture(scope="module")
def widest():
    """The four-device compile at the rule's bound, once for its cases; as
    on the chips at that table, the weight gradients go round the ring
    (`runs`, the narrow table, leaves them to the partitioner, as
    `tenk-train-dp4` does)."""
    with pytest.MonkeyPatch.context() as patch:
        _ring_at_any_width(patch)
        return _runs(HOT["widest"], (4,))


def _runs_at(request, table):
    """The module's runs at a table of ``HOT``: `runs` or `widest`."""
    return request.getfixturevalue({"narrow": "runs"}.get(table, table))


# -- (a) data=4 against the plain reference on the global batch -------------


@pytest.mark.parametrize("number", sorted(TOLERANCE))
@pytest.mark.parametrize("table", sorted(HOT))
def test_data_parallel_superstep_against_the_reference(request, table, number):
    runs = _runs_at(request, table)
    gaps = runner.compare(runs[4][0], runs["reference"])
    assert gaps[number] <= TOLERANCE[number], (gaps, runs[4][0])


def test_the_widest_table_is_the_rules_bound(widest):
    """What `widest` staged: the compact form at a table of F // 2 columns,
    every one of them live or all but a few (the live set pads to it)."""
    base = widest[4][4][0]
    assert base.live is not None and base.width == F // 2 == len(
        np.asarray(base.live))
    live = int(widest["corpus"][0]["traffic"].any(axis=0).sum())
    assert F // 4 < live <= F // 2


def test_the_widest_table_under_data4_is_recorded_as_it_ran(widest,
                                                            monkeypatch):
    """What ISSUE 44 reads on the chips for `tenk-train-live4k-dp4`, at this
    size: the `train.stage` span says the compact form at the bound, the
    epoch's span says the mesh, the collectives' gauge equals the dispatched
    program's own bytes and holds the w_ih gradients at the TABLE's rows
    (sixteen times `runs`' table here; since ISSUE 47 as the ring's
    permutes), and the two gauges of the compact feed read the table's width
    on every chip's behalf."""
    from test_obs_layers import _recorded

    _ring_at_any_width(monkeypatch)
    _, trainer, state, bundle, _, rest = widest[4]
    staged = []
    spans = _recorded(lambda: staged.append(trainer.stage_dataset(bundle)))
    (stage,) = [s for s in spans if s.name == "train.stage"]
    assert {k: stage.tags[k] for k in ("form", "padded", "bound", "width")
            } == {"form": "compact", "padded": F // 2, "bound": F // 2,
                  "width": F // 2}
    gauge = REGISTRY.get("deeprest_train_collective_bytes")
    gauge._series.clear()
    trainer._published_program = None
    out = []
    spans = _recorded(lambda: out.append(trainer.train_epoch(
        state, bundle, np.random.default_rng(0), staged=staged[0])))
    state = out[0][0]
    widest[4] = (widest[4][0], trainer, state, bundle, staged[0], rest)
    assert [s.tags["mesh"] for s in spans if s.name == "train.epoch"] == [
        "4x1x1"]
    read = {k[0]: v for k, v in gauge.series().items()}
    assert read == _gradient_bytes(trainer._dispatched_program_text(state),
                                   len(trainer._last_epoch_losses))
    width = F // 2
    rows = 4 * 2 * E * width * 3 * H
    # since ISSUE 47 they go round the ring: three of a chip's four quarters
    # arrive by permute, and what is all-reduced is what any table reduces
    assert read["collective-permute"] == rows * 3 // 4
    assert read["all-reduce"] < rows
    cols = REGISTRY.get("deeprest_train_projection_columns")
    adam = REGISTRY.get("deeprest_train_optimizer_rows")
    assert cols.value(kind="contracted") == width == adam.value(
        kind="updated")
    assert adam.value(kind="stale") == 0


# -- (b) data=4 against data=1 ----------------------------------------------


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_data_parallel_superstep_against_one_device(runs, number):
    """The same three steps whatever the mesh: the dropout mask is the
    same function of the key and the logical ``[E, B, T, 2H]`` shape, and
    every chip's rows are in the mean."""
    gaps = runner.compare(runs[4][0], runs[1][0])
    assert gaps[number] <= TOLERANCE[number], gaps


@pytest.mark.parametrize("table", sorted(HOT))
def test_a_chips_rows_left_out_of_the_mean_is_seen(request, table):
    runs = _runs_at(request, table)
    raw, data = runs["corpus"]
    short = _three_steps(4, raw, data, leave_out_a_chip=True)[0]
    gaps = runner.compare(short, runs["reference"])
    assert gaps["loss_rel_gap"] > 1000 * TOLERANCE["loss_rel_gap"], gaps


@pytest.mark.parametrize("table", sorted(HOT))
def test_state_is_the_same_on_every_chip(request, table):
    runs = _runs_at(request, table)
    state = runs[4][2]
    for leaf in jax.tree.leaves(state.params) + jax.tree.leaves(
            state.opt_state):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == 4
        assert all(np.array_equal(shards[0], s) for s in shards[1:])


# -- (c) what the program records under a mesh ------------------------------


def _gradient_bytes(hlo_text: str, steps: int) -> dict:
    """Bytes a step by kind of the collectives in ``hlo_text``, counted
    here from the instructions' own result types; one that no loop holds
    (its ``op_name`` says so: since ISSUE 45 the gathers of the carried
    rows, once a dispatch) counts a ``steps``-th."""
    sizes = {"f32": 4, "u32": 4, "s32": 4, "pred": 1, "bf16": 2}
    out = {}
    for line in hlo_text.splitlines():
        m = profiler._INSTRUCTION.match(line)
        kind = m and profiler.collective_kind(m["opcode"])
        if not kind or kind[1] == "-start":
            continue
        result = line.split(" = ", 1)[1].split(f" {m['opcode']}(")[0]
        n = 0
        for dtype, dims in re.findall(
                r"\b(f32|u32|s32|pred|bf16)\[([\d,]*)\]", result):
            n += sizes[dtype] * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
        looped = "/while/" in profiler._OP_NAME.search(line)[1]
        out[kind[0]] = out.get(kind[0], 0) + (n if looped else n / steps)
    return {kind: round(n) for kind, n in out.items()}


def test_collective_bytes_gauge_and_the_one_device_gauges(runs):
    _, trainer, state, bundle, staged, _ = runs[4]
    # the gauges are the process's: another fixture may have staged since
    staged = trainer.stage_dataset(bundle)
    gauge = REGISTRY.get("deeprest_train_collective_bytes")
    gauge._series.clear()
    was = obs.RECORDER.enabled
    obs.RECORDER.enabled = True
    obs.RECORDER.clear()
    try:
        state, _ = trainer.train_epoch(state, bundle,
                                       np.random.default_rng(0),
                                       staged=staged)
    finally:
        obs.RECORDER.enabled = was
    runs[4] = (runs[4][0], trainer, state, bundle, staged, runs[4][5])
    text = trainer._dispatched_program_text(state)
    read = {k[0]: v for k, v in gauge.series().items()}
    assert read == _gradient_bytes(text, len(trainer._last_epoch_losses))
    assert read["all-reduce"] > 0
    # what is reduced is the gradient: the w_ih leaves at the table's rows
    # (not F), and NOT the mask weights' [E, H, F], which every chip
    # derives from the reduced w_ih gradient
    width = staged[0].width
    grads = 4 * (2 * E * width * 3 * H + 2 * E * H * 3 * H + 4 * E * 3 * H
                 + E * 4 * H * len(QUANTILES) + E * len(QUANTILES))
    assert grads <= read["all-reduce"] < grads + 4 * E * H * F
    # the compact feed and the row-wise Adam hold under the `data` axis
    cols = REGISTRY.get("deeprest_train_projection_columns")
    rows = REGISTRY.get("deeprest_train_optimizer_rows")
    assert cols.value(kind="contracted") == width <= F / 4
    assert cols.value(kind="total") == F
    assert rows.value(kind="updated") == width and rows.value(kind="total") == F
    # the epoch's span says which mesh it ran on
    spans = [s for s in obs.RECORDER.snapshot() if s.name == "train.epoch"]
    assert spans and spans[-1].tags["mesh"] == "4x1x1"
    # one device: no collective, and the gauge is not touched
    one = runs[1]
    gauge._series.clear()
    one[1].train_epoch(one[2], one[3], np.random.default_rng(0),
                       staged=one[4])
    assert gauge.series() == {}
    assert profiler.collective_bytes(
        one[1]._dispatched_program_text(one[2])) == {}


def test_reading_the_collectives_compiles_nothing(runs):
    """The gauge is read from the executable the dispatch made: lowered on
    the dispatch's own arguments, the program is found in the jit's cache,
    compiled (so a mesh costs a training run no second compile)."""
    import jax._src.monitoring as monitoring

    _, trainer, state, bundle, staged, rest = runs[4]
    state, _ = trainer.train_epoch(state, bundle, np.random.default_rng(1),
                                   staged=staged)
    runs[4] = (runs[4][0], trainer, state, bundle, staged, rest)
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        jax.jit(lambda x: x + 1)(np.float32(len(runs)))   # the listener hears
        heard = len(compiles)
        trainer._published_program = None
        trainer._publish_program(state)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert heard and len(compiles) == heard
    assert trainer._published_program == trainer._staged_program
    # the same read gave the executable's bytes by kind
    found = REGISTRY.get("deeprest_train_program_bytes").series()
    assert {k[0] for k in found} == {"arguments", "outputs", "aliased",
                                     "temporaries", "code"}
    assert found[("arguments",)] > 0


def test_collective_row_of_the_scope_table(runs):
    _, trainer, state, *_ = runs[4]
    text = trainer._dispatched_program_text(state)
    table = profiler.scope_table(text, ())
    found = [k for k, v in table.items() if v == (profiler.COLLECTIVE, "-")]
    assert found and all(profiler.collective_kind(k) for k in found)


@pytest.mark.parametrize("text, expected", [
    ("  %ar = (f32[40,3]{0,1:T(4,128)S(1)}, bf16[40,512,3]{1,2,0}) "
     "all-reduce(%a, %b), channel_id=7", {"all-reduce": 480 + 122880}),
    ("  %ag = (f32[4,8]{1,0}, f32[16,8]{1,0}) all-gather-start(%a), "
     "dimensions={0}", {}),
    ("  %d = f32[16,8]{1,0} all-gather-done(%ag)", {"all-gather": 512}),
    ("  %p = pred[7]{0} collective-permute(%a)", {"collective-permute": 7}),
    ("  %f = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused", {}),
])
def test_collective_bytes_of_an_instruction(text, expected):
    hlo = "HloModule m\n\nENTRY %main (a: f32[8]) -> f32[8] {\n" + text + "\n}"
    assert profiler.collective_bytes(hlo) == expected


def test_collective_bytes_takes_a_loop_once_and_a_conditionals_larger_branch():
    hlo = """HloModule m

%body (p: f32[8]) -> f32[8] {
  %r = f32[8]{0} all-reduce(%p), to_apply=%add
}

%small (p: f32[8]) -> f32[8] {
  %r = f32[2]{0} all-reduce(%p), to_apply=%add
}

%large (p: f32[8]) -> f32[8] {
  %r = f32[4]{0} reduce-scatter(%p), to_apply=%add
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = f32[8]{0} while(%a), condition=%cond, body=%body
  %c = f32[8]{0} conditional(%i, %a, %a), branch_computations={%small, %large}
}
"""
    assert profiler.collective_bytes(hlo) == {"all-reduce": 32,
                                              "reduce-scatter": 16}


# -- (c2) ISSUE 45: the carried rows split over `data` -----------------------

# XLA:TPU's forms of the collectives that the split brings, cut from the
# compact superstep compiled for a described v5e:2x2 at toy shapes: a
# reduce-scatter is a `kCustom` fusion named for no collective; an
# asynchronous gather is a chain of three fusions, each holding a piece with
# the chain's id, the middle one beside the matmul that hides it; a
# dispatch's gathers stand in the entry computation, outside the step's loop.
_SCATTER_HLO = """HloModule jit_train_superstep

%add (x: bf16[], y: bf16[]) -> bf16[] {
  ROOT %a = bf16[] add(%x, %y)
}

%all-reduce-scatter.1 (input.1: bf16[4,16,8]) -> bf16[4,4,8] {
  %input.1 = bf16[4,16,8]{2,1,0} parameter(0)
  %all-reduce.19 = bf16[4,16,8]{2,1,0} all-reduce(%input.1), channel_id=22, replica_groups={{0,1,2,3}}, to_apply=%add, frontend_attributes={from-cross-replica-sharding="true"}
  ROOT %dynamic-slice.34 = bf16[4,4,8]{2,1,0} dynamic-slice(%all-reduce.19, %c, %i, %c), dynamic_slice_sizes={4,4,8}
}

%body (p: (bf16[4,16,8])) -> (bf16[4,16,8]) {
  %dw = bf16[4,16,8]{2,1,0} fusion(%p), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/dot_general"}
  %fusion.26 = bf16[4,4,8]{2,1,0} fusion(%dw), kind=kCustom, calls=%all-reduce-scatter.1, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/dot_general"}
  %all-reduce.21 = f32[4,8]{1,0} all-reduce(%b), channel_id=9, to_apply=%add, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/recurrence/psum"}
}

ENTRY %main (a: bf16[4,16,8]) -> bf16[4,16,8] {
  %while.1 = (bf16[4,16,8]{2,1,0}) while(%t), condition=%cond, body=%body
}
"""

_CHAIN_HLO = """HloModule jit_train_superstep

%fused_computation.280 (param_0: bf16[4,4,8]) -> (bf16[4,4,8], bf16[4,16,8], u32[]) {
  %all-gather.17 = bf16[4,16,8]{2,1,0} all-gather(%param_0), channel_id=4, dimensions={1}, frontend_attributes={chain_id="0"}
  ROOT %custom-call.46 = (bf16[4,4,8]{2,1,0}, bf16[4,16,8]{2,1,0}, u32[]{:S(2)}) custom-call(%all-gather.17), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.187 (param_0: bf16[4,4,8], param_1: bf16[4,16,8]) -> (bf16[4,6,2,8], bf16[4,16,8]) {
  %convolution.17 = bf16[4,6,2,8]{3,2,1,0} convolution(%x, %w), metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/in_proj/dot_general"}
  %all-gather.19 = bf16[4,16,8]{2,1,0} all-gather(%param_0), channel_id=4, dimensions={1}, frontend_attributes={chain_id="0"}
  ROOT %tuple.386 = (bf16[4,6,2,8]{3,2,1,0}, bf16[4,16,8]{2,1,0}) tuple(%convolution.17, %all-gather.19)
}

%fused_computation.282 (param_0: bf16[4,4,8], param_1: bf16[4,16,8]) -> bf16[4,16,8] {
  %all-gather.21 = bf16[4,16,8]{2,1,0} all-gather(%param_0), channel_id=4, dimensions={1}, frontend_attributes={chain_id="0"}
  ROOT %custom-call.48 = bf16[4,16,8]{2,1,0} custom-call(%param_0, %param_1, %all-gather.21), custom_call_target="AsyncCollectiveDone"
}

%body (p: (bf16[4,4,8])) -> (bf16[4,4,8]) {
  %all-gather.46 = bf16[4,16,8]{2,1,0} all-gather(%folded.fwd), channel_id=3, dimensions={1}, frontend_attributes={async_collective_name="all-gather-start"}, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/convert_element_type"}
  %async-collective-start = (bf16[4,4,8]{2,1,0}, bf16[4,16,8]{2,1,0}, u32[]{:S(2)}) fusion(%folded.bwd), kind=kCustom, calls=%fused_computation.280, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/convert_element_type"}
  %fusion.187 = (bf16[4,6,2,8]{3,2,1,0}, bf16[4,16,8]{2,1,0}) fusion(%g0, %g1, %all-gather.46), kind=kOutput, calls=%async_collective_fusion.187, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/in_proj/dot_general"}
  %async-collective-done = bf16[4,16,8]{2,1,0} fusion(%g2, %g3), kind=kCustom, calls=%fused_computation.282, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/convert_element_type"}
}

ENTRY %main (a: bf16[4,4,8]) -> bf16[4,4,8] {
  %while.1 = (bf16[4,4,8]{2,1,0}) while(%t), condition=%cond, body=%body
}
"""

_DISPATCH_HLO = """HloModule jit_train_superstep

%body (p: (f32[4,4,8])) -> (f32[4,4,8]) {
  %all-reduce.8 = f32[]{:T(128)} all-reduce(%loss), channel_id=2, to_apply=%add, metadata={op_name="jit(train_superstep)/while/body/jvp(loss)/reduce_sum"}
}

ENTRY %main (a: f32[4,4,8]) -> f32[4,16,8] {
  %while.1 = (f32[4,4,8]{2,1,0}) while(%t), condition=%cond, body=%body
  %all-gather.47 = f32[4,16,8]{2,1,0} all-gather(%rows.0), channel_id=18, dimensions={1}, metadata={op_name="jit(train_superstep)/sharding_constraint"}
  %all-gather.48 = f32[4,16,8]{2,1,0} all-gather(%rows.1), channel_id=19, dimensions={1}, metadata={op_name="jit(train_superstep)/sharding_constraint"}
}
"""


# ISSUE 47: a stage of the ring as XLA:TPU schedules it for a described
# v5e:2x2 (toy shapes): the two halves' permutes start, the stage's two chunk
# dots run as fusions of their own, the permutes are done, and the additions
# round what arrived for the next hop.
_RING_HLO = """HloModule jit_train_superstep

%body (p: (bf16[4,2,8])) -> (bf16[4,2,8]) {
  %collective-permute-start.7 = (bf16[4,2,8]{2,1,0}, bf16[4,2,8]{2,1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%sum.up), channel_id=5, source_target_pairs={{0,2},{2,3},{3,1},{1,0}}, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/ppermute"}
  %collective-permute-start.6 = (bf16[4,2,8]{2,1,0}, bf16[4,2,8]{2,1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%sum.down), channel_id=6, source_target_pairs={{2,0},{3,2},{1,3},{0,1}}, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/ppermute"}
  %convolution_bitcast_fusion.10 = f32[4,2,8]{2,1,0} fusion(%x, %d), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/btf,etbg->efg/dot_general"}
  %convolution_bitcast_fusion.11 = f32[4,2,8]{2,1,0} fusion(%x, %d), kind=kOutput, calls=%fused_dot.1, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/btf,etbg->efg/dot_general"}
  %collective-permute-done.7 = bf16[4,2,8]{2,1,0} collective-permute-done(%collective-permute-start.7), metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/ppermute"}
  %collective-permute-done.6 = bf16[4,2,8]{2,1,0} collective-permute-done(%collective-permute-start.6), metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/ppermute"}
  %add_convert_fusion.4 = bf16[4,2,8]{2,1,0} fusion(%collective-permute-done.7, %convolution_bitcast_fusion.10), kind=kLoop, calls=%fused_add, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/in_proj/shard_map/convert_element_type"}
}

ENTRY %main (a: bf16[4,2,8]) -> bf16[4,2,8] {
  %while.1 = (bf16[4,2,8]{2,1,0}) while(%t), condition=%cond, body=%body
}
"""


@pytest.mark.parametrize("text, steps, expected, row", [
    # the fusion's result, a chip's slice, as the reduce-scatter it is; the
    # all-reduce inside it is no all-reduce of the step
    (_SCATTER_HLO, 1, {"reduce-scatter": 4 * 4 * 8 * 2, "all-reduce": 128},
     ["all-reduce.21", "fusion.26"]),
    # the chain's three pieces once, beside the step's synchronous gather;
    # the fusion that hides the chain keeps its matmul's label
    (_CHAIN_HLO, 1, {"all-gather": 2 * 4 * 16 * 8 * 2},
     ["all-gather.46", "async-collective-done", "async-collective-start"]),
    # what stands outside the loop, divided by the dispatch's steps
    (_DISPATCH_HLO, 8, {"all-gather": 2 * 4 * 16 * 8 * 4 // 8,
                        "all-reduce": 4},
     ["all-gather.47", "all-gather.48", "all-reduce.8"]),
    # the ring's permutes, each pair once by its done; its dots are no row's
    # of the collectives
    (_RING_HLO, 1, {"collective-permute": 2 * 4 * 2 * 8 * 2},
     ["collective-permute-done.6", "collective-permute-done.7",
      "collective-permute-start.6", "collective-permute-start.7"]),
], ids=["all_reduce_scatter_fusion", "chained_asynchronous_gather",
        "gathers_outside_the_loop", "ring_of_chunk_dots"])
def test_collective_bytes_sees_xla_tpus_forms(text, steps, expected, row):
    assert profiler.collective_bytes(text, steps) == expected
    table = profiler.scope_table(text, ("in_proj", "recurrence"))
    assert sorted(k for k, v in table.items()
                  if v == (profiler.COLLECTIVE, "-")) == row
    if "fusion.187" in table:
        assert table["fusion.187"] == ("in_proj", "fwd")
    for name in table:
        if name.startswith(("convolution_bitcast_fusion", "add_convert")):
            assert table[name] == ("in_proj", "bwd")
    # a trace's event finds the row by its name where the map has it, and
    # the asynchronous fusions by their names alone
    for name in row:
        assert profiler._row_key(name, f"%{name} = ...", table) == (
            profiler.COLLECTIVE, "-")
    assert profiler._row_key("async-collective-done.3", "", None) == (
        profiler.COLLECTIVE, "-")


@pytest.mark.parametrize("name, kind", [
    ("all-reduce-scatter.1", ("reduce-scatter", "")),
    ("%all-reduce-scatter", ("reduce-scatter", "")),
    ("async-collective-start", (profiler.ASYNC_COLLECTIVE, "-start")),
    ("async-collective-done.2", (profiler.ASYNC_COLLECTIVE, "-done")),
    ("async_collective_fusion.187", None),
    ("fusion.26", None),
])
def test_collective_kind_of_xla_tpus_wrappers(name, kind):
    assert profiler.collective_kind(name) == kind


@pytest.mark.parametrize("data, width, split", [
    (4, 4096, 4), (4, 128, 4), (1, 4096, 1), (4, 130, 1), (None, 4096, 1)])
def test_the_carried_rows_split_is_the_data_axis_where_it_divides(
        data, width, split):
    from deeprest_tpu.parallel import sharding
    from deeprest_tpu.parallel.mesh import make_mesh

    mesh = None if data is None else make_mesh(MeshConfig(data=data))
    assert sharding.carried_rows_split(mesh, width) == split


def test_the_carried_rows_spec_has_one_owner(runs, monkeypatch):
    """The spec of the rows that ride the scan is `parallel/sharding.py`'s
    rule and nobody else's: a state of carried rows resolves its six w_ih
    leaves to rows over `data` and every other leaf as ever; with the rule
    taken out of the module the same call gives the whole-state table's
    answer; and the trainer and the model write no sharding of their own."""
    from jax.sharding import PartitionSpec as P

    from deeprest_tpu.models import qrnn
    from deeprest_tpu.parallel import sharding
    from deeprest_tpu.train import trainer as trainer_module

    _, trainer, state, _, staged, _ = runs[4]
    rows = trainer_module.take_w_ih(state, staged[0].live, trainer.mesh)
    whole, carried = (sharding.state_specs(rows, carried_rows=flag)
                      for flag in (False, True))
    differ = {sharding.leaf_path_name(path)
              for (path, a), b in zip(
                  jax.tree_util.tree_leaves_with_path(
                      whole, is_leaf=lambda x: isinstance(x, P)),
                  jax.tree.leaves(carried,
                                  is_leaf=lambda x: isinstance(x, P)))
              if a != b}
    assert differ == {f"{tree}/gru_{d}_w_ih" for d in ("fwd", "bwd")
                      for tree in ("params", "opt_state/0/mu",
                                   "opt_state/0/nu")}
    assert carried.params["gru_fwd_w_ih"] == P("expert", "data", None)
    assert whole.params["gru_fwd_w_ih"] == P("expert", "model", None)
    monkeypatch.setattr(sharding, "CARRIED_ROWS_RULES", ())
    assert sharding.state_specs(rows, carried_rows=True) == whole
    for module in (trainer_module, qrnn):
        with open(module.__file__) as fh:
            source = fh.read()
        assert "NamedSharding" not in source
        assert "with_sharding_constraint(" not in source.replace(
            "jax.lax.with_sharding_constraint, state", "")


def test_a_chip_steps_a_quarter_of_the_tables_rows(runs):
    """`deeprest_train_optimizer_rows{kind="per_chip"}` and the benchmark's
    reader of it: a quarter of the table's width under `data`=4, where the
    compiled step holds the carried rows and their moments a quarter at a
    time, and the width on one device, whose step holds no such array."""
    from chipbench.readers import carried_rows

    rows = REGISTRY.get("deeprest_train_optimizer_rows")
    read = {}
    for data in (4, 1):
        _, trainer, state, bundle, staged, rest = runs[data]
        staged = trainer.stage_dataset(bundle)      # the process's gauges
        if data == 1:        # an earlier case's epoch took that state
            state = trainer.init_state(trainer.sample_input(bundle))
        state, _ = trainer.train_epoch(state, bundle,
                                       np.random.default_rng(2),
                                       staged=staged)
        runs[data] = (runs[data][0], trainer, state, bundle, staged, rest)
        width = staged[0].width
        read[data] = (rows.value(kind="per_chip"), rows.value(kind="updated"),
                      carried_rows.per_chip_pct({}))
        text = trainer._dispatched_program_text(state)
        quarter = f"f32[{E},{width // 4},{3 * H}]"
        assert (quarter in text) == (data == 4)
    assert read[4] == (width // 4, width, 25.0)
    assert read[1] == (width, width, 100.0)
    # a program without the kind (an older commit) reads as nothing
    rows._series.pop(("per_chip",))
    assert carried_rows.per_chip_pct({}) is None


def test_the_folded_weight_arrives_in_the_rules_pieces(runs, widest):
    """`deeprest_train_projection_gather_pieces` and the benchmark's reader of
    it (ISSUE 49): set beside `per_chip` by a trainer whose carried rows are
    split over `data`, to `sharding.gather_pieces` of the table's rows where
    a hop carries the ring's bound (here: at any width, five groups of one
    expert; with a bound that admits two pieces of E = 5, one), to 1 where
    the table is narrow and the partitioner gathers the weight whole; one
    chip leaves it absent, and a registry without it reads as nothing."""
    from chipbench.readers import gather_pieces
    from deeprest_tpu.obs import setup as obs_setup
    from deeprest_tpu.parallel import sharding

    gauge = REGISTRY.get(obs_setup.GATHER_PIECES)
    assert gauge is not None

    def published(run):
        _, trainer, _, _, staged, _ = run
        gauge._series.clear()
        trainer._publish_optimizer_rows(staged[0], 0, 10)
        return gauge.series(), gather_pieces.per_step({})

    assert published(runs[4]) == ({(): 1.0}, 1.0)       # under the rule
    width = widest[4][4][0].width
    sent = E * width // 4 * 3 * H * 4        # float32 rows, by one chip
    for bound, pieces in ((0, E), (sent // 2, 1), (sent // 5, E)):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(sharding, "RING_MIN_HOP_BYTES", bound)
            assert published(widest[4]) == ({(): float(pieces)},
                                            float(pieces)), bound
    assert published(runs[1]) == ({}, None)             # rows not split
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(REGISTRY, "_metrics", {
            k: v for k, v in REGISTRY._metrics.items()
            if k != obs_setup.GATHER_PIECES})
        assert gather_pieces.per_step({}) is None       # an older commit


# -- (d) the three readers on a recorded slice of a four-chip trace ---------


@pytest.fixture(scope="module")
def recorded():
    with open(SLICE) as fh:
        return json.load(fh)


def test_readers_on_the_recorded_step(recorded):
    planes = [(p, [(line, [tuple(e) for e in events])
                   for line, events in lines])
              for p, lines in recorded["planes"]]
    found = collectives.reduce_planes(planes)
    assert found["chips"] == 4
    evidence = {"collectives": found, "steps": recorded["steps"]}
    ms = collectives.ms_per_step(evidence)
    exposed = collectives.exposed_ms_per_step(evidence)
    assert ms == pytest.approx(recorded["expected"]["collective_ms"])
    assert exposed == pytest.approx(
        recorded["expected"]["collective_exposed_ms"])
    assert 0 < exposed <= ms
    # the program's own table reads the same trace: its row `collective`
    # (self time on the chip's one operation line) is the exposed part
    ours = profiler.layer_table_of(planes, steps=recorded["steps"])
    row = {(r["scope"], r["pass"]): r for r in ours["rows"]}
    assert row[(profiler.COLLECTIVE, "-")]["seconds"] == pytest.approx(
        found["exposed_s"])
    assert sum(r["seconds"] for r in ours["rows"]) == pytest.approx(
        ours["busy_s"])
    # and the yardstick's busy time is the same union as ever
    assert trace_reduce.reduce_planes(planes)["busy_s"] == pytest.approx(
        ours["busy_s"])


def _line(*events):
    return [("/device:TPU:0", [("XLA Ops", list(events))])]


@pytest.mark.parametrize("events, whole, alone", [
    # an asynchronous pair: from the start's start to the done's end, and
    # the fusion between them hides its part
    ([("%all-reduce-start.1 = f32[8] all-reduce-start(%a)", 100, 10),
      ("%fusion.2 = f32[8] fusion(%b)", 110, 200),
      ("%all-reduce-done.1 = f32[8] all-reduce-done(%s)", 310, 90)],
     300, 100),
    # a synchronous one with nothing beside it: all of it exposed
    ([("%fusion.1 = f32[8] fusion(%b)", 0, 100),
      ("%all-reduce.5 = f32[8] all-reduce(%a)", 100, 50)], 50, 50),
    # inside a loop's event: the `while` holds it and is no work itself
    ([("%while.1 = (f32[8]) while(%t)", 0, 1000),
      ("%all-reduce.5 = f32[8] all-reduce(%a)", 100, 50),
      ("%fusion.3 = f32[8] fusion(%b)", 150, 850)], 50, 50),
    # an overlapped pair, wholly hidden
    ([("%all-gather-start.2 = f32[8] all-gather-start(%a)", 0, 5),
      ("%fusion.1 = f32[8] fusion(%b)", 0, 400),
      ("%all-gather-done.2 = f32[8] all-gather-done(%s)", 390, 10)],
     400, 0),
    ([("%fusion.1 = f32[8] fusion(%b)", 0, 100)], 0, 0),
])
def test_collective_time_and_its_exposed_part(events, whole, alone):
    found = collectives.reduce_planes(_line(*events))
    assert found["collective_s"] == pytest.approx(whole / 1e9)
    assert found["exposed_s"] == pytest.approx(alone / 1e9)
    # the program's row: the collectives' self time.  Where the line is
    # serial, as a chip's is, that is the exposed part (in the fourth case
    # the fusion is drawn over the pair's own events, which no chip does)
    rows = {r["scope"]: r["seconds"]
            for r in profiler.layer_table_of(_line(*events))["rows"]}
    if whole != 400:
        assert rows.get(profiler.COLLECTIVE, 0.0) == pytest.approx(
            alone / 1e9)


def test_a_permute_pair_costs_the_row_what_the_chip_waited_for_it():
    """`Trainer.profile_epoch`'s `collective` row on a stage of ISSUE 47's
    ring, as a chip's operation line draws it: a permute's start is a short
    event, its done lasts as long as the chip waited for the arrival, and
    the chunk dots between them are `in_proj`'s backward through the
    compiled step's map.  The row holds the starts and the waits and none
    of the dots' time; the benchmark's reader sees the pairs from start to
    done and the same exposed part."""
    table = profiler.scope_table(_RING_HLO, ("in_proj", "recurrence"))
    events = [
        ("%collective-permute-start.7 = (bf16[4,2,8]) "
         "collective-permute-start(%sum.up)", 0, 4),
        ("%collective-permute-start.6 = (bf16[4,2,8]) "
         "collective-permute-start(%sum.down)", 4, 4),
        ("%convolution_bitcast_fusion.10 = f32[4,2,8] fusion(%x, %d)",
         8, 200),
        ("%convolution_bitcast_fusion.11 = f32[4,2,8] fusion(%x, %d)",
         208, 200),
        ("%collective-permute-done.7 = bf16[4,2,8] "
         "collective-permute-done(%collective-permute-start.7)", 408, 60),
        ("%collective-permute-done.6 = bf16[4,2,8] "
         "collective-permute-done(%collective-permute-start.6)", 468, 2),
        ("%add_convert_fusion.4 = bf16[4,2,8] fusion(%a, %b)", 470, 30),
    ]
    ours = profiler.layer_table_of(_line(*events), scopes=table)
    rows = {(r["scope"], r["pass"]): r["seconds"] for r in ours["rows"]}
    assert rows[(profiler.COLLECTIVE, "-")] == pytest.approx(70 / 1e9)
    assert rows[("in_proj", "bwd")] == pytest.approx(430 / 1e9)
    assert sum(rows.values()) == pytest.approx(ours["busy_s"]) == 500 / 1e9
    found = collectives.reduce_planes(_line(*events))
    assert found["collective_s"] == pytest.approx(470 / 1e9)
    assert found["exposed_s"] == pytest.approx(70 / 1e9)


def test_readers_find_nothing_where_there_is_nothing():
    assert collectives.ms_per_step({"steps": 4}) is None
    assert collectives.exposed_ms_per_step({"steps": 4, "collectives": {
        "collective_s": 0.0, "exposed_s": 0.0}}) is None


# -- (e) the cell's files ---------------------------------------------------


def _load(*path):
    with open(os.path.join(REPO, *path)) as fh:
        return json.load(fh)


# the two cells across chips: `tenk-train-dp4` (ISSUE 31) and, at the widest
# table, `tenk-train-live4k-dp4` (ISSUE 44).  `mix_of`: the one-chip mix whose
# parameters the cell's mix holds letter for letter.
MESH_CELLS = {
    "tenk-train-dp4": {
        "config": "endpoints-10k-dp4", "mix_of": "week-sparse",
        "reduced": ["chips", "corpus_days", "mesh"], "hot_paths": 256,
        "reduced_a_step": 23_839_368, "words": ("the same float32 state",)},
    "tenk-train-live4k-dp4": {
        "config": "endpoints-10k-live4k-dp4", "mix_of": "week-live4k",
        "reduced": ["chips", "mesh", "corpus_days"], "hot_paths": 4096,
        "reduced_a_step": 259_768_968,
        "words": ("the same float32 state", "mean gradient over all 128",
                  "no column dropped", "Adam on every row of every leaf")},
}
LISTED_IN_EVERY_TRAIN_CELL = (
    "proj_columns_pct.train", "adam_rows_pct.train",
    "proj_dead_columns_pct.train", "init_state_s.train", "compile_s.train",
    "compilations.train", "init_state_peak_gb.train", "steady_hbm_gb.train",
    "gru_kernel_vmem_pct.train", "dropout_draws_per_step.train",
    "time_reversals_per_step.train", "kernel_edge_passes_per_step.train",
    # ISSUE 50: set-up's seconds in jax's pipeline, by stage
    "trace_s.train", "lower_s.train", "superstep_build_s.train",
    # ISSUE 51: whether the process loaded the kept superstep
    "superstep_loaded.train",
    # ISSUE 56: the weight-gradient dots a step runs only to hand over
    "bare_weight_grad_dots_per_step.train")


@pytest.mark.parametrize("name", sorted(MESH_CELLS))
def test_the_cells_files_exist_and_say_what_the_issue_says(name):
    want = MESH_CELLS[name]
    bench = _load("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    assert cell["chips"] == 4 and cell["config"] == want["config"]
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load(entry["file"])
    one = _load("chipbench", "configs", "endpoints-10k.json")
    assert cfg["model"] == one["model"]              # no width cut
    assert cfg["train"] == {**one["train"], "batch_size": 128}
    assert cfg["mesh"] == {"data": 4, "expert": 1, "model": 1}
    assert cfg["runners"] == ["train_mesh"] and cfg["chips"] == 4
    assert cfg["reduced"] == entry["reduced"] == want["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    # several deployments of one brief line: the driver takes a
    # configuration with another's source AND reduced keys as no new one
    for other in bench["configs"]:
        if other["name"] != entry["name"]:
            assert (other["source"], set(other["reduced"])) != (
                entry["source"], set(entry["reduced"])), other["name"]
            assert other["file"] != entry["file"]
    assert cfg["all_reduce_bytes_per_step"] == want["reduced_a_step"]
    for words in want["words"]:
        assert words in cfg["guarantee"], words
    mix = _load("chipbench", "traffic", cell["traffic"] + ".json")
    assert (mix["runner"], mix["generator"]) == ("train_mesh", "corpus")
    assert mix["params"] == _load("chipbench", "traffic",
                                  want["mix_of"] + ".json")["params"]
    assert mix["params"]["hot_paths"] == want["hot_paths"]
    assert mix["params"]["buckets"] == 10080
    limits = _load("chipbench", "limits", cell["name"] + ".json")
    assert set(limits["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                     "delta_norm_gap"}
    for m in bench["per_layer"]:
        spec = _load("chipbench", "layer_metrics", m["name"] + ".json")
        module, func = spec["reader"].split(":")
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "readers", module + ".py"))
        if m["layer"] == "mesh":
            # ISSUE 44's cell appended; what was there keeps its place
            # (ISSUE 45's metric, the last, has a reader of its own)
            assert m["workloads"] == sorted(MESH_CELLS)
            assert spec["runners"] == ["train_mesh"]
            assert callable(getattr(importlib.import_module(
                f"chipbench.readers.{module}"), func))
    for name in ("train_steps_per_s", "hbm_peak_gb"):
        metric = {m["name"]: m for m in bench["end_to_end"]}[name]
        assert cell["name"] in metric["workloads"]
    # the accepted per-layer metrics that list their cells name this one;
    # the seven that apply by runner name keep no list (the runner is read
    # as the `train` run it is)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED_IN_EVERY_TRAIN_CELL:
        assert cell["name"] in by_name[name]["workloads"], name
    unlisted = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 7
    assert all(_load("chipbench", "layer_metrics", name + ".json")["runners"]
               == ["train"] for name in unlisted)
    # the quota: of ten cells (ISSUE 48's and ISSUE 54's are one-chip
    # cells) two may ask for four chips, and these do
    assert len(bench["workloads"]) == 10 and len(bench["configs"]) == 9
    assert sorted(c["name"] for c in bench["workloads"]
                  if c["chips"] == 4) == sorted(MESH_CELLS)


def test_the_wide_cell_is_the_rules_widest_table_under_the_mesh():
    """`tenk-train-live4k-dp4`'s live set pads to a table of 4,096, under the
    bound the rule reads while the mesh's `model` axis is 1 (F // 2), and one
    path more would not: the compact form at its widest, on every chip."""
    from deeprest_tpu.ops.densify import compact_rule

    cfg = _load("chipbench", "configs", "endpoints-10k-live4k-dp4.json")
    mix = _load("chipbench", "traffic", "week-live4k-dp4.json")
    f, hot = cfg["model"]["feature_dim"], mix["params"]["hot_paths"]
    assert cfg["mesh"]["model"] == 1
    assert compact_rule(hot, f) == (4096, f // 2)
    assert compact_rule(hot + 1, f)[0] > f // 2
    assert set(cfg["assumed"]) == set(_load(
        "chipbench", "configs", "endpoints-10k-dp4.json")["assumed"]) | {
            "live_paths"}
