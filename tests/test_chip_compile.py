"""The main path's programs, compiled for a TPU v5e that is described and
not attached (`on-chip-measurement` guide, section 2, third rehearsal).

Nothing runs here: the chip's compiler (libtpu is installed) either accepts
each program at its real width or raises what it would raise on the chip —
a Mosaic lowering it does not implement, a kernel over the 16 MiB scoped
VMEM limit, a kernel GSPMD cannot partition, a step that does not fit HBM.
Interpret-mode tests can show none of these.  A pass is not a chip run;
`chip_smoke.py` is.

Only one process may hold libtpu, so the topology is described inside a
fixture of THIS file, never at import: every xdist worker collects the same
tests and only the worker that runs the file loads the library.  All of
these tests live in this one file for the same reason.  Code that asks
``jax.default_backend()`` still sees the CPU here, so the tests name the
backend (``rnn_backend="pallas"``) and the accelerator's rung set
themselves.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeprest_tpu.config import Config, ModelConfig, TrainConfig
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.models.qrnn import QuantileGRU, resolve_params
from deeprest_tpu.ops.densify import SparseBase
from deeprest_tpu.ops.quantize import quantize_params
from deeprest_tpu.parallel.mesh import AXES
from deeprest_tpu.parallel.sharding import state_sharding
from deeprest_tpu.serve.fused import FusedRolledEngine
from deeprest_tpu.train.trainer import Trainer, TrainState

# the module: `from deeprest_tpu.ops import gru` is the function of that name
gru_ops = importlib.import_module("deeprest_tpu.ops.gru")

E, F, H, W, B = 40, 512, 128, 60, 32      # the flagship geometry
F_10K = 10240
NNZ_CAP = 64
U_LIVE = 256                              # live call paths of the 10k cell
HBM_BYTES = 16e9                          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next one would warn
    # and compile again), so the cache stays off around these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1), AXES)


def _on(mesh, tree, spec=P()):
    """Shapes of ``tree``, placed on ``mesh`` (replicated by default)."""
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _kernel_calls(compiled) -> int:
    return len(re.findall(r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


def _model_config(dtype="bfloat16", feature_dim=F, experts=E) -> ModelConfig:
    return ModelConfig(feature_dim=feature_dim, num_metrics=experts,
                       hidden_size=H, compute_dtype=dtype,
                       rnn_backend="pallas")


def _param_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: QuantileGRU(config=cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, W, cfg.feature_dim))
        ))["params"]


# ---------------------------------------------------------------------------
# the recurrence kernel, forward and backward
# ---------------------------------------------------------------------------


def _gru_grad(mesh, dtype, rows, experts=E):
    """Lowered ``jax.grad`` of a bidirectional pallas GRU at the flagship
    shape, ``rows`` windows."""
    def shapes():
        keys = jax.random.split(jax.random.PRNGKey(0))
        return [gru_ops.init_gru_params(k, experts, F, H, dtype)
                for k in keys]

    fwd, bwd = (_on(mesh, p, P("expert")) for p in jax.eval_shape(shapes))
    x = _on(mesh, jax.ShapeDtypeStruct((rows, W, F), dtype), P("data"))
    live = mesh if mesh.size > 1 else None

    def loss(fwd, bwd, x):
        out = gru_ops.bidirectional_gru(fwd, bwd, x, backend="pallas",
                                        mesh=live)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(fwd, bwd, x)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_fwd_bwd_flagship(one_chip, dtype):
    compiled = _gru_grad(one_chip, jnp.dtype(dtype), B).compile()
    assert _kernel_calls(compiled) == 4        # 2 directions x (fwd, bwd)


@pytest.mark.parametrize("experts", [E, 200])     # both benchmark cells
def test_bidirectional_joins_before_the_one_transpose(one_chip, experts):
    """One kernel call a direction and pass, and the two directions joined
    in the kernels' ``[E,T,B,H]`` order: the compiler is handed no
    direction transposed alone, and makes none (ops/gru._layer_pallas)."""
    compiled = _gru_grad(one_chip, jnp.bfloat16, B, experts).compile()
    assert _kernel_calls(compiled) == 4
    text = compiled.as_text()
    assert f"[{experts},{W},{B},{H}]" in text          # a kernel's output
    assert f"[{experts},{B},{W},{H}]" not in text


def test_kernel_fat_rows_g4(one_chip):
    """Four batches' rows in one call: 128 rows per recurrence dot, the
    widest the bf16 training kernels fit (tests/test_coalesce.py block
    plan)."""
    compiled = _gru_grad(one_chip, jnp.bfloat16, 4 * B).compile()
    assert _kernel_calls(compiled) == 4


def test_kernel_f32_g4_has_no_plan_and_says_so(one_chip):
    with pytest.raises(ValueError, match=r"backward E=40 T=60 B=128 H=128"):
        _gru_grad(one_chip, jnp.float32, 4 * B)


@pytest.mark.parametrize("shape", [(4, 1, 1), (2, 2, 1)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_under_mesh(topo, dtype, shape):
    """shard_map over (data, expert): one kernel call per direction per
    pass in each device's program, and no all-gather around it."""
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), AXES)
    compiled = _gru_grad(mesh, jnp.dtype(dtype), B).compile()
    assert _kernel_calls(compiled) == 4
    assert "all-gather" not in compiled.as_text()


def _time_reversals(text: str) -> list[str]:
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    return profiler.time_reversals(text, scopes.RECURRENCE)


@pytest.mark.parametrize("experts,shape", [
    (E, (1, 1, 1)), (200, (1, 1, 1)),           # both benchmark widths
    (E, (4, 1, 1)),                             # `tenk-train-dp4`'s mesh
])
def test_reverse_direction_reverses_no_array_in_time(topo, experts, shape):
    """The reverse direction is the kernels walking the time blocks back
    to front (ISSUE 41): the gradient of the bidirectional layer holds its
    four kernel calls and no ``reverse`` at all, where flipping round a
    forward-only kernel compiled to five (three of ``[E,60,32,384]``, two
    of ``[E,60,32,128]``)."""
    n = int(np.prod(shape))
    mesh = Mesh(np.asarray(topo.devices[:n]).reshape(shape), AXES)
    compiled = _gru_grad(mesh, jnp.bfloat16, B * shape[0], experts).compile()
    text = compiled.as_text()
    assert _kernel_calls(compiled) == 4
    assert _time_reversals(text) == []
    assert " reverse(" not in text
    # ... and none is cut or summed round them (ISSUE 42; alone, the
    # layer's parent fused its two sums and kept the split: three)
    assert _kernel_edge_passes(text) == []


# ---------------------------------------------------------------------------
# the fused serving program, every rung an accelerator builds
# ---------------------------------------------------------------------------


def _engine(cfg: ModelConfig, sparse: bool) -> FusedRolledEngine:
    model = QuantileGRU(config=cfg)
    stats_x = MinMaxStats(min=np.zeros((cfg.feature_dim,), np.float32),
                          max=np.ones((cfg.feature_dim,), np.float32))
    stats_y = MinMaxStats(min=np.zeros((E,), np.float32),
                          max=np.ones((E,), np.float32))
    return FusedRolledEngine(
        lambda p, x: model.apply({"params": resolve_params(p)}, x,
                                 deterministic=True),
        stats_x, stats_y, W,
        # what the engine picks by itself on an accelerator
        page_windows=64, coalesce_pages=FusedRolledEngine.ACCEL_COALESCE_PAGES,
        sparse_nnz_cap=NNZ_CAP if sparse else None,
        feature_dim=cfg.feature_dim if sparse else None)


def _fused_lowered(mesh, cfg, rung, sparse=False, quant="off"):
    eng = _engine(cfg, sparse)
    assert rung in eng.rungs
    params = _param_shapes(cfg)
    if quant != "off":
        params = jax.eval_shape(lambda p: quantize_params(p, quant), params)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    if sparse:
        x = (sds((rung, W, NNZ_CAP), i32), sds((rung, W, NNZ_CAP), f32))
        program = eng._program_sparse
    else:
        x = (sds((rung, W, cfg.feature_dim), f32),)
        program = eng._program
    args = (params, *x, eng._x_mn, eng._x_rg, eng._y_mn, eng._y_rg,
            eng._carry0, sds((rung,), i32), sds((rung,), jnp.bool_),
            sds((), i32), sds((), jnp.bool_))
    return jax.jit(program).lower(*_on(mesh, args))


@pytest.mark.parametrize("rung,sparse,dtype,quant", [
    (64, False, "bfloat16", "off"),
    (64, True, "bfloat16", "off"),
    (128, False, "bfloat16", "off"),
    (192, False, "bfloat16", "off"),
    (256, False, "bfloat16", "off"),
    (256, True, "bfloat16", "off"),
    (8, False, "bfloat16", "off"),
    (128, False, "float32", "off"),
    (256, False, "float32", "off"),
    (128, False, "bfloat16", "int8"),
    (256, False, "float32", "int8"),
])
def test_fused_serve_program(one_chip, rung, sparse, dtype, quant):
    compiled = _fused_lowered(one_chip, _model_config(dtype), rung,
                              sparse=sparse, quant=quant).compile()
    assert _kernel_calls(compiled) == 2        # one per direction


def test_fused_serve_program_10k_sparse(one_chip):
    compiled = _fused_lowered(one_chip, _model_config(feature_dim=F_10K),
                              256, sparse=True).compile()
    assert _kernel_calls(compiled) == 2


# ---------------------------------------------------------------------------
# the whole train step
# ---------------------------------------------------------------------------


def _trainer_and_state(mesh, feature_dim, accum=1, batch=B, experts=E):
    """A `Trainer` at the flagship geometry and the shapes of its state,
    placed on ``mesh`` as `state_sharding` places them."""
    cfg = Config(model=_model_config(feature_dim=feature_dim,
                                     experts=experts),
                 train=TrainConfig(batch_size=batch, window_size=W,
                                   grad_accum_windows=accum))
    trainer = Trainer(cfg, feature_dim, [f"m{i}" for i in range(experts)],
                      mesh=mesh)

    def state():
        rng = jax.random.PRNGKey(0)
        params = dict(trainer.model.init(
            rng, jnp.zeros((1, W, feature_dim)))["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=trainer.tx.init(params), rng=rng)

    shapes = jax.eval_shape(state)
    return trainer, jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, state_sharding(mesh, shapes))


def _train_step_lowered(mesh, feature_dim, sparse, accum=1,
                        superstep=False, batch=B, experts=E, table=U_LIVE):
    """``superstep``: the G=1 superstep (a 3 x 50 plan, as the 10k cell's
    126-step epoch gives; under a mesh of several chips the 1 x 32 plan of
    `tenk-train-dp4`'s 32-step epoch, split over ``data``) instead of the
    per-step program.  ``table``: the width of the compact form's table."""
    trainer, state_sds = _trainer_and_state(mesh, feature_dim, accum, batch,
                                            experts)
    t_len = 4096
    sds = jax.ShapeDtypeStruct
    if sparse:
        # "compact": the form the trainer stages when few of the call
        # paths are live (ops/densify.py), here a table of `table` columns
        width = table if sparse == "compact" else feature_dim
        base = SparseBase(cols=sds((t_len, NNZ_CAP), jnp.int32),
                          vals=sds((t_len, NNZ_CAP), jnp.float32),
                          mn=sds((width,), jnp.float32),
                          rg=sds((width,), jnp.float32),
                          live=(sds((width,), jnp.int32)
                                if sparse == "compact" else None),
                          capacity=feature_dim)
    else:
        base = sds((t_len, feature_dim), jnp.bfloat16)
    y_base = sds((t_len, experts), jnp.float32)
    if accum == 1 and not superstep:
        args = (base, y_base, sds((B,), jnp.int32), sds((B,), jnp.float32))
        return trainer._train_step_indexed.lower(state_sds, *_on(mesh, args))
    # a chunk holds whole updates: 50 steps are 56 with 8 microbatches an
    # update, as Trainer._superstep_len rounds them
    plan = ((2, accum, batch) if not superstep
            else (3, -(-50 // accum) * accum, batch) if mesh.size == 1
            else (1, 32, batch))
    plans = _on(mesh, (sds(plan, jnp.int32), sds(plan, jnp.float32)),
                P(None, None, "data"))
    return trainer._superstep.lower(
        state_sds, *_on(mesh, (base, y_base)), *plans,
        *_on(mesh, (sds((), jnp.int32),)))


def _whole_leaf_copies(text: str) -> int:
    leaf = re.escape(f"f32[{E},{F_10K},{3 * H}]")
    return len(re.findall(rf"= {leaf}\{{[^}}]*\}} copy\(", text))


def _need(mem) -> int:
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _assert_masks_drawn_once(text: str, passes: int = 1, experts: int = E,
                             rows: int = B):
    """The dropout mask of each forward pass (ISSUE 36) is drawn once in
    the compiled step, by a fusion that returns masks and nothing else:
    ``pred`` arrays, a byte an element, of the joined output's ``E*B*W*2H``
    elements, which the forward select and the ``heads`` cotangent's read
    (the parent: the threefry in both their fusions, and no mask in
    memory).  Under accumulation the text holds ``passes`` forward passes
    (the microbatches' scan is unrolled), and their masks, one a
    microbatch from its own key, may come out of one fusion as siblings.
    ``rows``: the windows one chip sees."""
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    drawn = profiler.threefry_draws(text, scopes.DROPOUT)
    assert 1 <= len(drawn) <= passes, drawn
    masks = [f"pred[{experts},{a},{b},{2 * H}]"
             for a, b in ((rows, W), (W, rows))]
    made = []
    for name in drawn:
        results = re.search(rf"^\s*%?{re.escape(name)} = (.*?) fusion\(",
                            text, re.M)[1]
        made += re.findall(r"\w+\[[\d,]*\]", results)
    assert len(made) == passes and all(m in masks for m in made), made


@pytest.mark.parametrize("feature_dim,sparse,accum", [
    (F, False, 1),
    (F, False, 4),
    (F_10K, True, 1),
    # by hand (`-m slow`): one more whole-step compile is CPU time the
    # timing gates of tier-1's bench tests do not have to spare
    pytest.param(F_10K, "compact", 1, marks=pytest.mark.slow),
])
def test_train_step_fits_the_chip(one_chip, feature_dim, sparse, accum):
    """Forward, backward and Adam in one program, state donated: the
    kernel is in it and arguments + temporaries fit 16 GB of HBM.  With
    ``accum`` 4 it is the superstep of four microbatches an update instead
    (ISSUE 48; their scan unrolled, so four passes an update in the text).
    The compact form never builds a window or a folded weight F wide."""
    compiled = _train_step_lowered(one_chip, feature_dim, sparse,
                                   accum).compile()
    assert _kernel_calls(compiled) == 4 * accum
    text = compiled.as_text()
    _assert_masks_drawn_once(text, passes=accum)
    # `dropout`, `mixing` and `heads` get the two directions as ONE array
    assert f"[{E},{B},{W},{2 * H}]" in text
    assert f"[{E},{B},{W},{H}]" not in text
    if sparse == "compact":
        assert f"[{B},{W},{U_LIVE}]" in text
        assert f"[{B},{W},{F_10K}]" not in text
        assert f"bf16[{E},{F_10K},{3 * H}]" not in text
    mem = compiled.memory_analysis()
    assert _need(mem) < HBM_BYTES, mem


@pytest.fixture(scope="module")
def compact_superstep(one_chip):
    """The compact 10k superstep (a 3 x 50 plan), compiled once for the
    two tests that read it."""
    return _train_step_lowered(one_chip, F_10K, "compact",
                               superstep=True).compile()


def test_compact_superstep_draws_the_dropout_mask_once(compact_superstep):
    """E=40: the 10k cells' program (ISSUE 36).  The temporaries' bounds
    of the next test hold with the 19.7 MB mask in them."""
    _assert_masks_drawn_once(compact_superstep.as_text())


@pytest.fixture(scope="module")
def dense_superstep_e200(one_chip):
    """`tt-train-dense`'s program (the dense feed, E=200, F=2,048, a
    3 x 50 plan), compiled once for the two tests that read it."""
    return _train_step_lowered(one_chip, 2048, False, superstep=True,
                               experts=200).compile()


@pytest.mark.parametrize("cell", ["compact_superstep",
                                  "dense_superstep_e200"])
def test_superstep_reverses_no_array_in_time(request, cell):
    """The whole-step programs of the 10k cells (E=40) and of
    `tt-train-dense` (E=200): four kernel calls as before and no
    ``reverse`` under ``recurrence`` (ISSUE 41; the parent's text holds
    five), which is what ``deeprest_train_time_reversals`` reads on the
    chip."""
    compiled = request.getfixturevalue(cell)
    assert _kernel_calls(compiled) == 4
    assert _time_reversals(compiled.as_text()) == []


def _kernel_edge_passes(text: str) -> list[str]:
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    return profiler.kernel_edge_passes(text, scopes.RECURRENCE,
                                       scopes.GRU_KERNEL_BWD)


@pytest.mark.parametrize("cell", ["compact_superstep",
                                  "dense_superstep_e200",
                                  "compact_superstep_table4k"])
def test_superstep_makes_no_pass_at_the_kernels_edge(request, cell):
    """The three whole-step programs the six cells run (ISSUE 42): four
    kernel calls, no ``split`` under ``recurrence`` (both backward calls
    are handed the ONE ``[E,60,32,256]`` cotangent and read their halves
    through their ``BlockSpec``) and no ``reduce`` over a ``dproj`` (the
    kernels return the input bias's gradient), which is what
    ``deeprest_train_kernel_edge_passes`` reads on the chip."""
    compiled = request.getfixturevalue(cell)
    text = compiled.as_text()
    assert _kernel_calls(compiled) == 4
    assert _kernel_edge_passes(text) == []
    assert "recurrence/split" not in text
    joined = re.findall(r"gru_kernel_bwd[.\d]* = .*? custom-call\(([^)]*)\)",
                        text)
    assert len(joined) == 2 and len({ops.split()[-1] for ops in joined}) == 1


# The three instructions of the parent's programs (ISSUE 42's table), as
# the compiler for the described v5e wrote them (layouts and backend
# configuration left out): the fusion the `split` became, and a direction's
# sum over `dproj`, with the calls and tuple elements between them.
_PARENT_STEP = ("jit(train_superstep)/while/body/closed_call/cond/"
                "branch_1_fun/transpose(jvp(QuantileGRU))")
_PARENT_EDGE = """HloModule jit_train_superstep

%region_16.33 (reduce_sum.80: bf16[], reduce_sum.81: bf16[]) -> bf16[] {{
  %reduce_sum.81 = bf16[] parameter(1), metadata={{op_name="reduce_sum"}}
  %reduce_sum.80 = bf16[] parameter(0), metadata={{op_name="reduce_sum"}}
  ROOT %reduce_sum.82 = bf16[] add(%reduce_sum.80, %reduce_sum.81), metadata={{op_name="{step}/in_proj/reduce_sum"}}
}}

%fused_computation.311 (param_0.959: bf16[{e},60,32,256]) -> (bf16[{e},60,32,128], bf16[{e},60,32,128]) {{
  %param_0.959 = bf16[{e},60,32,256]{{3,2,1,0}} parameter(0)
  %split.8 = bf16[{e},60,32,128]{{3,2,1,0}} slice(%param_0.959), slice={{[0:{e}], [0:60], [0:32], [0:128]}}, metadata={{op_name="{step}/recurrence/split"}}
  %split.9 = bf16[{e},60,32,128]{{3,2,1,0}} slice(%param_0.959), slice={{[0:{e}], [0:60], [0:32], [128:256]}}, metadata={{op_name="{step}/recurrence/split"}}
  ROOT %tuple.197 = (bf16[{e},60,32,128]{{3,2,1,0}}, bf16[{e},60,32,128]{{3,2,1,0}}) tuple(%split.8, %split.9)
}}

%body (p: bf16[{e},60,32,256], q: bf16[{e},60,32,384]) -> bf16[{e},384] {{
  %{cotangent} = bf16[{e},60,32,256]{{3,2,1,0}} parameter(0)
  %proj = bf16[{e},60,32,384]{{3,2,1,0}} parameter(1)
  %constant.550 = bf16[] constant(0)
  %{split} = (bf16[{e},60,32,128]{{3,2,1,0}}, bf16[{e},60,32,128]{{3,2,1,0}}) fusion(%{cotangent}), kind=kLoop, calls=%fused_computation.311, metadata={{op_name="{step}/recurrence/split"}}
  %get-tuple-element.1564 = bf16[{e},60,32,128]{{3,2,1,0}} get-tuple-element(%{split}), index=1, metadata={{op_name="{step}/recurrence/split"}}
  %get-tuple-element.1563 = bf16[{e},60,32,128]{{3,2,1,0}} get-tuple-element(%{split}), index=0, metadata={{op_name="{step}/recurrence/split"}}
  %gru_kernel_bwd.6 = (bf16[{e},60,32,384]{{3,2,1,0}}, f32[{e},128,384]{{2,1,0}}, f32[{e},384]{{1,0}}, f32[{e},32,128]{{2,1,0}}) custom-call(%proj, /*index=5*/%get-tuple-element.1564), custom_call_target="tpu_custom_call", metadata={{op_name="{step}/recurrence/gru_kernel_bwd/pallas_call"}}
  %gru_kernel_bwd.7 = (bf16[{e},60,32,384]{{3,2,1,0}}, f32[{e},128,384]{{2,1,0}}, f32[{e},384]{{1,0}}, f32[{e},32,128]{{2,1,0}}) custom-call(%proj, /*index=5*/%get-tuple-element.1563), custom_call_target="tpu_custom_call", metadata={{op_name="{step}/recurrence/gru_kernel_bwd/pallas_call"}}
  %pallas_call.17 = bf16[{e},60,32,384]{{3,2,1,0}} get-tuple-element(%gru_kernel_bwd.6), index=0, metadata={{op_name="{step}/recurrence/gru_kernel_bwd/pallas_call"}}
  %pallas_call.38 = bf16[{e},60,32,384]{{3,2,1,0}} get-tuple-element(%gru_kernel_bwd.7), index=0, metadata={{op_name="{step}/recurrence/gru_kernel_bwd/pallas_call"}}
  %{sums[0]} = bf16[{e},384]{{1,0:T(8,128)(2,1)S(1)}} reduce(%pallas_call.17, %constant.550), dimensions={{1,2}}, to_apply=%region_16.33, metadata={{op_name="{step}/in_proj/reduce_sum"}}
  ROOT %{sums[1]} = bf16[{e},384]{{1,0:T(8,128)(2,1)S(1)}} reduce(%pallas_call.38, %constant.550), dimensions={{1,2}}, to_apply=%region_16.33, metadata={{op_name="{step}/in_proj/reduce_sum"}}
}}
"""


@pytest.mark.parametrize("e,cotangent,split,sums", [
    (200, "fusion.68", "fusion.174", ("reduce.43", "reduce.44")),   # dense
    (E, "fusion.63", "fusion.201", ("reduce.56", "reduce.57")),     # table 256
    (E, "fusion.95", "fusion.207", ("reduce.56", "reduce.57")),     # table 4,096
])
def test_parents_three_programs_hold_three_passes_at_the_kernels_edge(
        e, cotangent, split, sums):
    """What the counter reads on the three programs of ISSUE 42's parent
    (their three instructions each kept above, not the whole texts): the
    split of the joined cotangent and two sums over a ``dproj``."""
    text = _PARENT_EDGE.format(step=_PARENT_STEP, e=e, cotangent=cotangent,
                               split=split, sums=sums)
    assert _kernel_edge_passes(text) == [split, *sums]


def test_dense_superstep_e200_draws_the_dropout_mask_once(
        dense_superstep_e200):
    """E=200: `tt-train-dense`'s program, in which the mask is 98.3 MB; the
    superstep still needs less than `init_state` leaves at its peak (9.317
    GB: the ledger's `hbm_peak_gb`) beside the staged corpus, so the peak
    stays `init_state`'s."""
    compiled = dense_superstep_e200
    assert _kernel_calls(compiled) == 4
    _assert_masks_drawn_once(compiled.as_text(), experts=200)
    mem = compiled.memory_analysis()
    print(f"dense E=200 superstep for a described v5e: temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, needs "
          f"{_need(mem) / 1e9:.3f} GB")
    assert _need(mem) < 9.25e9, mem


@pytest.fixture(scope="module")
def dense_form_10k_superstep(one_chip):
    """`tenk-train-alllive`'s program (the sparse base in its dense form at
    F = 10,240, a 3 x 50 plan), compiled once for the tests that read
    it."""
    return _train_step_lowered(one_chip, F_10K, True,
                               superstep=True).compile()


def test_dense_form_10k_superstep_is_the_all_live_program(
        dense_form_10k_superstep):
    """The sparse base in its DENSE form at the 10k width (ISSUE 38; since
    ISSUE 39 moved the rule's bound to F // 2 the program of a live set
    over 4,096 paths, the all-live corpus among them, and of a mesh whose
    `model` axis shards F; no benchmark cell runs it any more; a 3 x 50
    plan): the scatter builds `[32,60,10240]` windows, the projection
    contracts over all F columns of the bf16 folded weights, both w_ih
    gradients are whole leaves; with the 4.46 GB of state it needs less than
    `init_state` leaves at its peak (8.924 GB: the 10k cells'
    `hbm_peak_gb`), so the peak stays `init_state`'s."""
    compiled = dense_form_10k_superstep
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"dense-form 10k superstep for a described v5e: temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, needs "
          f"{_need(mem) / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB; whole-leaf "
          f"copy operations {_whole_leaf_copies(text)}")
    assert _kernel_calls(compiled) == 4
    _assert_masks_drawn_once(text)
    assert f"[{B},{W},{F_10K}]" in text                  # the dense windows
    assert f"bf16[{E},{F_10K},{3 * H}]" in text          # the folded weights
    assert _need(mem) < 8.9e9, mem


TABLE_4K = 4096


@pytest.fixture(scope="module")
def compact_superstep_table4k(one_chip):
    """The live4k cells' program (the compact superstep at a table of
    4,096 columns, a 3 x 50 plan), compiled once for the tests that read
    it."""
    return _train_step_lowered(one_chip, F_10K, "compact", superstep=True,
                               table=TABLE_4K).compile()


def test_compact_superstep_at_the_widest_table_is_the_live4k_cells_program(
        compact_superstep_table4k):
    """`tenk-train-live4k`'s program since ISSUE 39: the compact superstep
    at the widest table the rule admits at F = 10,240, 4,096 columns (a
    3 x 50 plan).  Windows and folded weights are the table's width and
    never F wide, the six `[40,4096,384]` arrays of rows ride the scan as
    the 256-wide ones do (ten `while`s, no whole-leaf copy), and with the
    4.46 GB of state it needs 6.42 GB (temporaries 1.953 since ISSUE 56
    ordered the update by leaf: the second direction's bf16
    `[40,384,4096]` gradient, 126 MB, is no longer written; 2.016 from
    ISSUE 42, which took the split and the two sums from round the backward
    kernels and left the buffer assignment 94 MB wider than the 1.922
    before it): 2.5 GB under what `init_state` leaves at its
    peak (8.924 GB, the cell's `hbm_peak_gb`), so the peak stays
    `init_state`'s.  (A table of 8,192,
    which the rule does not admit, compiles to 3.554 GB of temporaries and
    8.015 GB needed: ISSUE 39's reading, not kept as a case.)"""
    table = TABLE_4K
    compiled = compact_superstep_table4k
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"compact 10k superstep, a table of {table}, for a described "
          f"v5e: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, needs "
          f"{_need(mem) / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB; whole-leaf "
          f"copy operations {_whole_leaf_copies(text)}")
    assert _kernel_calls(compiled) == 4
    _assert_masks_drawn_once(text)
    assert f"[{B},{W},{table}]" in text                  # the table's windows
    assert f"[{B},{W},{F_10K}]" not in text
    assert f"bf16[{E},{F_10K},{3 * H}]" not in text
    assert len(re.findall(r" while[(]", text)) == 10
    assert _whole_leaf_copies(text) == 0
    assert mem.temp_size_in_bytes == pytest.approx(1.953e9, rel=0.03), mem
    assert _need(mem) < 6.5e9, mem
    assert mem.generated_code_size_in_bytes <= 20e6, mem


# the four one-chip programs at one microbatch an update, and the shape of
# the w_ih leaves a step of each differentiates with respect to
_W_IH_LEAF = {
    "dense_superstep_e200": (200, 2048, 3 * H),        # `tt-train-dense`
    "dense_form_10k_superstep": (E, F_10K, 3 * H),     # `tenk-train-alllive`
    "compact_superstep_table4k": (E, TABLE_4K, 3 * H),  # the live4k cells
    "compact_superstep": (E, U_LIVE, 3 * H),           # `tenk-train-sparse`
}
_W_IH_BWD = re.compile(
    r"^\s+%(?P<name>[\w.\-]+) = (?P<result>.*?) fusion\(.*"
    r"transpose\(jvp\(QuantileGRU\)\)/in_proj/[^\"]*dot_general\"", re.M)


def _bare_weight_grad_dots(text: str, leaf) -> list[str]:
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    return profiler.bare_weight_grad_dots(text, scopes.IN_PROJ, [leaf])


@pytest.mark.parametrize("cell", list(_W_IH_LEAF))
def test_each_weight_gradient_dot_carries_its_own_leafs_adam(request, cell):
    """ISSUE 56: in the four one-chip programs every layer-0
    weight-gradient convolution fusion (one a direction) returns its OWN
    leaf's three float32 arrays (the leaf, `mu`, `nu`) and the one
    `[E, F]` partial of the mask's gradient, and nothing else: no fusion
    holds Adam for both leaves, so none of the two dots runs bare and no
    `[E, 3H, F]` gradient is written, which is what
    ``deeprest_train_bare_weight_grad_dots`` reads as 0 on the chip.  (The
    parent's texts hold ONE fusion of eight outputs and a bare dot that
    returns the bf16 gradient: the next test.)"""
    text = request.getfixturevalue(cell).as_text()
    e, f, g = leaf = _W_IH_LEAF[cell]
    found = {m["name"]: re.findall(r"\b(\w+)\[([\d,]*)\]", m["result"])
             for m in _W_IH_BWD.finditer(text)}
    assert len(found) == 2, found
    for name, arrays in found.items():
        assert sorted(arrays) == sorted(
            3 * [("f32", f"{e},{f},{g}")] + [("f32", f"{e},{f}")]), name
    assert _bare_weight_grad_dots(text, leaf) == []


# The two backward fusions of the parent's programs (ISSUE 56's table), as
# the compiler for the described v5e wrote them (layouts, operands' bodies
# and backend configuration left out): Adam of BOTH leaves round one
# direction's dot, and the other direction's dot alone.
_PARENT_DOTS = """HloModule jit_train_superstep

%fused_computation.23 (p0: bf16[{e},384,32,60], p1: bf16[32,60,{f}]) -> (f32[{e},{f},384], f32[{e},{f},384], f32[{e},{f},384], f32[{e},{f}], f32[{e},{f}], f32[{e},{f},384], f32[{e},{f},384], f32[{e},{f},384]) {{
  %p0 = bf16[{e},384,32,60]{{1,2,3,0}} parameter(0)
  %p1 = bf16[32,60,{f}]{{2,0,1}} parameter(1)
  %convolution.14 = bf16[{e},384,{f},1]{{2,1,3,0}} convolution(%p0, %p1), window={{size=1x60}}, dim_labels=0bf1_i1o0->0bf1, metadata={{op_name="{step}/in_proj/btf,efg->etbg/dot_general"}}
  %leaf = f32[{e},{f},384]{{1,2,0}} convert(%convolution.14), metadata={{op_name="jit(train_superstep)/while/body/closed_call/cond/branch_1_fun/optimizer/add"}}
  %partial = f32[{e},{f}]{{1,0}} reduce(%leaf), dimensions={{2}}
  ROOT %tuple.9 = (f32[{e},{f},384]{{1,2,0}}, f32[{e},{f},384]{{1,2,0}}, f32[{e},{f},384]{{1,2,0}}, f32[{e},{f}]{{1,0}}, f32[{e},{f}]{{1,0}}, f32[{e},{f},384]{{1,2,0}}, f32[{e},{f},384]{{1,2,0}}, f32[{e},{f},384]{{1,2,0}}) tuple(%leaf, %leaf, %leaf, %partial, %partial, %leaf, %leaf, %leaf)
}}

%fused_computation.70 (p0: bf16[{e},384,32,60], p1: bf16[32,60,{f}]) -> bf16[{e},384,{f},1] {{
  %p0 = bf16[{e},384,32,60]{{1,2,3,0}} parameter(0)
  %p1 = bf16[32,60,{f}]{{2,0,1}} parameter(1)
  ROOT %convolution.15 = bf16[{e},384,{f},1]{{2,1,3,0}} convolution(%p0, %p1), window={{size=1x60}}, dim_labels=0bf1_i1o0->0bf1, metadata={{op_name="{step}/in_proj/btf,efg->etbg/dot_general"}}
}}

%fused_computation.75 (p0: bf16[32,60,{f}], p1: bf16[{e},{f},384]) -> bf16[{e},60,32,384] {{
  %p0 = bf16[32,60,{f}]{{2,0,1}} parameter(0)
  %p1 = bf16[{e},{f},384]{{1,2,0}} parameter(1)
  ROOT %convolution.9 = bf16[{e},60,32,384]{{3,2,1,0}} convolution(%p0, %p1), dim_labels=0fb1_o1i0->0bf1, metadata={{op_name="jit(train_superstep)/while/body/closed_call/cond/branch_1_fun/jvp(QuantileGRU)/in_proj/btf,efg->etbg/dot_general"}}
}}

%body (d0: bf16[{e},384,32,60], d1: bf16[{e},384,32,60], x: bf16[32,60,{f}], w: bf16[{e},{f},384]) -> bf16[{e},60,32,384] {{
  %d0 = bf16[{e},384,32,60]{{1,2,3,0}} parameter(0)
  %d1 = bf16[{e},384,32,60]{{1,2,3,0}} parameter(1)
  %x = bf16[32,60,{f}]{{2,0,1}} parameter(2)
  %w = bf16[{e},{f},384]{{1,2,0}} parameter(3)
  %{bare} = bf16[{e},384,{f},1]{{2,1,3,0:T(8,128)(2,1)}} fusion(%d1, %x), kind=kOutput, calls=%fused_computation.70, metadata={{op_name="{step}/in_proj/btf,efg->etbg/dot_general"}}
  %{both} = (f32[{e},{f},384]{{1,2,0:T(8,128)}}, f32[{e},{f},384]{{1,2,0:T(8,128)}}, f32[{e},{f},384]{{1,2,0:T(8,128)}}, f32[{e},{f}]{{1,0:T(8,128)S(1)}}, f32[{e},{f}]{{1,0:T(8,128)S(1)}}, /*index=5*/f32[{e},{f},384]{{1,2,0:T(8,128)}}, f32[{e},{f},384]{{1,2,0:T(8,128)}}, f32[{e},{f},384]{{1,2,0:T(8,128)}}) fusion(%d0, %x), kind=kOutput, calls=%fused_computation.23, metadata={{op_name="{step}/in_proj/btf,efg->etbg/dot_general"}}
  ROOT %fusion.53 = bf16[{e},60,32,384]{{3,2,1,0}} fusion(%x, %w), kind=kOutput, calls=%fused_computation.75, metadata={{op_name="jit(train_superstep)/while/body/closed_call/cond/branch_1_fun/jvp(QuantileGRU)/in_proj/btf,efg->etbg/dot_general"}}
}}
"""


@pytest.mark.parametrize("e,f,both,bare", [
    (200, 2048, "fusion.17", "fusion.56"),       # the dense feed, E=200
    (E, F_10K, "fusion.17", "fusion.62"),        # the dense form
    (E, TABLE_4K, "fusion.42", "fusion.87"),     # a table of 4,096
    (E, U_LIVE, "fusion.83", "fusion.97"),       # a table of 256
])
def test_parents_four_programs_hold_one_bare_weight_gradient_dot(
        e, f, both, bare):
    """What the counter reads on the four programs of ISSUE 56's parent
    (their two backward fusions and a forward one each kept above, not the
    whole texts): the fusion that returns the bf16 gradient, and neither
    the one that holds Adam for both leaves nor the forward's dot; with
    another leaf's shape nothing is a weight gradient."""
    text = _PARENT_DOTS.format(step=_PARENT_STEP, e=e, f=f, both=both,
                               bare=bare)
    assert _bare_weight_grad_dots(text, (e, f, 3 * H)) == [bare]
    assert _bare_weight_grad_dots(text, (e, f // 2, 3 * H)) == []


def _plain_step_lowered(mesh, feature_dim):
    """The per-step program of the dense feed with the PLAIN update written
    out (tests/test_sparse_adam.py's `plain_step`: one ``tx.update`` over
    the whole gradient tree, which is what `apply_gradients` was before
    ISSUE 56 ordered it by leaf)."""
    from test_sparse_adam import plain_step

    trainer, state_sds = _trainer_and_state(mesh, feature_dim)
    sds = jax.ShapeDtypeStruct
    args = (sds((4096, feature_dim), jnp.bfloat16),
            sds((4096, E), jnp.float32),
            sds((B,), jnp.int32), sds((B,), jnp.float32))
    return jax.jit(plain_step(trainer, W), donate_argnums=0).lower(
        state_sds, *_on(mesh, args))


def test_the_plain_update_compiles_to_one_bare_weight_gradient_dot(one_chip):
    """The same counter on the parent's STRUCTURE, compiled here: a train
    step with the plain update holds one bare weight-gradient dot (the
    compiler gives both leaves' Adam to one direction's dot), the trainer's
    own per-step program at the same shapes none."""
    leaf = (E, 2048, 3 * H)
    plain = _plain_step_lowered(one_chip, 2048).compile().as_text()
    assert len(_bare_weight_grad_dots(plain, leaf)) == 1
    ordered = _train_step_lowered(one_chip, 2048, False).compile().as_text()
    assert _bare_weight_grad_dots(ordered, leaf) == []


def test_compact_superstep_updates_the_leaves_in_place(compact_superstep):
    """The compact 10k superstep (ISSUE 32), the guard of its mechanism in
    tier-1 (8-10 s): the table's rows of the two w_ih leaves and of their
    moments ride the scan, and the take before it and the put after it
    index rows of the ``[E*F, 3H]`` view, so the compiler is asked for no
    other layout of a leaf and copies none (the parent: a gather and a
    scatter over dimension 1 of each, twelve whole-leaf copies a dispatch,
    3.88 GB of temporaries, 8.34 GB needed, 18.9 MB of code).  The other
    nine ``while`` s are the off-table pass (ISSUE 34: 18.8 MB of code for
    15.9): the loop over chunks of stale rows, inside it the steps' loop
    and six loops that put a chunk back a row at a time, and the all-rows
    loop past the bound; each reads and writes the carried leaves in
    place, so their trip counts, 0 where the moments are zero off the
    table, are all they cost."""
    compiled = compact_superstep
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"compact 10k superstep for a described v5e: temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, needs "
          f"{_need(mem) / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB; whole-leaf "
          f"copy operations {_whole_leaf_copies(text)}")
    assert _kernel_calls(compiled) == 4
    assert len(re.findall(r" while[(]", text)) == 10
    assert _whole_leaf_copies(text) == 0
    assert mem.temp_size_in_bytes < 1.0e9, mem
    assert _need(mem) < 5.5e9, mem
    assert mem.generated_code_size_in_bytes <= 20e6, mem


def test_compact_superstep_accumulates_on_the_tables_rows(
        one_chip, compact_superstep):
    """`tenk-train-accum8`'s program (ISSUE 48): the compact 10k superstep
    at 8 microbatches an update, a 3 x 56 plan, compiled for the described
    v5e.  The microbatches' scan is unrolled (the rolled loop read 222.6
    steps/s on the chip for 307.4: PERF.md section 6, PR 48), so the text
    holds no ``while`` more than at one microbatch, 32 kernel calls and 8
    masks.  The accumulator is the table's rows and the other leaves: no
    ``[E, F, 3H]`` array is named that the superstep of one microbatch does
    not name (the leaves themselves, taken from and put to once a
    dispatch), no whole leaf is copied, and the mask weights' ``[E, H, F]``
    leaf is passed over by as many fusions as at one microbatch: the sum of
    the eight microbatches' gradients of it is made inside Adam's fusion
    (the twin this replaced: two ``[E, F, 3H]`` float32 gradients a
    microbatch and 2.93 GB of temporaries at a plan of two updates)."""
    compiled = _train_step_lowered(one_chip, F_10K, "compact", accum=8,
                                   superstep=True).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print(f"compact 10k superstep at 8 microbatches an update for a "
          f"described v5e: temporaries {mem.temp_size_in_bytes / 1e9:.3f} "
          f"GB, needs {_need(mem) / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB")
    one = compact_superstep.as_text()
    assert _kernel_calls(compiled) == 4 * 8
    assert len(re.findall(r" while[(]", text)) == 10
    _assert_masks_drawn_once(text, passes=8)
    leaf = f"f32[{E},{F_10K},{3 * H}]"
    assert text.count(leaf) == one.count(leaf)
    assert _whole_leaf_copies(text) == 0

    def passes_over_the_mask_weights(hlo: str) -> int:
        """Fusions with a result of the mask weights' shape."""
        return sum(f"f32[{E},{H},{F_10K}]" in line.split(" fusion(")[0]
                   for line in hlo.splitlines() if " fusion(" in line)

    assert 0 < passes_over_the_mask_weights(text) <= \
        passes_over_the_mask_weights(one)
    assert mem.temp_size_in_bytes < 3.0e9, mem
    assert _need(mem) < 7.5e9 < HBM_BYTES / 2, mem
    assert mem.generated_code_size_in_bytes <= 60e6, mem


def test_compact_superstep_names_where_its_kernels_operands_live(
        compact_superstep):
    """What ``Trainer._publish_program`` reads from the executable it
    dispatched (ISSUE 35), on the 10k compact superstep compiled for the
    described v5e: ``kernel_operand_spaces`` finds both kernels and every
    array their two calls each are handed (the text names an operand, its
    own computation holds its type), the bytes are the shapes', part of
    them is in VMEM by the compiler's memory-space assignment and the
    rest in HBM, and ``memory_analysis`` gives the five kinds of
    ``deeprest_train_program_bytes``."""
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    found = profiler.kernel_operand_spaces(compact_superstep.as_text(),
                                           scopes.KERNELS)
    bf16, f32 = 2, 4
    xp = E * W * B * 3 * H * bf16          # the projected input; the gates
    h = E * W * B * H * bf16               # a hidden-state sequence
    w_hh, bias, h0 = E * H * 3 * H, E * 3 * H * f32, E * B * H * f32
    handed = {
        # operands: xp, w_hh, the bias, h0; results: h twice, the gates
        "gru_kernel_fwd": (xp + w_hh * bf16 + bias + h0) + (2 * h + xp),
        # operands: xp, h, the gates, w_hh, the JOINED cotangent of both
        # directions' h (each call is handed the whole of it and reads its
        # own half: ISSUE 42); results: xp's cotangent, w_hh's, the four
        # [H] sums of the two biases', h0's
        "gru_kernel_bwd": ((2 * xp + h + w_hh * bf16 + 2 * h)
                           + (xp + w_hh * f32 + E * 4 * H * f32 + h0)),
    }
    print(f"compact 10k superstep for a described v5e: the kernels' "
          f"operands and results by memory space {found}")
    assert set(found) == set(scopes.KERNELS) == set(handed)
    for kernel, spaces in found.items():
        assert set(spaces) == {"hbm", "vmem"}, (kernel, spaces)
        assert sum(spaces.values()) == 2 * handed[kernel], (kernel, spaces)
    assert profiler.kernel_operand_spaces(
        compact_superstep.as_text(), ("gru_kernel_fwd",)).keys() \
        == {"gru_kernel_fwd"}
    mem = compact_superstep.memory_analysis()
    kinds = {"arguments": mem.argument_size_in_bytes,
             "outputs": mem.output_size_in_bytes,
             "aliased": mem.alias_size_in_bytes,
             "temporaries": mem.temp_size_in_bytes,
             "code": mem.generated_code_size_in_bytes}
    assert all(v > 0 for v in kinds.values()), kinds
    assert kinds["aliased"] <= kinds["outputs"] <= kinds["arguments"]


@pytest.mark.slow       # by hand, as the one-chip compact superstep above
def test_compact_superstep_under_data4_reduces_the_compact_gradients(topo):
    """`tenk-train-dp4`'s superstep (ISSUE 31: global batch 128 over a mesh
    data=4, a 1 x 32 plan) for a described v5e:2x2.  The kernels stay
    whole under ``shard_map``; a chip needs what one chip needs (the state
    is replicated, the windows are 32 a chip); and what the partitioner
    reduces each step is the w_ih gradients at the table's rows, w_hh and
    the heads as the bfloat16 matmuls make them, 23.8 MB, NOT the 210 MB of
    the mask weights' float32 gradient, which every chip derives from the
    reduced w_ih gradient (PERF.md section 6, PR 31); and, as on one chip
    since ISSUE 32, no chip copies a leaf to reach the table's rows."""
    from deeprest_tpu.obs import profiler

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1, 1), AXES)
    compiled = _train_step_lowered(mesh, F_10K, "compact", superstep=True,
                                   batch=4 * B).compile()
    text = compiled.as_text()
    assert _kernel_calls(compiled) == 4
    assert "all-gather" not in text
    assert _time_reversals(text) == []
    # one `shard_map` round the layer's VJP (ISSUE 42): a chip's backward
    # kernels sum the input bias's gradient over its own rows and the two
    # `[40,384]` sums cross `data` as bfloat16, as the parent's did
    assert _kernel_edge_passes(text) == []
    _assert_masks_drawn_once(text)          # each chip's own 32 windows
    moved = profiler.collective_bytes(text)
    print(f"compact 10k superstep under data=4 for a described v5e:2x2: "
          f"collectives a step {moved}")
    assert moved == {"all-reduce": 23_839_368}, moved
    assert _whole_leaf_copies(text) == 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9 and _need(mem) < 5.5e9, mem
