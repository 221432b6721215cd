"""`tenk-train-live4k-dp4`'s superstep (ISSUE 44), compiled for a TPU v5e
2x2 that is described and not attached: `tests/test_chip_compile.py`'s
rehearsal for the one program of the benchmark that runs across chips at a
wide table.  A file of its own so that xdist's `loadfile` gives its one
four-chip compile a worker beside that file's (the driver's command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD``, so two workers may describe a topology at
once; where libtpu cannot be loaded the fixture skips, as there).

Nothing runs here: a pass is a count of bytes and of instructions, never a
rate.  The chips' readings are PERF.md's (section 6, PR 44).
"""

import re

import numpy as np
import pytest
from jax.sharding import Mesh

from deeprest_tpu.obs import profiler
from deeprest_tpu.ops import scopes
from deeprest_tpu.parallel.mesh import AXES
from test_chip_compile import (   # noqa: F401 — `topo` is a fixture
    B, E, F_10K, H, TABLE_4K, W, _assert_masks_drawn_once, _kernel_calls,
    _kernel_edge_passes, _need, _time_reversals, _train_step_lowered,
    _whole_leaf_copies, topo,
)

# what the partitioner hands the collectives each step at a table of 4,096
# under `data`=4 (PR 39's count for a described v5e:2x2; the configuration
# file's `all_reduce_bytes_per_step`, which the chips' gauge has to equal)
REDUCED_A_STEP = 259_768_968
NARROW_A_STEP = 23_839_368          # `tenk-train-dp4`: a table of 256


@pytest.fixture(scope="module")
def wide_table_under_data4(topo):
    """The compact superstep at a table of 4,096 under a mesh data=4 (global
    batch 128, the 1 x 32 plan of a 32-step epoch), compiled once for the
    cases that read it."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1, 1), AXES)
    compiled = _train_step_lowered(mesh, F_10K, "compact", superstep=True,
                                   batch=4 * B, table=TABLE_4K).compile()
    return compiled, compiled.as_text()


def test_wide_table_under_data4_reduces_the_tables_gradients(
        wide_table_under_data4):
    """What crosses the chips each step is the two w_ih gradients at the
    table's 4,096 rows and w_hh as the bfloat16 matmuls make them, the
    heads' and the loss: 259.8 MB, eleven times `tenk-train-dp4`'s 23.8,
    and still NOT the mask weights' float32 `[40,128,10240]` gradient
    (210 MB more), which every chip derives from the reduced w_ih gradient.
    Nothing is gathered: the state is whole on every chip."""
    _, text = wide_table_under_data4
    moved = profiler.collective_bytes(text)
    print(f"compact 10k superstep, a table of {TABLE_4K}, under data=4 for a "
          f"described v5e:2x2: collectives a step {moved}")
    assert moved == {"all-reduce": REDUCED_A_STEP}, moved
    bf16 = 2
    rows = 2 * E * (TABLE_4K - 256) * 3 * H * bf16   # what the table adds
    assert moved["all-reduce"] - NARROW_A_STEP == rows
    assert "all-gather" not in text
    assert moved["all-reduce"] < REDUCED_A_STEP + 4 * E * H * F_10K


def test_wide_table_under_data4_keeps_the_kernels_whole_and_named(
        wide_table_under_data4):
    """The recurrence stays four kernel calls under ``shard_map`` (a chip's
    32 windows), `kernel_operand_spaces` finds both kernels by the names
    `ops/scopes.KERNELS` gives them, no array is reversed in time, nothing
    is cut or summed at the kernels' edge, and each chip draws ONE mask of
    its own share."""
    compiled, text = wide_table_under_data4
    assert _kernel_calls(compiled) == 4
    found = profiler.kernel_operand_spaces(text, scopes.KERNELS)
    assert set(found) == set(scopes.KERNELS)
    assert all(sum(spaces.values()) > 0 for spaces in found.values()), found
    assert _time_reversals(text) == []
    assert _kernel_edge_passes(text) == []
    _assert_masks_drawn_once(text)


def test_wide_table_under_data4_needs_what_one_chip_needs(
        wide_table_under_data4):
    """A chip's memory is the one-chip cell's (`tenk-train-live4k`: the
    state whole, the table's windows 32 a chip): no whole-leaf copy, the
    table's windows and never F-wide ones, temporaries about 1.9-2.1 GB, so
    with the 4.46 GB of state the superstep stays under what `init_state`
    leaves at its peak (8.92 GB) and the peak stays `init_state`'s."""
    compiled, text = wide_table_under_data4
    mem = compiled.memory_analysis()
    print(f"... temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, needs "
          f"{_need(mem) / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB; whole-leaf copy "
          f"operations {_whole_leaf_copies(text)}")
    assert f"[{B},{W},{TABLE_4K}]" in text
    assert f"[{B},{W},{F_10K}]" not in text
    assert f"bf16[{E},{F_10K},{3 * H}]" not in text
    assert _whole_leaf_copies(text) == 0
    assert 1.5e9 < mem.temp_size_in_bytes < 2.3e9, mem
    assert _need(mem) < 6.8e9, mem


def test_wide_table_under_data4_names_its_collectives_for_the_readers(
        wide_table_under_data4):
    """Every collective of the compiled step is of a kind the program's
    table and the benchmark's reader know (`profiler.collective_kind`): a
    synchronous instruction, or an asynchronous `-start` / `-done` pair
    whose bytes are counted once, at the `-done`."""
    _, text = wide_table_under_data4
    kinds = {}
    for line in text.splitlines():
        m = profiler._INSTRUCTION.match(line)
        kind = m and profiler.collective_kind(m["opcode"])
        if kind:
            kinds[kind] = kinds.get(kind, 0) + 1
    print(f"... collective instructions by (kind, suffix): {kinds}")
    assert kinds and {k[0] for k in kinds} == {"all-reduce"}
    assert kinds.get(("all-reduce", "-start"), 0) == kinds.get(
        ("all-reduce", "-done"), 0)
    table = profiler.scope_table(text, scopes.STEP_SCOPES + scopes.KERNELS)
    rows = [k for k, v in table.items() if v == (profiler.COLLECTIVE, "-")]
    assert len(rows) == sum(kinds.values())
    assert not re.search(r"\breduce-scatter\b|\ball-to-all\b", text)
