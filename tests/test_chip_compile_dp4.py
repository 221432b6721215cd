"""`tenk-train-live4k-dp4`'s superstep (ISSUE 44), compiled for a TPU v5e
2x2 that is described and not attached: `tests/test_chip_compile.py`'s
rehearsal for the one program of the benchmark that runs across chips at a
wide table.  A file of its own so that xdist's `loadfile` gives its one
four-chip compile a worker beside that file's (the driver's command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD``, so two workers may describe a topology at
once; where libtpu cannot be loaded the fixture skips, as there).

Since ISSUE 45 the table's rows that ride the scan are split over ``data``
(`parallel/sharding.py`): a chip steps a quarter of them, the bfloat16
folded weight is gathered and the six float32 arrays are gathered once a
dispatch.  Since ISSUE 47 the weight's gradient is no dot and reduce-scatter
one after the other but a ring of chunk dots whose partial sums travel by
`collective-permute` while the next chunk's dots run
(`sharding.project_split_rows`).  Since ISSUE 49 the folded weight's gather
is cut too, along the experts: a group's rows arrive while the group before
it is projected, so of a step's gathers only the first piece stands alone.

Nothing runs here: a pass is a count of bytes and of instructions, never a
rate.  The chips' readings are PERF.md's (section 6, PRs 44 and 45).
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

# what the partitioner handed the all-reduces each step at a table of 4,096
# under `data`=4 until ISSUE 45 (PR 39's count for a described v5e:2x2; the
# configuration file's `all_reduce_bytes_per_step`), and what it still does:
# w_hh as the bfloat16 matmuls make it, the biases, the heads, what the mask's
# backward needs of the fold, the loss
REDUCED_A_STEP = 259_768_968
NARROW_A_STEP = 23_839_368          # `tenk-train-dp4`: a table of 256
SMALL_A_STEP = 9_749_128
STEPS = 32                          # of the 1 x 32 plan
SPLIT = 4                           # the mesh's `data` axis
ROWS = TABLE_4K // SPLIT            # the rows one chip carries
# the ring of a step (ISSUE 47): two directions of the GRU, three hops, a
# half of a chunk's rows each way round
PERMUTES = 2 * (SPLIT - 1) * 2
HALF = f"bf16[{E},{ROWS // 2},{3 * H}]"
# the forward's gather of a step (ISSUE 49): a direction's folded weight in
# eight groups of five experts, 3.9 MB from each chip a piece
PIECES = 8
PIECE = f"bf16[{E // PIECES},{TABLE_4K},{3 * H}]"
GATHERED_A_STEP = 298_844_160       # the two folded weights and 6 x f32 / 32


@pytest.fixture(scope="module")
def wide_table_under_data4(topo):
    """The compact superstep at a table of 4,096 under a mesh data=4 (global
    batch 128, the 1 x 32 plan of a 32-step epoch), compiled once for the
    cases that read it."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1, 1), AXES)
    compiled = _train_step_lowered(mesh, F_10K, "compact", superstep=True,
                                   batch=4 * B, table=TABLE_4K).compile()
    return compiled, compiled.as_text()


def _loop(text: str) -> list[str]:
    """The lines of the step: what the scan's loop holds, by `op_name`."""
    return [line for line in text.splitlines() if "/while/" in line]


def test_wide_table_under_data4_sends_the_tables_gradients_round_a_ring(
        wide_table_under_data4):
    """What crosses the chips: the two w_ih gradients at the table's 4,096
    rows go round a RING (ISSUE 47): twelve `collective-permute` pairs a step
    of half a chunk's rows, `bf16[40,512,384]`, three of a chip's four
    quarters arriving a direction (on the links what the reduce-scatter of
    ISSUE 45 and the all-reduce before it moved), and no `%all-reduce-scatter`
    fusion and no `reduce-scatter` anywhere; the bfloat16 folded weight is
    gathered twice a step, the six float32 arrays of the carried rows once a
    dispatch, outside the loop, and what is still all-reduced is what a
    table of any width reduces.  Still NOT the mask weights' float32
    `[40,128,10240]` gradient (210 MB more), which every chip derives from
    its rows."""
    _, text = wide_table_under_data4
    moved = profiler.collective_bytes(text, STEPS)
    print(f"compact 10k superstep, a table of {TABLE_4K}, under data=4 for a "
          f"described v5e:2x2: collectives a step {moved}")
    bf16, f32 = 2, 4
    whole = E * TABLE_4K * 3 * H
    assert moved == {
        "all-reduce": SMALL_A_STEP,
        "collective-permute": 2 * whole * bf16 * (SPLIT - 1) // SPLIT,
        "all-gather": 2 * whole * bf16 + 6 * whole * f32 // STEPS}, moved
    assert moved["collective-permute"] == 188_743_680
    # the parent's all-reduce less the table's rows is what is left of it
    assert REDUCED_A_STEP - 2 * whole * bf16 < SMALL_A_STEP < NARROW_A_STEP
    assert "all-reduce-scatter" not in text
    assert not re.search(r"\breduce-scatter\b|\ball-to-all\b", text)
    assert not re.findall(
        rf"= bf16\[{E},{TABLE_4K},{3 * H}\]\S* all-reduce\(", text)
    loop = _loop(text)
    for end in ("-start", "-done"):
        pairs = [line for line in loop
                 if re.search(rf" collective-permute{end}\(", line)]
        assert len(pairs) == PERMUTES, (end, len(pairs))
        assert all(f"{HALF}{{2,1,0" in line for line in pairs), pairs[0]
    assert not re.search(r" collective-permute\(", text)   # none synchronous
    # a dispatch's six gathers stand outside the loop, and only they do
    outside = [line for line in text.splitlines()
               if " all-gather(" in line and "/while/" not in line]
    assert len(outside) == 6 and all(
        f" = f32[{E},{TABLE_4K},{3 * H}]" in line for line in outside)


def _schedule(text: str) -> list:
    """The loop's instructions in the order the scheduler left them (the
    module is scheduled: a computation's lines are its sequence)."""
    assert "is_scheduled=true" in text.splitlines()[0]
    computations, _ = profiler.instruction_lines(text)
    (body,) = [lines for lines in computations.values()
               if any(m["opcode"] == "collective-permute-start"
                      for m, _ in lines)]
    return body


def test_wide_table_under_data4_hides_every_permute_behind_a_dot(
        wide_table_under_data4):
    """The ring's point: between a permute's `-start` and its `-done` the
    chip has a chunk dot of `in_proj`'s backward to run (a fusion whose
    `op_name` holds the scope and a `dot_general`): every pair of the step
    but the first to be done.  A hop's addition is
    fused into its dot, which waits for its own arrival, so what a permute
    encloses is the dots of the other half and of the other direction's
    ring, interleaved by the scheduler.  Written without the barrier that
    ties a stage's dots to the sums it sends, the scheduler made all
    sixteen dots first and the six stages stood alone after them: that
    form fails here."""
    _, text = wide_table_under_data4
    body = _schedule(text)
    started, ended, enclosed = {}, {}, {}
    for at, (m, line) in enumerate(body):
        if m["opcode"] == "collective-permute-start":
            started[m["name"]] = at
        elif m["opcode"] == "collective-permute-done":
            begun = re.search(r"collective-permute-done\(%?([\w.\-]+)",
                              line)[1]
            ended[begun] = at
            enclosed[begun] = [
                inner["name"] for inner, between in body[started[begun]:at]
                if inner["opcode"] == "fusion"
                and re.search(rf'op_name="[^"]*transpose[^"]*/{scopes.IN_PROJ}'
                              r'/[^"]*dot_general', between)]
    assert len(enclosed) == PERMUTES == len(started)
    print(f"... dots between a permute's start and done: {enclosed}")
    # but the step's first: the four first-stage permutes start together,
    # behind the four dots that make their sums, and the first to be done
    # has what else is ready between (the heads' backward, w_hh's Adam)
    bare = [name for name, dots in enclosed.items() if not dots]
    assert len(bare) <= 1, enclosed
    for name in bare:
        assert started[name] == min(started.values())
        assert any(m["opcode"] == "fusion"
                   for m, _ in body[started[name]:ended[name]])
    # sixteen chunk dots a step over half a chunk's rows, each rounded for
    # the wire where it is made (a hop's addition is fused into its dot)
    dots = {name for names in enclosed.values() for name in names}
    made = [m["name"] for m, line in body if m["opcode"] == "fusion"
            and f" = {HALF}" in line and "dot_general" in line]
    assert len(made) == 2 * SPLIT * 2 and dots <= set(made), (made, dots)
    assert not any(f" = f32[{E},{ROWS // 2},{3 * H}]" in line
                   for _, line in body)          # no partial kept apart


def _gather_chains(text: str, body: list) -> dict:
    """The asynchronous gathers of the loop: ``{chain_id: [(position in the
    schedule, instruction name), ...]}`` of the fusions whose computation
    holds a piece of the chain (XLA:TPU's start, the fusion the transfer
    runs beside, the done)."""
    computations, _ = profiler.instruction_lines(text)
    chain_of = {}
    for comp, lines in computations.items():
        for m, line in lines:
            found = re.search(r'chain_id="(\d+)"', line)
            if found and m["opcode"] == "all-gather":
                chain_of[comp] = int(found[1])
    chains = {}
    for at, (m, line) in enumerate(body):
        called = profiler._CALLS.search(line)
        if m["opcode"] == "fusion" and called and called[1] in chain_of:
            chains.setdefault(chain_of[called[1]], []).append(
                (at, m["name"], line))
    return chains


def test_wide_table_under_data4_hides_every_gather_behind_a_projection(
        wide_table_under_data4):
    """ISSUE 49's point: a direction's folded weight reaches the projection
    in `PIECES` groups of experts, and every piece of a step but the first
    is an asynchronous chain whose transfer runs INSIDE a forward dot of
    `in_proj` (the group before it, the second direction's first piece
    beside the first direction's last dot): between a chain's start and its
    done lies exactly that fusion.  The one synchronous `all-gather` of the
    loop is the step's first piece.  The bytes gathered a step are the
    parent's, nothing is gathered whole, no partial sum of the projection
    is kept (the contraction is not cut), and the groups' results are
    joined where the dots write them: no `concatenate` is left."""
    _, text = wide_table_under_data4
    assert profiler.collective_bytes(text, STEPS)[
        "all-gather"] == GATHERED_A_STEP
    body = _schedule(text)
    alone = [(at, line) for at, (m, line) in enumerate(body)
             if m["opcode"] == "all-gather"]
    assert len(alone) == 1 and f" = {PIECE}" in alone[0][1], alone
    chains = _gather_chains(text, body)
    assert len(chains) == 2 * PIECES - 1, sorted(chains)
    forward_dot = re.compile(
        rf'op_name="[^"]*/jvp\(QuantileGRU\)/{scopes.IN_PROJ}/[^"]*dot_general')
    spans = []
    for chain, pieces in chains.items():
        (begin, start, _), (at, _, dot), (end, done, last) = pieces
        assert profiler.collective_kind(start) == (
            profiler.ASYNC_COLLECTIVE, "-start"), pieces
        assert profiler.collective_kind(done) == (
            profiler.ASYNC_COLLECTIVE, "-done"), pieces
        assert begin < at < end and forward_dot.search(dot), (chain, dot)
        assert f" = {PIECE}" in last          # what arrives: a group's rows
        spans.append((begin, end))
    # one transfer at a time, each behind the step's first piece
    spans.sort()
    assert alone[0][0] < spans[0][0]
    assert all(done < begin for (_, done), (begin, _) in zip(spans, spans[1:]))
    # the 2 x PIECES dots: all but the step's last hide a transfer
    dots = [m["name"] for m, line in body
            if m["opcode"] == "fusion" and forward_dot.search(line)]
    assert len(dots) == 2 * PIECES, dots
    loop = _loop(text)
    assert not any(f"bf16[{E},{TABLE_4K},{3 * H}]" in line for line in loop)
    assert not any(f" = f32[{E},{W},{B},{3 * H}]" in line for line in loop)
    assert not any(re.search(rf" = bf16\[{E},{W},{B},{3 * H}\]\S* "
                             r"(concatenate|copy)\(", line) for line in loop)


def test_wide_table_under_data4_walks_the_chips_as_they_sit(
        wide_table_under_data4, topo):
    """The ring's neighbours are neighbours on the board (`parallel/mesh.
    data_ring`): a described v5e 2x2 lists its chips (0,0), (1,0), (0,1),
    (1,1), so 0 -> 1 -> 2 -> 3 -> 0 would cross the diagonal twice; half of
    the permutes go one way round 0 -> 2 -> 3 -> 1 -> 0, half the other."""
    _, text = wide_table_under_data4
    where = {d.id: tuple(d.coords) for d in topo.devices}
    ways = {}
    for line in _loop(text):
        if " collective-permute-start(" in line:
            pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", line)[1]
            pairs = tuple(tuple(int(i) for i in p.split(","))
                          for p in re.findall(r"\{?(\d+,\d+)", pairs))
            ways[pairs] = ways.get(pairs, 0) + 1
    assert sorted(ways.values()) == [PERMUTES // 2] * 2, ways
    one, other = ways
    assert sorted(other) == sorted((b, a) for a, b in one)
    for a, b in one:
        assert sum(abs(p - q) for p, q in zip(where[a], where[b])) == 1


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
    """A chip's memory is under the one-chip cell's (`tenk-train-live4k`:
    the state whole, the table's windows 32 a chip): no whole-leaf copy, the
    table's windows and never F-wide ones, the rows' Adam on a chip's
    `[40,1024,384]` and no float32 array of the whole table inside the loop,
    temporaries about 1.55 GB (ISSUE 44's program: 2.02; the ring's float32
    partials and travelling sums take no more than the whole bfloat16
    products they replace), so with the 4.46 GB of state the superstep stays
    under what `init_state` leaves at its peak (8.92 GB) and the peak stays
    `init_state`'s; and the ring's halves reach the rows' Adam as they lie
    (`{2,1,0}`), with no copy of a chip's `bf16[40,1024,384]` between."""
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
    loop = _loop(text)
    assert not any(f"f32[{E},{TABLE_4K},{3 * H}]" in line for line in loop)
    adam = [line.split(" fusion(")[0] for line in loop
            if "/optimizer/" in line and " fusion(" in line
            and f"f32[{E},{ROWS},{3 * H}]" in line.split(" fusion(")[0]]
    assert adam and adam[0].count(f"f32[{E},{ROWS},{3 * H}]") == 6, adam
    assert 1.2e9 < mem.temp_size_in_bytes < 1.75e9, mem
    assert _need(mem) < 6.3e9, mem
    assert not re.search(
        rf"= bf16\[{E},({ROWS}|{ROWS // 2}),{3 * H}\]\{{[^}}]*\}} copy\(", text)


def test_wide_table_under_data4_names_its_collectives_for_the_readers(
        wide_table_under_data4):
    """Every collective of the compiled step is of a kind the program's
    table knows (`profiler.collective_kind`) and lies in `scope_table`'s
    `collective` row: a synchronous instruction, the ring's permutes by
    their `-start` and `-done` (ISSUE 47: names the benchmark's reader
    knows too, where it did not see the `fusion.N` scatters they replace),
    XLA:TPU's wrapper fusions (the start and the done of each asynchronous
    gather, whose three pieces carry one `chain_id` and count once: since
    ISSUE 49 every piece of the folded weights but the step's first), and no
    fusion of the work a chain or a permute hides behind: the chunk dots
    and the groups' projections stay in `in_proj`'s rows."""
    _, text = wide_table_under_data4
    table = profiler.scope_table(text, scopes.STEP_SCOPES + scopes.KERNELS)
    rows = sorted(k for k, v in table.items()
                  if v == (profiler.COLLECTIVE, "-"))
    print(f"... the collective row holds {rows}")
    kinds = {}
    for name in rows:
        kind = profiler.collective_kind(name) or ("wrapped", "")
        kinds[kind] = kinds.get(kind, 0) + 1
    assert ("wrapped", "") not in kinds          # ISSUE 45's two scatters
    assert kinds.pop((profiler.ASYNC_COLLECTIVE, "-start")) == 2 * PIECES - 1
    assert kinds.pop((profiler.ASYNC_COLLECTIVE, "-done")) == 2 * PIECES - 1
    assert kinds.pop(("collective-permute", "-start")) == PERMUTES
    assert kinds.pop(("collective-permute", "-done")) == PERMUTES
    assert set(kinds) == {("all-reduce", ""), ("all-gather", "")}, kinds
    assert kinds[("all-gather", "")] == 1 + 6     # a step's first, the six
    # a collective instruction anywhere is in the row itself or inside a
    # fusion: a wrapper of the row, or the one that hides the chain
    computations, _ = profiler.instruction_lines(text)
    holders = {comp for comp, lines in computations.items()
               if any(profiler.collective_kind(m["opcode"])
                      for m, _ in lines)}
    calling = {m["name"]: profiler._CALLS.search(line)[1]
               for lines in computations.values() for m, line in lines
               if m["opcode"] == "fusion"}
    fused = {comp for comp in holders if comp in calling.values()}
    hiding = [name for name, comp in calling.items()
              if comp in fused and name not in rows]
    assert len(hiding) == 2 * PIECES - 1 and all(
        table[name] == (scopes.IN_PROJ, "fwd") for name in hiding), hiding
    dots = [m["name"] for m, line in _schedule(text)
            if m["opcode"] == "fusion" and f" = {HALF}" in line]
    assert len(dots) == 2 * SPLIT * 2 and all(
        table[name] == (scopes.IN_PROJ, "bwd") for name in dots), dots
    chained = set(re.findall(r'chain_id="(\d+)"', text))
    assert len(chained) == 2 * PIECES - 1
    for comp in holders - fused:
        for m, _ in computations[comp]:
            if profiler.collective_kind(m["opcode"]):
                assert m["name"] in rows, m["name"]


def test_narrow_table_under_data4_stays_with_the_partitioner(topo):
    """`tenk-train-dp4`'s table of 256 under the same mesh lies under the
    rule (`sharding.ring_scatters`: 0.98 MB a hop), so nothing of ISSUEs 47
    and 49 engages: the folded weights are gathered WHOLE (one synchronously,
    the other in the one asynchronous chain), the gradients are the
    partitioner's two `%all-reduce-scatter` fusions, no permute, no piece,
    and the bytes a step are PR 45's.  (Instruction by instruction the text
    is the parent's of ISSUE 49 in the parent's order, shapes and layouts,
    under other instruction numbers: PERF.md section 6, PR 49.)"""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1, 1), AXES)
    text = _train_step_lowered(mesh, F_10K, "compact", superstep=True,
                               batch=4 * B, table=256).compile().as_text()
    assert profiler.collective_bytes(text, STEPS) == {
        "all-reduce": SMALL_A_STEP, "reduce-scatter": 3_932_160,
        "all-gather": 18_677_760}
    assert "collective-permute" not in text
    assert len(set(re.findall(r'chain_id="(\d+)"', text))) == 1
    whole = f"bf16[{E},256,{3 * H}]"
    gathered = [line for line in _loop(text) if re.search(
        r"^\s*(ROOT )?%\S+ = \S+ all-gather\(", line)]
    assert gathered and all(f" = {whole}" in line for line in gathered)
    scatters = [line for line in text.splitlines()
                if " fusion(" in line and "all-reduce-scatter" in line]
    assert len(scatters) == 2, len(scatters)
    # no group of experts' rows anywhere: every array of the table's rows
    # holds all forty experts
    assert not re.search(rf"bf16\[\d+,256,{3 * H}\]", text.replace(whole, ""))


def test_one_chip_compact_superstep_holds_nothing_of_the_split(topo):
    """With `data` = 1 the rule engages nothing: the compact superstep at the
    same table, compiled for one described chip, holds no collective and no
    array of a quarter of the table's rows (its text is the parent's but for
    the line numbers in the kernels' serialized modules: PERF.md, PR 45)."""
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1), AXES)
    text = _train_step_lowered(mesh, F_10K, "compact", superstep=True,
                               table=TABLE_4K).compile().as_text()
    assert profiler.collective_bytes(text) == {}
    assert "collective-permute" not in text
    table = profiler.scope_table(text, scopes.STEP_SCOPES + scopes.KERNELS)
    assert (profiler.COLLECTIVE, "-") not in table.values()
    assert f"[{E},{ROWS},{3 * H}]" not in text
    assert f"f32[{E},{TABLE_4K},{3 * H}]" in text
