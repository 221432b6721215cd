"""Fused pallas TPU kernel for the GRU recurrence.

The `lax.scan` recurrence in ops/gru.py lowers to an XLA while-loop whose
per-step state round-trips through HBM and whose per-step matmul is far too
small to hide loop overhead (B=32, H=128 — latency-bound, SURVEY.md §7.3).
This kernel runs the whole time loop *inside one pallas invocation*:

- grid = (expert_blocks, T) with time as the innermost (sequential) grid
  dimension; the hidden state lives in a VMEM scratch buffer that persists
  across time steps — zero HBM traffic for the carry;
- the DIRECTION of the scan is the order in which the grid visits the
  array's time axis, never a copy of the array: the forward direction's
  grid step ``j`` is time block ``j`` and the loop inside a block ascends;
  the reverse direction's (``reverse=True``) is block ``nb - 1 - j`` by the
  blocks' ``index_map`` and the loop descends.  Every array a kernel reads
  or writes stays time-aligned with ``proj`` in HBM;
- the hoisted input projections ``proj = x @ W_ih + b_ih`` (computed
  outside, one large MXU matmul) stream through VMEM blocks, double-
  buffered by the pallas pipeline;
- ``W_hh`` is indexed only by the expert block, so it stays resident in
  VMEM for all T steps of that block;
- the backward pass is a second pallas kernel walking time AGAINST the
  scan's order (back to front for the forward direction, front to back
  for the reverse one), recomputing gate activations from (proj, h_prev and the
  hidden-side gate pre-activations the training forward stashed) and
  accumulating weight and bias gradients in VMEM scratch, flushed to HBM
  on the final step.

Only the recurrence is hand-written: input/output projections, the feature
mask, mixing, and heads remain plain XLA einsums (models/qrnn.py), which
XLA already fuses well. Numerics match ops/gru.py's scan (gate order r,z,n;
``n = tanh(x_n + b_in + r · (h·W_hn + b_hn))``).

The VJP's boundary (:func:`gru_recurrence`) is one LAYER, not one kernel
call: it starts at the add of the input bias to the projection's einsum
and ends behind the join of the layer's directions, one operation further
out on each side than the kernels' values need.  Autodiff of those two
operations outside it read the kernels' largest arrays again to compute
nothing the backward kernel does not hold: a ``reduce_sum`` over each
direction's ``dproj`` for ``db_ih`` (the kernel has the float32 gate
gradients in registers and sums them for ``db_hh`` already), and a
``split`` that copied the joined cotangent into one array a direction (a
``BlockSpec`` reads a direction's H lanes where they lie).  With both
inside, the compiled step holds neither
(obs/profiler.kernel_edge_passes; PERF.md section 6, PR 42).

Used automatically on TPU backends (ops/gru.py dispatch); `interpret=True`
makes every entry point runnable on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeprest_tpu.ops import scopes

# Experts per kernel program at most: amortizes grid overhead while
# keeping VMEM residency (W_hh alone is _E_BLK * H * 3H * 4B).  A ceiling:
# _choose_blocks derives the plan of a call from its shape.
_E_BLK = 8
# Time steps per kernel program at most.  Each program advances the
# recurrence that many steps with the hidden state in VMEM scratch: fewer
# grid programs and fewer (larger) DMA blocks.  Inside a program the loop
# runs time-OUTER, expert-INNER so each step issues e_blk *independent*
# matmuls that pipeline through the MXU (expert-outer would serialize
# each expert's whole chain: PERF.md section 6, PR 28).  Callers pad T up
# to a multiple (pad_time); padded tail steps compute garbage that is
# sliced off, which is safe because the tail is beyond every real output
# in scan order.
_T_BLK = 6
# f32 sublane granularity — batch is padded up to this.
_SUBLANE = 8
# [H] sums the backward kernel keeps for the two biases' gradients: of
# da_r, da_z, dhn and dtanh (_bwd_kernel).
_N_DB = 4
# Scoped-VMEM budget for one kernel program: the compiler's 16 MiB limit
# less 1 MiB for Mosaic's own fixed scratch.  The per-expert byte models
# below count everything else: blocks indexed by the sequential time grid
# twice (the pallas pipeline double-buffers them), resident blocks once
# (_resident), scratch, and the in-kernel temporaries (_temp_bytes).
_VMEM_BUDGET = 15 << 20


def _legal_blocks(e: int, t: int) -> tuple[list[int], list[int]]:
    """Legal expert blocks (ascending) and time blocks (descending)."""
    legal_e = [c for c in range(_SUBLANE, e + 1, _SUBLANE)
               if e % c == 0 and c <= _E_BLK] or [e]
    t_candidates = [c for c in range(min(_T_BLK, t), 0, -1) if t % c == 0]
    return legal_e, t_candidates


def _choose_blocks(e: int, t: int, per_expert_bytes,
                   shape: str = "") -> tuple[int, int]:
    """Pick (e_blk, t_blk) whose footprint fits the scoped-VMEM budget,
    or raise when none does.

    The f32 backward kernel at e_blk=8/t_blk=6 needs ~21 MB
    — over the chip's 16 MiB scoped-VMEM limit, a hard compile error —
    while the bf16 production path fits.  The expert axis is the sublane
    of the 2-D f32 bias blocks, so pallas requires e_blk % 8 == 0 (or
    e_blk == e); the time axis is grid-leading and unconstrained, so VMEM
    pressure is relieved by shrinking t_blk.  ``per_expert_bytes`` maps
    t_blk → bytes per expert; ``shape`` names the call in the error.
    Correctness is unaffected (experts independent; the kernels carry
    hidden state across time blocks in scratch)."""
    legal_e, t_candidates = _legal_blocks(e, t)
    # Prefer the widest expert block; shrink time first, then experts.
    for e_blk in reversed(legal_e):
        for t_blk in t_candidates:
            if e_blk * per_expert_bytes(t_blk) <= _VMEM_BUDGET:
                return e_blk, t_blk
    e_min, t_min = legal_e[0], t_candidates[-1]
    raise ValueError(
        f"GRU kernel {shape or f'E={e} T={t}'} does not fit scoped VMEM: "
        f"the smallest plan (e_blk={e_min}, t_blk={t_min}) needs "
        f"{e_min * per_expert_bytes(t_min)} bytes against a budget of "
        f"{_VMEM_BUDGET}; use fewer rows per call, or bfloat16")


def _resident(block_shape):
    """BlockSpec of a block indexed by the expert grid axis only (W_hh,
    b_hh, h0 in; dW/db/dh0 out).  It changes once per expert block, so it
    is single-buffered: the pipeline's default second buffer would cost
    VMEM (2.4 MB in the bf16 backward at e_blk=8) and hide no DMA."""
    zeros = (0,) * (len(block_shape) - 1)
    return pl.BlockSpec(block_shape, lambda i, j: (i, *zeros),
                        pipeline_mode=pl.Buffered(1))


def _time_map(nb: int, descending: bool, lane_block: int = 0):
    """Index map of a block indexed by the sequential time grid: grid step
    ``j`` is time block ``j``, or block ``nb - 1 - j`` where the kernel
    walks the array's time axis back to front.  ``lane_block``: which
    block of the last axis, for an array wider than its block there (the
    joined cotangent, of which a direction reads its own H lanes)."""
    if descending:
        return lambda i, j: (i, nb - 1 - j, 0, lane_block)
    return lambda i, j: (i, j, 0, lane_block)


def _walk(t_blk: int, descending: bool):
    """The steps of one time block in the order the kernel takes them."""
    return reversed(range(t_blk)) if descending else range(t_blk)


def _gates(xproj, gates_h):
    """Shared gate math. xproj/gates_h: [B, 3H] → (r, z, n)."""
    xr, xz, xn = jnp.split(xproj, 3, axis=-1)
    hr, hz, hn = jnp.split(gates_h, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    return r, z, n, hn


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(proj_ref, w_ref, b_ref, h0_ref, *refs, dot_dtype, emit_prev,
                reverse):
    # Training (emit_prev=True) also streams out the PRE-update hidden
    # state per step: the VJP consumes h_prev directly instead of
    # re-materializing it outside the kernel as concat(h0, h_all[:-1]) —
    # one full [E,T,B,H] HBM round-trip saved per step.  The
    # pre-activation hidden gates (h·W_hh + b_hh) stream out too, so the
    # backward skips its recompute dot: one [B,H]x[H,3H] MXU dot less per
    # expert-step for one extra [E,T,B,3H] stream in the kernel's I/O
    # dtype each way (recomputing read slower on the chip in both cells,
    # PERF.md section 6, PR 28).
    if emit_prev:
        out_ref, prev_ref, gates_ref, h_scr = refs
    else:
        (out_ref, h_scr), prev_ref, gates_ref = refs, None, None
    t = pl.program_id(1)

    @pl.when(t == 0)  # first grid step == first time block in scan order
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    n_e, t_blk = proj_ref.shape[0], proj_ref.shape[1]
    hs = [h_scr[i] for i in range(n_e)]
    ws = [w_ref[i].astype(dot_dtype) for i in range(n_e)]
    bs = [b_ref[i].astype(jnp.float32) for i in range(n_e)]

    def step(i, tt):
        if prev_ref is not None:
            prev_ref[i, tt] = hs[i].astype(prev_ref.dtype)
        gates_h = (
            jax.lax.dot_general(hs[i].astype(dot_dtype), ws[i],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + bs[i]
        )
        if gates_ref is not None:
            gates_ref[i, tt] = gates_h.astype(gates_ref.dtype)
        xproj = proj_ref[i, tt].astype(jnp.float32)
        r, z, n, _ = _gates(xproj, gates_h)
        hs[i] = (1.0 - z) * n + z * hs[i]
        out_ref[i, tt] = hs[i].astype(out_ref.dtype)

    for tt in _walk(t_blk, reverse):  # time OUTER, in scan order
        for i in range(n_e):          # experts INNER: independent matmuls
            step(i, tt)
    for i in range(n_e):
        h_scr[i] = hs[i]


def _dot_dtype_for(proj_dtype):
    """bf16 models run the recurrence matmuls in bf16 with f32 accumulation
    (an f32 matmul costs ~3x the MXU passes of bf16 and the model's own
    dtype is bf16 — the hidden-state CARRY stays f32 in VMEM either way);
    f32 models keep exact f32 dots."""
    return jnp.bfloat16 if proj_dtype == jnp.bfloat16 else jnp.float32


def _out_dtype_for(proj_dtype):
    """Hidden-state STORAGE dtype: bf16 models stream h in bf16 (the model
    casts h_all to its own dtype right after the kernel anyway — f32
    storage only doubled the largest HBM stream); f32 models stay exact.

    Currently coincides with _dot_dtype_for (matmul precision), but the
    two are distinct knobs: storage feeds the VJP's h_prev residual — and
    the _bwd_call byte accounting — while the dot dtype only picks the
    MXU path.  Change one without the other deliberately, not by drift.
    Accepted approximation for bf16 models: the backward's dz term
    (dh·(h_prev − n)) sees bf16-rounded h_prev where it previously saw
    the exact f32 carry — ~2^-9 relative, inside the bf16 training noise
    floor, and covered by the bf16 grad-parity test tolerances."""
    return jnp.bfloat16 if proj_dtype == jnp.bfloat16 else jnp.float32


def _temp_bytes(b, g3, h, proj_dtype):
    """In-kernel temporaries per expert that Mosaic keeps in VMEM beside
    the blocks: the f32 gate pre-activations ``[B, 3H]`` of every expert
    of the program are in flight at once, and the bf16-dot path adds the
    ``[B, H]`` casts of the carry.  Fitted to what the v5e compiler
    reports (jax 0.9.0, libtpu 0.0.34) at B = 32..256, t_blk = 1..6; with
    the 1 MiB kept out of ``_VMEM_BUDGET`` it bounds every reading from
    above.  tests/test_chip_compile.py holds it to the compiler."""
    casts = b * h * 4 if _dot_dtype_for(proj_dtype) == jnp.bfloat16 else 0
    return b * g3 * 4 + casts


def _fwd_per_expert_bytes(b, g3, h, proj_dtype, emit_prev,
                          w_itemsize, h0_itemsize):
    """Forward-kernel VMEM bytes per expert as a function of t_blk — the
    single source for _choose_blocks AND the public block_plan probe."""
    io = jnp.dtype(proj_dtype).itemsize
    oo = jnp.dtype(_out_dtype_for(proj_dtype)).itemsize
    n_h_out = 2 if emit_prev else 1
    return lambda t_blk: (
        # proj in + h out (+ prev out and gates out when training),
        # double-buffered
        2 * (t_blk * b * g3 * io + n_h_out * t_blk * b * h * oo
             + (t_blk * b * g3 * io if emit_prev else 0))
        + h * g3 * w_itemsize + g3 * 4                   # W_hh, b_hh resident
        + b * h * h0_itemsize + b * h * 4                # h0 block + scratch
        + _temp_bytes(b, g3, h, proj_dtype)
    )


def _fwd_call(proj, w_hh, b_hh, h0, interpret, emit_prev=False,
              reverse=False):
    e, t, b, g3 = proj.shape
    h = g3 // 3
    assert t % _T_BLK == 0, (t, _T_BLK)   # callers pad_time first
    out_dtype = _out_dtype_for(proj.dtype)
    per_expert = _fwd_per_expert_bytes(b, g3, h, proj.dtype, emit_prev,
                                       w_hh.dtype.itemsize, h0.dtype.itemsize)
    e_blk, t_blk = _choose_blocks(
        e, t, per_expert,
        f"forward{' (training)' if emit_prev else ''} "
        f"E={e} T={t} B={b} H={h} {proj.dtype}")
    eb = e // e_blk
    nb = t // t_blk
    grid = (eb, nb)
    # the reverse direction walks the time blocks back to front
    by_time = _time_map(nb, reverse)
    g_spec = pl.BlockSpec((e_blk, t_blk, b, g3), by_time)
    h_spec = pl.BlockSpec((e_blk, t_blk, b, h), by_time)
    h_shape = jax.ShapeDtypeStruct((e, t, b, h), out_dtype)
    out_specs, out_shape = h_spec, h_shape
    if emit_prev:
        out_specs = [h_spec, h_spec, g_spec]
        out_shape = [h_shape, h_shape,
                     jax.ShapeDtypeStruct((e, t, b, g3), proj.dtype)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dot_dtype=_dot_dtype_for(proj.dtype),
                          emit_prev=emit_prev, reverse=reverse),
        grid=grid,
        in_specs=[
            g_spec,
            _resident((e_blk, h, g3)),
            _resident((e_blk, g3)),
            _resident((e_blk, b, h)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((e_blk, b, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=scopes.GRU_KERNEL_FWD,
    )(proj, w_hh, b_hh, h0)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(proj_ref, hprev_ref, gates_in_ref, w_ref, dout_ref,
                dproj_ref, dw_ref, db_ref, dh0_ref,
                dh_scr, dw_scr, db_scr, dg_scr, *, dot_dtype, reverse):
    # b_hh is no operand: the stashed gates hold it already.  dout_ref is
    # this direction's H lanes of the layer's joined cotangent, cut out by
    # the block's index_map (_bwd_call).
    t = pl.program_id(1)
    t_total = pl.num_programs(1)

    @pl.when(t == 0)  # first grid step == last time block in scan order
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    n_e, t_blk = proj_ref.shape[0], proj_ref.shape[1]
    ws = [w_ref[i].astype(dot_dtype) for i in range(n_e)]
    dhs = [dh_scr[i] for i in range(n_e)]
    hh = dh_scr.shape[-1]
    # Bias-gradient accumulators, one [1, H] row per sum: Mosaic refuses a
    # 1-D concat of the [H] sums ("Input offsets outside of the first
    # tile"), so they stay apart and land in their 128-aligned lane slices
    # of db_scr at the end of the program, like dproj/dg below.  Four sums
    # serve both biases (_N_DB): db_hh is (da_r, da_z, dhn), db_ih is (da_r,
    # da_z, dtanh), the row sums of what goes out as dproj, taken here from
    # the float32 gate gradients and not from a second pass over dproj.
    dbs = [[db_scr[i:i + 1, k * hh:(k + 1) * hh] for k in range(_N_DB)]
           for i in range(n_e)]

    def step(i, tt):
        h_prev = hprev_ref[i, tt].astype(jnp.float32)
        # Forward stashed the pre-activation hidden gates — no recompute
        # dot (it would be 1/3 of this kernel's per-step MXU work).
        gates_h = gates_in_ref[i, tt].astype(jnp.float32)
        xproj = proj_ref[i, tt].astype(jnp.float32)
        r, z, n, hn = _gates(xproj, gates_h)

        dh_total = dout_ref[i, tt].astype(jnp.float32) + dhs[i]
        dn = dh_total * (1.0 - z)
        dz = dh_total * (h_prev - n)
        dtanh = dn * (1.0 - n * n)
        da_r = dtanh * hn * r * (1.0 - r)
        da_z = dz * z * (1.0 - z)
        dhn = dtanh * r
        # Gate-sliced stores instead of jnp.concatenate: each concat is a
        # full [B,3H] VPU copy per expert-step; the gate pieces land
        # directly in their 128-aligned lane slices of the output block
        # and the dgates stash (dot dtype — the SAME quantization the
        # old per-step dW dot applied).
        dproj_ref[i, tt, :, 0:hh] = da_r.astype(dproj_ref.dtype)
        dproj_ref[i, tt, :, hh:2 * hh] = da_z.astype(dproj_ref.dtype)
        dproj_ref[i, tt, :, 2 * hh:3 * hh] = dtanh.astype(dproj_ref.dtype)
        dg_scr[i, tt, :, 0:hh] = da_r.astype(dg_scr.dtype)
        dg_scr[i, tt, :, hh:2 * hh] = da_z.astype(dg_scr.dtype)
        dg_scr[i, tt, :, 2 * hh:3 * hh] = dhn.astype(dg_scr.dtype)

        # dh_prev = dh·z + dgates_h @ W_hhᵀ (contract the 3H axis); the
        # dgates operand reads back from the stash in the dot dtype.
        dhs[i] = dh_total * z + jax.lax.dot_general(
            dg_scr[i, tt], ws[i], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for k, dgate in enumerate((da_r, da_z, dhn, dtanh)):
            dbs[i][k] = dbs[i][k] + jnp.sum(dgate, axis=0, keepdims=True)

    for tt in _walk(t_blk, not reverse):   # time OUTER, against scan order
        for i in range(n_e):               # experts INNER
            step(i, tt)
    for i in range(n_e):
        # dW_hh += h_prevᵀ @ dgates, contracted over the WHOLE time block
        # (K = t_blk·B instead of B): one MXU dot per block instead of one
        # per step — ~t_blk× fewer dW dispatches at far better systolic
        # occupancy; algebraically the same sum, reassociated.
        h_flat = hprev_ref[i].astype(dot_dtype).reshape(
            -1, hprev_ref.shape[-1])
        g_flat = dg_scr[i].reshape(-1, dg_scr.shape[-1])
        dw_scr[i] = dw_scr[i] + jax.lax.dot_general(
            h_flat, g_flat, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dh_scr[i] = dhs[i]
        for k in range(_N_DB):
            db_scr[i:i + 1, k * hh:(k + 1) * hh] = dbs[i][k]

    @pl.when(t == t_total - 1)  # last grid step == the scan's first: flush
    def _flush():
        dw_ref[...] = dw_scr[...]
        db_ref[...] = db_scr[...]
        dh0_ref[...] = dh_scr[...]


def _bwd_per_expert_bytes(b, g3, h, proj_dtype, hp_io, do_io, w_itemsize):
    """Backward-kernel VMEM bytes per expert as a function of t_blk — the
    single source for _choose_blocks AND the public block_plan probe."""
    io = jnp.dtype(proj_dtype).itemsize
    dot_io = jnp.dtype(_dot_dtype_for(proj_dtype)).itemsize
    return lambda t_blk: (
        # time-grid blocks, double-buffered: proj, h_prev, dout and the
        # stashed gates in; dproj out (h_prev/dout ride the model's out
        # dtype — _vjp_bwd)
        2 * (t_blk * b * g3 * io + t_blk * b * h * (hp_io + do_io)
             + t_blk * b * g3 * io
             + t_blk * b * g3 * io)
        # resident: W_hh in, dW/db/dh0 out, dh/dW/db scratch (db: the
        # _N_DB sums of both biases), dgates stash (dot dtype) for the
        # block-batched dW dot
        + h * g3 * w_itemsize
        + h * g3 * 4 + _N_DB * h * 4 + b * h * 4
        + b * h * 4 + h * g3 * 4 + _N_DB * h * 4
        + t_blk * b * g3 * dot_io
        + _temp_bytes(b, g3, h, proj_dtype)
    )


def _bwd_call(proj, h_prev_all, gates_all, w_hh, dout, interpret,
              reverse=False, half=0):
    """One direction's backward kernel.  ``dout`` is the cotangent of the
    layer's JOINED hidden states ``[E, T, B, n*H]``, whole: the kernel
    reads this direction's H lanes of it where they lie (``half``, static:
    which H-wide block of the last axis), so nothing cuts the joined array
    apart first.  Returns ``dproj``, ``dW_hh``, ``db_hh``, ``dh0`` and
    ``db_ih``, the last the row sums of ``dproj`` in float32."""
    e, t, b, g3 = proj.shape
    h = g3 // 3
    assert t % _T_BLK == 0, (t, _T_BLK)   # callers pad_time first
    assert dout.shape[:3] == (e, t, b) and dout.shape[3] % h == 0, dout.shape
    per_expert = _bwd_per_expert_bytes(
        b, g3, h, proj.dtype, h_prev_all.dtype.itemsize,
        dout.dtype.itemsize, w_hh.dtype.itemsize)
    e_blk, t_blk = _choose_blocks(
        e, t, per_expert, f"backward E={e} T={t} B={b} H={h} {proj.dtype}")
    eb = e // e_blk
    nb = t // t_blk
    grid = (eb, nb)
    # against scan order: back to front, and front to back where the
    # forward kernel walked the array back to front
    by_time = _time_map(nb, not reverse)
    g_spec = pl.BlockSpec((e_blk, t_blk, b, g3), by_time)
    h_spec = pl.BlockSpec((e_blk, t_blk, b, h), by_time)
    dproj, dw, db, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, dot_dtype=_dot_dtype_for(proj.dtype),
                          reverse=reverse),
        grid=grid,
        in_specs=[
            g_spec,
            h_spec,
            g_spec,
            _resident((e_blk, h, g3)),
            pl.BlockSpec((e_blk, t_blk, b, h), _time_map(nb, not reverse,
                                                         half)),
        ],
        out_specs=[
            g_spec,
            _resident((e_blk, h, g3)),
            _resident((e_blk, _N_DB * h)),
            _resident((e_blk, b, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, t, b, g3), proj.dtype),
            jax.ShapeDtypeStruct((e, h, g3), jnp.float32),
            jax.ShapeDtypeStruct((e, _N_DB * h), jnp.float32),
            jax.ShapeDtypeStruct((e, b, h), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((e_blk, b, h), jnp.float32),
            pltpu.VMEM((e_blk, h, g3), jnp.float32),
            pltpu.VMEM((e_blk, _N_DB * h), jnp.float32),
            pltpu.VMEM((e_blk, t_blk, b, g3), _dot_dtype_for(proj.dtype)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=scopes.GRU_KERNEL_BWD,
    )(proj, h_prev_all, gates_all, w_hh, dout)
    # the four sums are (da_r, da_z, dhn, dtanh): the first two are both
    # biases' to the bit
    db_hh = db[:, :g3]
    db_ih = jnp.concatenate([db[:, :2 * h], db[:, g3:]], axis=-1)
    return dproj, dw, db_hh, dh0, db_ih


# ---------------------------------------------------------------------------
# custom-VJP wrapper: one layer, every direction of it
# ---------------------------------------------------------------------------


def _pads(e: int, t: int, b: int, dtype, reverse: bool):
    """``jnp.pad`` widths (experts, time, rows) of one direction's kernel
    call: rows pad to the sublane, experts and time to the kernels' widest
    blocks.  The time pad sits at the END of scan order (the FRONT of the
    array when ``reverse``), beyond every real output."""
    t_pad = pad_time(t) - t
    return ((0, pad_experts(e) - e), (t_pad, 0) if reverse else (0, t_pad),
            (0, pad_batch(b, dtype) - b))


def _kernel_operands(direction, reverse: bool, biased: bool):
    """(proj, w_hh, b_hh, h0) as one direction's kernels take them: the
    input bias added to the projection's einsum (XLA fuses the add and the
    cast into the dot that makes ``xw``) unless ``xw`` came with it
    (``biased``), every array padded (:func:`_pads`; nothing at a shape the
    blocks divide)."""
    xw, b_ih, w_hh, b_hh, h0 = direction
    e, t, b, _ = xw.shape
    proj = xw
    if not biased:
        with jax.named_scope(scopes.IN_PROJ):
            proj = (xw + b_ih[:, None, None, :]).astype(xw.dtype)
    pad_e, pad_t, pad_b = _pads(e, t, b, proj.dtype, reverse)
    none = (0, 0)
    return (jnp.pad(proj, (pad_e, pad_t, pad_b, none)),
            jnp.pad(w_hh, (pad_e, none, none)), jnp.pad(b_hh, (pad_e, none)),
            jnp.pad(h0, (pad_e, pad_b, none)))


def _unpadded(h_all, e: int, t: int, b: int, reverse: bool):
    """The real ``[E, T, B, H]`` states of a padded call's."""
    t0 = h_all.shape[1] - t if reverse else 0
    return h_all[:e, t0:t0 + t, :b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gru_recurrence(directions, interpret=False, reverses=(False,),
                   biased=False):
    """Run the GRU time recurrence of ONE layer over its pre-projected
    inputs: every direction of it, joined on the last axis.

    The VJP's boundary.  It spans one operation more on each side of the
    kernels than the kernels need for their values, because of what the
    backward pass can then leave out.  In front, the ADD of the input bias
    ``b_ih`` to the projection's einsum: the backward kernel holds the
    float32 gate gradients whose row sums are ``db_ih`` and hands them back
    beside ``db_hh``, where autodiff of an add outside would read each
    direction's ``dproj`` a second time to sum its bf16 roundings.  Behind,
    the JOIN of the directions: the backward pass receives the cotangent of
    the joined array and gives it whole to each direction's kernel, which
    reads its own H lanes of it through its ``BlockSpec``, where autodiff
    of a ``concatenate`` outside would first copy the halves apart.  The
    forward pass computes what the same operations outside would, to the
    bit, and compiles to the same operations.

    Args:
      directions: a tuple, one entry a direction, of ``(xw, b_ih, w_hh,
        b_hh, h0)``.  ``xw``: ``[E, T, B, 3H]``, ``x @ W_ih`` per expert
        WITHOUT its bias (gate order r, z, n along the last axis), f32 or
        bf16: the kernels' I/O dtype.  bf16 selects the bf16-dot path
        (_dot_dtype_for): matmuls run bf16 with f32 accumulation while the
        carry and gate math stay f32 in VMEM — bf16 I/O also halves the
        dominant HBM stream, and ``dxw`` comes back in the same dtype.
        ``b_ih``: ``[E, 3H]`` input bias; ``w_hh``: ``[E, H, 3H]``
        hidden-to-hidden weights; ``b_hh``: ``[E, 3H]`` hidden bias;
        ``h0``: ``[E, B, H]`` initial hidden state.  No shape needs to
        divide the kernels' blocks: the pads and their slices are in here.
      interpret: run the pallas kernels in interpret mode (CPU testing).
      reverses: a direction each, whether it scans the time axis back to
        front (static).  Only the ORDER in which the two kernels visit time
        changes (module header): every array stays time-aligned with
        ``xw``, ``h0`` enters at the last step of the array and the state
        at ``t`` is the one after consuming ``xw[:, t:]``.  The values are
        those of flipping ``xw`` in time, scanning forward and flipping the
        states back, bit for bit, and so is every gradient but ``dW_hh``:
        the backward kernel contracts a whole time block in one dot, and
        the block now lies in array order, the reverse of scan order, so
        the same float32 sum is associated in another order inside the dot
        (about 1e-7 of the leaf's largest magnitude).
      biased: every ``xw`` came WITH its bias (static): the caller's
        projection added ``b_ih`` where it made the product, in pieces that
        an add over the whole array here could not be fused into
        (parallel/sharding.project_split_rows), and under
        ``stop_gradient``, because the bias's gradient still leaves by this
        VJP, from the backward kernels' gate gradients.

    Returns: ``[E, T, B, n*H]`` hidden states, direction ``d``'s in lanes
    ``[d*H, (d+1)*H)`` — f32 for f32 models, bf16 for bf16 models
    (_out_dtype_for: the model casts to its own dtype right after the
    kernel anyway, and f32 storage doubled the largest stream).
    """
    return _joined_fwd(directions, interpret, reverses, biased,
                       emit_prev=False)[0]


def _joined_fwd(directions, interpret, reverses, biased, emit_prev):
    """The forward kernel of each direction and the join; with
    ``emit_prev`` the training forward, which also returns each direction's
    residuals."""
    outs, residuals = [], []
    for direction, reverse in zip(directions, reverses, strict=True):
        e, t, b, _ = direction[0].shape
        proj, w_hh, b_hh, h0 = _kernel_operands(direction, reverse, biased)
        out = _fwd_call(proj, w_hh, b_hh, h0, interpret, emit_prev=emit_prev,
                        reverse=reverse)
        if emit_prev:
            h_all, h_prev_all, gates_all = out
            residuals.append((proj, w_hh, h_prev_all, gates_all,
                              direction[1], b_hh, h0))
        else:
            h_all = out
        outs.append(_unpadded(h_all, e, t, b, reverse))
    return jnp.concatenate(outs, axis=-1), tuple(residuals)


def _vjp_fwd(directions, interpret, reverses, biased):
    # Training forward streams h_prev out of the kernel directly — the
    # backward consumes it without the concat(h0, h_all[:-1]) round-trip,
    # and h_all itself is NOT a residual (the recompute needs only
    # h_prev).  The pre-activation hidden gates ride as a third output so
    # the backward skips its recompute dot.  Both stashes are stored
    # time-aligned with proj whichever way the scan ran, which is all the
    # backward kernel needs.  proj (with its bias) is the residual, xw is
    # none: it never leaves the dot's fusion.  The two biases and h0 ride
    # along for their dtypes (tiny next to the stashes).
    return _joined_fwd(directions, interpret, reverses, biased,
                       emit_prev=True)


def _vjp_bwd(interpret, reverses, biased, residuals, dout):
    e, t, b, _ = dout.shape
    io_dtype = residuals[0][0].dtype
    dout = dout.astype(_out_dtype_for(io_dtype))     # the joined array, once
    grads = []
    for half, (reverse, res) in enumerate(zip(reverses, residuals)):
        proj, w_hh, h_prev_all, gates_all, b_ih, b_hh, h0 = res
        # the joined cotangent, padded as this direction's arrays are (the
        # pad's rows, steps and experts get a zero cotangent and give zero
        # gate gradients); at a shape that pads nothing, and every
        # benchmark cell's is one, it is the array itself for every
        # direction
        pad_e, pad_t, pad_b = _pads(e, t, b, io_dtype, reverse)
        padded = jnp.pad(dout, (pad_e, pad_t, pad_b, (0, 0)))
        dproj, dw, db_hh, dh0, db_ih = _bwd_call(
            proj, h_prev_all, gates_all, w_hh, padded, interpret, reverse,
            half)
        grads.append((_unpadded(dproj, e, t, b, reverse),
                      db_ih[:e].astype(b_ih.dtype),
                      dw[:e].astype(w_hh.dtype),
                      db_hh[:e].astype(b_hh.dtype),
                      dh0[:e, :b].astype(h0.dtype)))
    return (tuple(grads),)


gru_recurrence.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# padding helpers (shape hygiene for the kernel's tiling constraints)
# ---------------------------------------------------------------------------


def pad_batch(b: int, dtype=None) -> int:
    """Round the batch up to the sublane granularity of ``dtype``.

    The batch is the second-minor axis of every ``[.., B, 3H/H]`` block:
    f32 tiles need B % 8 == 0, bf16 tiles B % 16 == 0."""
    import jax.numpy as jnp

    gran = 2 * _SUBLANE if dtype == jnp.bfloat16 else _SUBLANE
    return int(np.ceil(b / gran) * gran)


def pad_time(t: int) -> int:
    """Round the time axis up to the kernel's time-block granularity.

    The kernel calls require ``T % _T_BLK == 0``; ``gru_recurrence`` pads
    ``proj`` at the END of scan order to this length (:func:`_pads`) and
    slices the states back to ``t``; the tail gets a zero cotangent."""
    return int(np.ceil(t / _T_BLK) * _T_BLK)


def pad_experts(e: int) -> int:
    """Round the expert axis up to the widest expert block, so that a
    legal block (a multiple of the sublane that divides E) always exists."""
    return int(np.ceil(e / _E_BLK) * _E_BLK)


def supported(t: int, h: int) -> bool:
    """Kernel preconditions: lane-aligned hidden size, non-trivial window."""
    return h % 128 == 0 and t >= 1


def block_plan(e: int, t: int, b: int, h: int, dtype=jnp.float32,
               training: bool = True) -> dict:
    """Predict the (e_blk, t_blk) blocking and scoped-VMEM fit at a shape.

    The serving page fold fattens the kernels' B (row) axis — the VMEM
    footprint model that sizes blocks (_choose_blocks) was built at B=32
    and is re-validated here at the fatter row counts: callers
    (tests/test_coalesce.py, tests/test_trainticket_config.py) probe the
    EXACT per-expert byte model the kernel calls use (shared
    _fwd/_bwd_per_expert_bytes) without compiling anything.

    ``dtype`` is the kernel I/O (proj) dtype — bf16 for bf16 models, f32
    otherwise (ops/gru.py ``_kernel_io_dtype``); ``b`` is the PRE-padding
    row count (``pad_batch`` is applied here).  ``training=True`` reports
    the tighter of the forward (emit_prev + gate stash) and backward
    plans, since both kernels run under the custom VJP.

    Returns ``{"e_blk", "t_blk", "per_expert_bytes", "block_bytes",
    "fits", "b_padded", "t_padded", "budget"}`` for the binding kernel.
    """
    io_dtype = jnp.bfloat16 if jnp.dtype(dtype) == jnp.bfloat16 \
        else jnp.float32
    b_pad = pad_batch(b, io_dtype)
    t_pad = pad_time(t)
    g3 = 3 * h
    w_itemsize = jnp.dtype(io_dtype).itemsize
    out_io = jnp.dtype(_out_dtype_for(io_dtype)).itemsize
    plans = []
    fwd_pe = _fwd_per_expert_bytes(
        b_pad, g3, h, io_dtype, emit_prev=training, w_itemsize=w_itemsize,
        h0_itemsize=4)
    plans.append(("fwd", fwd_pe))
    if training:
        bwd_pe = _bwd_per_expert_bytes(
            b_pad, g3, h, io_dtype, hp_io=out_io, do_io=out_io,
            w_itemsize=w_itemsize)
        plans.append(("bwd", bwd_pe))
    worst = None
    for _name, per_expert in plans:
        try:
            e_blk, t_blk = _choose_blocks(e, t_pad, per_expert)
        except ValueError:
            # nothing fits: report the smallest legal plan, fits=False
            legal_e, t_candidates = _legal_blocks(e, t_pad)
            e_blk, t_blk = legal_e[0], t_candidates[-1]
        block_bytes = e_blk * per_expert(t_blk)
        entry = {
            "e_blk": e_blk, "t_blk": t_blk,
            "per_expert_bytes": per_expert(t_blk),
            "block_bytes": block_bytes,
            "fits": block_bytes <= _VMEM_BUDGET,
            "b_padded": b_pad, "t_padded": t_pad, "budget": _VMEM_BUDGET,
        }
        if worst is None or entry["block_bytes"] > worst["block_bytes"]:
            worst = entry
    return worst
