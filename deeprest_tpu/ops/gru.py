"""Expert-batched GRU as a TPU-friendly `lax.scan`.

The reference runs one `torch.nn.GRU` per expert in a Python loop
(reference: resource-estimation/qrnn.py:24,33-44).  Here all experts run as
one batched scan with the expert axis `E` as a leading array dimension, which

- turns E small matmuls per step into one `[E,B,H] x [E,H,3H]` batched
  matmul that tiles onto the MXU,
- **hoists the input projections out of the recurrence**: the `x @ W_ih`
  term has no sequential dependency, so it is computed for all T time steps
  as a single large `[E,B*T,F] x [E,F,3H]` matmul before the scan; only the
  hidden-to-hidden matmul stays inside the sequential loop, and
- makes expert parallelism a sharding annotation on axis 0 instead of a
  code change.

Gate math matches torch's GRU (gate order r, z, n; two separate biases;
``n = tanh(x_n + b_in + r * (h @ W_hn + b_hn))``) so numerics are directly
comparable against the public torch API.

On a TPU (backend 'auto') the scan is the fused kernel of
ops/pallas_gru.py: one kernel call a direction, on projections and hidden
states in the kernels' ``[E, T, B, ·]`` order.  This module does the layout
work round the calls, and there is one form of it (:func:`_layer_pallas`):
a reverse direction is the kernels walking the same arrays back to front
(no array is flipped in time), and a layer's directions go through ONE
``pallas_gru.gru_recurrence``, which adds each direction's input bias and
joins the directions on the last axis BEFORE the one transpose to ``[E, B,
T, 2H]``; its backward kernels read the joined cotangent where it lies and
return the input bias's gradient themselves.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deeprest_tpu.ops import scopes

_BACKENDS = ("auto", "scan", "pallas", "pallas_interpret")


def _resolve_backend(backend: str) -> str:
    """'auto' → the fused pallas kernel on TPU, `lax.scan` elsewhere."""
    if backend not in _BACKENDS:
        raise ValueError(f"unknown GRU backend {backend!r}; one of {_BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "scan"
    return backend


class GRUParams(NamedTuple):
    """One direction of GRU weights with a leading expert axis.

    Shapes: ``w_ih [E, F, 3H]``, ``w_hh [E, H, 3H]``, ``b_ih [E, 3H]``,
    ``b_hh [E, 3H]``; gate order along the ``3H`` axis is (r, z, n).
    """

    w_ih: jax.Array
    w_hh: jax.Array
    b_ih: jax.Array
    b_hh: jax.Array

    @property
    def hidden_size(self) -> int:
        return self.w_hh.shape[-2]


def resolve_weights(params: GRUParams) -> GRUParams:
    """Weights-adapter hook (round 22): dequantize-at-use for quantized
    serving weights, identity otherwise.  Called once at the top of the
    ``gru``/``bidirectional_gru`` entry points, so EVERY recurrence path
    (scan, pallas, bidirectional) shares the one sanctioned dequant site
    (ops/quantize.dequantize); the widen+scale runs inside the calling
    executable and XLA fuses it into the first projection dot."""
    from deeprest_tpu.ops.quantize import QuantTensor, dequantize

    if isinstance(params.w_ih, QuantTensor) \
            or isinstance(params.w_hh, QuantTensor):
        return params._replace(w_ih=dequantize(params.w_ih),
                               w_hh=dequantize(params.w_hh))
    return params


def init_gru_params(
    key: jax.Array, num_experts: int, input_size: int, hidden_size: int,
    dtype=jnp.float32,
) -> GRUParams:
    """Uniform(-1/sqrt(H), 1/sqrt(H)) init, the torch GRU default, so
    like-for-like numerical comparisons start from the same distribution."""
    k = 1.0 / np.sqrt(hidden_size)
    ks = jax.random.split(key, 4)
    shapes = [
        (num_experts, input_size, 3 * hidden_size),
        (num_experts, hidden_size, 3 * hidden_size),
        (num_experts, 3 * hidden_size),
        (num_experts, 3 * hidden_size),
    ]
    return GRUParams(*[
        jax.random.uniform(kk, s, dtype=dtype, minval=-k, maxval=k)
        for kk, s in zip(ks, shapes)
    ])


def _gru_scan(
    params: GRUParams,
    x: jax.Array,
    h0: jax.Array,
    reverse: bool,
    unroll: int,
    project=None,
) -> jax.Array:
    """Core scan. x: [E, B, T, F]; h0: [E, B, H] → outputs [E, B, T, H]."""
    # Hoisted input projection: one big MXU matmul over all time steps,
    # time-major for the scan.  A rank-3 ``x [B,T,F]`` is shared across all
    # experts without materializing E copies (the per-expert feature mask is
    # folded into w_ih by the caller instead — see models/qrnn.py).
    eq = "btf,efg->tebg" if x.ndim == 3 else "ebtf,efg->tebg"
    with jax.named_scope(scopes.IN_PROJ):
        proj = (jnp.einsum(eq, x, params.w_ih) + params.b_ih[:, None, :]
                if project is None else
                jnp.moveaxis(project(x, params.w_ih, params.b_ih), 1, 0))

    def step(h, xproj):
        # xproj: [E,B,3H]; h: [E,B,H]
        gates_h = jnp.einsum("ebh,ehg->ebg", h, params.w_hh) + params.b_hh[:, None, :]
        xr, xz, xn = jnp.split(xproj, 3, axis=-1)
        hr, hz, hn = jnp.split(gates_h, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h_new = (1.0 - z) * n + z * h
        return h_new, h_new

    with jax.named_scope(scopes.RECURRENCE):
        _, outs = jax.lax.scan(step, h0, proj, reverse=reverse, unroll=unroll)
        return jnp.moveaxis(outs, 0, 2)  # [T,E,B,H] -> [E,B,T,H]


def _kernel_io_dtype(dtype) -> jnp.dtype:
    """bf16 proj stays bf16 (the producing einsum already quantized the
    values, so wider storage only doubles the recurrence's dominant HBM
    stream — proj in, dproj out); anything else upcasts to f32.  For bf16
    models the kernel also runs its matmuls in bf16 (f32 accumulate) and
    W_hh ships in bf16; the hidden-state CARRY and all gate elementwise
    math stay f32 in VMEM (pallas_gru._dot_dtype_for)."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _project(params: GRUParams, x: jax.Array, project=None) -> jax.Array:
    """Hoisted input projection ``x @ W_ih`` → [E, T, B, 3H] in the
    kernel's I/O dtype, WITHOUT ``b_ih``: the add is the first operation
    inside the recurrence's VJP (pallas_gru.gru_recurrence), whose
    backward kernels return the bias's gradient.  XLA fuses the add into
    this dot either way.  ``project`` (see :func:`gru`) makes the product
    in pieces, so it adds the bias itself, to each: here under
    ``stop_gradient``, since the bias's gradient still comes from the
    kernels (``gru_recurrence(..., biased=True)``)."""
    eq = "btf,efg->etbg" if x.ndim == 3 else "ebtf,efg->etbg"
    with jax.named_scope(scopes.IN_PROJ):
        xw = (jnp.einsum(eq, x, params.w_ih) if project is None
              else project(x, params.w_ih,
                           jax.lax.stop_gradient(params.b_ih)))
        return xw.astype(_kernel_io_dtype(
            jnp.result_type(xw.dtype, params.b_ih.dtype)))


def _recur_local(directions, interpret: bool, reverses: tuple[bool, ...],
                 biased: bool = False):
    """The layer's kernel calls on the arrays one device holds.

    ``directions``: a direction each, ``(xw [E, T, B, 3H], b_ih [E, 3H],
    w_hh [E, H, 3H], b_hh [E, 3H], h0 [E, B, H])``, time-aligned with the
    input whichever way it scans (``reverses``: the order in which the
    kernels visit the time axis; ``biased``: ``xw`` came with ``b_ih`` in
    it).  Returns the directions' states joined
    on the last axis, ``[E, T, B, n*H]``.  Shape hygiene for the kernels'
    tiling happens per device, inside the call."""
    from deeprest_tpu.ops import pallas_gru

    # W_hh ships in the dot dtype: for bf16 models an f32 copy would
    # double its HBM/VMEM footprint only to be downcast inside every grid
    # program.  b_hh stays f32 (it is ADDED to the f32 accumulator).
    directions = tuple(
        (xw, b_ih, w_hh.astype(xw.dtype), b_hh.astype(jnp.float32),
         h0.astype(jnp.float32))
        for xw, b_ih, w_hh, b_hh, h0 in directions)
    return pallas_gru.gru_recurrence(directions, interpret, reverses, biased)


def _recurrence(directions, interpret: bool, reverses: tuple[bool, ...],
                mesh, biased: bool = False):
    """:func:`_recur_local`, under ONE ``shard_map`` a layer when ``mesh``
    has more than one device.

    Mosaic kernels cannot be partitioned by GSPMD, and the recurrence is
    independent over rows and over experts, so each device runs the kernels
    on its own ``[E/expert, T, B/data, ·]`` block.  The ``model`` axis only
    shards the hoisted input projection, which stays outside; here every
    operand is replicated over it.  A device's backward kernel sums the
    bias gradients over its own rows, and ``shard_map``'s transpose sums
    the weight and bias cotangents over ``data``."""
    if mesh is None or mesh.size == 1:
        return _recur_local(directions, interpret, reverses, biased)
    from jax.sharding import PartitionSpec as P

    n_data, n_expert = mesh.shape["data"], mesh.shape["expert"]
    e, _, b, _ = directions[0][0].shape
    if e % n_expert:
        raise ValueError(f"{e} experts do not divide over the mesh's "
                         f"expert axis of {n_expert}")
    # Rows that do not divide over ``data`` (a ragged eval batch) pad up
    # to it; every row is independent, so the pad rows are sliced off.
    b_pad = -b % n_data
    if b_pad:
        directions = tuple(
            (jnp.pad(xw, ((0, 0), (0, 0), (0, b_pad), (0, 0))), b_ih, w_hh,
             b_hh, jnp.pad(h0, ((0, 0), (0, b_pad), (0, 0))))
            for xw, b_ih, w_hh, b_hh, h0 in directions)
    rows = P("expert", None, "data", None)
    direction = (rows, P("expert", None), P("expert", None, None),
                 P("expert", None), P("expert", "data", None))
    out = jax.shard_map(
        lambda d: _recur_local(d, interpret, reverses, biased), mesh=mesh,
        in_specs=((direction,) * len(directions),), out_specs=rows,
        check_vma=False,
    )(directions)
    return out[:, :, :b] if b_pad else out


def _layer_pallas(
    directions,
    x: jax.Array,
    interpret: bool,
    mesh=None,
    project=None,
) -> jax.Array:
    """Fused-kernel path of one layer.  ``directions``: a direction each,
    ``(params, h0, reverse)``, one for :func:`gru` and (forward, reverse)
    for :func:`bidirectional_gru`.  A direction is its hoisted input
    projection (one MXU einsum) and one kernel call a pass of the pallas
    recurrence of ops/pallas_gru.py (see that module for the kernel
    design), time-aligned with ``x``: a reverse direction is the kernels
    walking the projection back to front and writing each state where its
    input lay, so nothing is flipped.  The directions' hidden states are
    joined on the last axis while still in the kernels' ``[E, T, B, H]``
    order, inside the recurrence's VJP, BEFORE the one transpose to ``[E,
    B, T, n*H]``.

    The values are those of transposing each direction and joining
    afterwards, bit for bit; the order matters to the compiler.  Handed two
    transposed halves it drew the dropout mask again in every fusion that
    reads the joined array (six a train step, for two here; the model has
    held the draw itself to one since ISSUE 36), and on the chip the
    operations outside the kernels took 7.54 ms a step for 5.86 at E=40
    and 48.1 for 40.1 at E=200 (PERF.md section 6, PR 29)."""
    operands = tuple((_project(p, x, project), p.b_ih, p.w_hh, p.b_hh, h0)
                     for p, h0, _ in directions)
    # the kernels carry their own names inside this scope; what is left
    # under `recurrence` is the layout work around them
    with jax.named_scope(scopes.RECURRENCE):
        out = _recurrence(operands, interpret,
                          tuple(reverse for _, _, reverse in directions),
                          mesh, biased=project is not None)
        return jnp.moveaxis(out, 1, 2).astype(x.dtype)      # [E,B,T,n*H]


def gru(
    params: GRUParams,
    x: jax.Array,
    h0: jax.Array | None = None,
    reverse: bool = False,
    unroll: int = 4,
    backend: str = "auto",
    mesh=None,
    project=None,
) -> jax.Array:
    """Single-direction GRU over the time axis.

    Args:
      params: expert-stacked weights.
      x: inputs ``[E, B, T, F]``, or ``[B, T, F]`` shared across experts.
      h0: initial hidden state ``[E, B, H]`` (zeros if None — the reference
          always starts from zeros, reference: resource-estimation/qrnn.py:38-41).
      reverse: scan the sequence back-to-front; outputs stay time-aligned
          with ``x`` (``out[:, :, t]`` is the state after consuming x[t] in
          scan order), matching the torch bidirectional layout.
      unroll: scan unroll factor (amortizes per-step overhead on TPU).
      backend: 'auto' | 'scan' | 'pallas' | 'pallas_interpret'. 'auto'
          picks the fused pallas kernel on TPU backends, `lax.scan`
          elsewhere; 'pallas_interpret' runs the kernel in interpret mode
          (CPU numerics tests).
      mesh: the ``(data, expert, model)`` mesh the caller's arrays are
          sharded over, if any.  The pallas kernel then runs under
          ``shard_map`` over ``data`` and ``expert``; the scan ignores it
          (GSPMD partitions it from the operands' shardings).
      project: the input projection of windows ``x [B, T, F]`` where it is
          not the plain einsum and add: ``(x, w_ih, b_ih) -> [E, T, B,
          3H]``, the same values made in pieces and with a backward of its
          own (the carried rows split over ``data``:
          parallel/sharding.project_split_rows).

    Returns: ``[E, B, T, H]`` hidden states.
    """
    params = resolve_weights(params)
    e = params.w_ih.shape[0]
    b = x.shape[-3]
    if h0 is None:
        h0 = jnp.zeros((e, b, params.hidden_size), dtype=x.dtype)
    resolved = _resolve_backend(backend)
    if resolved != "scan":
        from deeprest_tpu.ops import pallas_gru

        if pallas_gru.supported(x.shape[-2], params.hidden_size):
            return _layer_pallas(((params, h0, reverse),), x,
                                 interpret=resolved == "pallas_interpret",
                                 mesh=mesh, project=project)
        if backend != "auto":
            # An explicit pallas request that silently ran the scan path
            # would hide a perf bug; 'auto' falls through quietly by design.
            import warnings

            warnings.warn(
                f"GRU backend {backend!r} requested but unsupported for "
                f"T={x.shape[-2]}, H={params.hidden_size} (needs H % 128 == 0);"
                " falling back to lax.scan",
                stacklevel=2,
            )
    return _gru_scan(params, x, h0, reverse=reverse, unroll=unroll,
                     project=project)


def bidirectional_gru(
    fwd: GRUParams,
    bwd: GRUParams,
    x: jax.Array,
    unroll: int = 4,
    backend: str = "auto",
    mesh=None,
    project=None,
) -> jax.Array:
    """Bidirectional GRU: ``[E, B, T, F] → [E, B, T, 2H]``.

    Output layout matches torch: last-dim halves are (forward, backward),
    each time-aligned with the input.  ``mesh`` and ``project`` as in
    :func:`gru`.
    """
    fwd, bwd = resolve_weights(fwd), resolve_weights(bwd)
    resolved = _resolve_backend(backend)
    if resolved != "scan":
        from deeprest_tpu.ops import pallas_gru

        if pallas_gru.supported(x.shape[-2], fwd.hidden_size):
            h0 = jnp.zeros((fwd.w_ih.shape[0], x.shape[-3], fwd.hidden_size),
                           jnp.float32)
            return _layer_pallas(((fwd, h0, False), (bwd, h0, True)), x,
                                 interpret=resolved == "pallas_interpret",
                                 mesh=mesh, project=project)
    # The scan backend, and an H the kernels do not take (``gru`` warns
    # where pallas was asked for by name): two single-direction calls.
    out_f = gru(fwd, x, reverse=False, unroll=unroll, backend=backend,
                mesh=mesh, project=project)
    out_b = gru(bwd, x, reverse=True, unroll=unroll, backend=backend,
                mesh=mesh, project=project)
    return jnp.concatenate([out_f, out_b], axis=-1)
