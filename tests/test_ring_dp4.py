"""The layer-0 projection of carried rows split over `data` (ISSUE 47):
`parallel/sharding.project_split_rows`, whose backward with respect to the
rows is a ring of chunk dots and permutes (`_ring_rows_gradient`) where the
partitioner's was a dot and a reduce-scatter one after the other.  Held here,
on the CPU's virtual devices, to the sum it replaces: the same einsum and
`psum_scatter` of the same operands, the gradient autodiff gives today's
path, and the chips' own rows.  `tests/test_mesh_dp4.py` holds the whole
program to the reference under the same mesh; `tests/test_chip_compile_dp4.py`
holds what XLA:TPU makes of the ring for a described `v5e:2x2`.

Since ISSUE 49 the forward is cut too, along the experts
(`_gather_and_project`: a group's rows gathered while the group before it is
projected), and is held here to the pinned einsum bit for bit: no sum is cut.

No number of this file is a device number.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeprest_tpu.config import MeshConfig, ModelConfig
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES, QuantileGRU
from deeprest_tpu.parallel import mesh as mesh_module
from deeprest_tpu.parallel import sharding
from deeprest_tpu.parallel.mesh import data_ring, make_mesh

E, T, G = 3, 5, 48          # experts, steps of a window, 3H


@pytest.fixture
def ring_at_any_width(monkeypatch):
    """The ring at this file's toy widths, which the rule of the chips'
    readings (`sharding.ring_scatters`) leaves to the partitioner."""
    monkeypatch.setattr(sharding, "RING_MIN_HOP_BYTES", 0)


def _mesh(data):
    return make_mesh(MeshConfig(data=data), jax.devices()[:data])


def _bias(rows):
    return jax.random.normal(jax.random.PRNGKey(5),
                             (rows.shape[0], rows.shape[-1]), rows.dtype)


def _operands(mesh, width, dtype, batch_a_chip=2, experts=E):
    """Windows, split rows and a cotangent of the projection, placed as the
    compact superstep places them."""
    b = batch_a_chip * mesh.shape["data"]
    k = jax.random.split(jax.random.PRNGKey(width), 3)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    return (put(jax.random.normal(k[0], (b, T, width), dtype), P("data")),
            put(jax.random.normal(k[1], (experts, width, G), dtype),
                P("expert", "data", None)),
            put(jax.random.normal(k[2], (experts, T, b, G), dtype),
                P("expert", None, "data", None)))


def _scattered_sum(mesh, x, dxw):
    """What the ring replaces: each chip's dot over its own windows,
    accumulated in float32 and rounded for the wire, and `psum_scatter`."""
    def local(x, dxw):
        part = jnp.einsum("btf,etbg->efg", x, dxw,
                          preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(part.astype(dxw.dtype), "data",
                                    scatter_dimension=1, tiled=True)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data", None, None), P("expert", None, "data", None)),
        out_specs=P("expert", "data", None), check_vma=False)(x, dxw)


def _split_over_data(mesh, rows) -> bool:
    return rows.sharding.is_equivalent_to(
        NamedSharding(mesh, P("expert", "data", None)), rows.ndim)


def _ring(mesh, x, rows, dxw):
    return jax.jit(lambda x, rows, dxw: jax.vjp(
        lambda r: sharding.project_split_rows(mesh, x, r, _bias(r)),
        rows)[1](dxw)[0])(x, rows, dxw)


# bfloat16's spacing at the largest magnitude (the ring rounds a running sum
# once a hop, the scatter each chip's partial once and their sum once), and
# float32's
SPACING = {jnp.bfloat16: 2.0 ** -7, jnp.float32: 2.0 ** -21}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [128, 256, 4096])
@pytest.mark.parametrize("data", [2, 4])
def test_ring_equals_the_dot_and_scatter_it_replaces(
        data, width, dtype, ring_at_any_width):
    mesh = _mesh(data)
    x, rows, dxw = _operands(mesh, width, dtype)
    got = _ring(mesh, x, rows, dxw)
    assert got.dtype == dtype and got.shape == rows.shape
    want = _scattered_sum(mesh, x, dxw).astype(jnp.float32)
    exact = jnp.einsum("btf,etbg->efg", x.astype(jnp.float32),
                       dxw.astype(jnp.float32))
    top = float(jnp.max(jnp.abs(exact)))
    for other in (want, exact):
        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - other)))
        assert gap <= SPACING[dtype] * top, (gap, top)
    # each chip holds its own `width / data` rows, and they are those rows
    assert _split_over_data(mesh, got)
    chunk = width // data
    for shard in got.addressable_shards:
        lo = shard.index[1].start or 0
        assert shard.data.shape == (E, chunk, G) and lo % chunk == 0
        gap = np.abs(np.asarray(shard.data, np.float32)
                     - np.asarray(exact[:, lo:lo + chunk]))
        assert gap.max() <= SPACING[dtype] * top


def _forward_both_ways(mesh, x, rows):
    """(the cut forward, the pinned einsum and add) of the same operands."""
    bias = _bias(rows)
    return (jax.jit(lambda x, rows: sharding.project_split_rows(
                mesh, x, rows, bias))(x, rows),
            jax.jit(lambda x, rows: sharding._project_pinned(
                mesh, x, rows, bias))(x, rows))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [128, 256, 4096])
@pytest.mark.parametrize("data", [2, 4])
def test_forward_by_groups_of_experts_equals_the_pinned_einsum_to_the_bit(
        data, width, dtype, ring_at_any_width):
    """Every element is the dot the pinned einsum makes, with the bias added
    to it: over all the rows, in the operands' dtype, nothing summed across
    chips or groups.  Here a group is one expert of eight (the fixture's
    bound of 0 bytes)."""
    mesh = _mesh(data)
    x, rows, _ = _operands(mesh, width, dtype, experts=8)
    assert sharding.gather_pieces(mesh, rows) == 8
    got, want = _forward_both_ways(mesh, x, rows)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("experts, carry, pieces", [
    (12, 5, 4),     # five pieces would carry enough, five does not divide
    (6, 7, 6), (16, 99, 8),             # no more than eight
    (7, 5, 1), (6, 3, 1), (3, 99, 1),   # fewer than four: whole
])
def test_an_expert_count_the_pieces_do_not_divide_takes_fewer_groups(
        experts, carry, pieces, monkeypatch):
    """The groups are equal: of the numbers of pieces up to eight that still
    carry the bound a chip (here ``carry`` of them), the largest that
    divides the experts a chip holds, and under four pieces the weight
    stays whole (the partitioner's gather); the forward cut so is still the
    pinned einsum to the bit."""
    mesh = _mesh(4)
    x, rows, _ = _operands(mesh, 256, jnp.bfloat16, experts=experts)
    sent = rows.size * rows.dtype.itemsize // 4        # by one chip a step
    monkeypatch.setattr(sharding, "RING_MIN_HOP_BYTES", sent // carry)
    assert sharding.ring_scatters(mesh, rows)
    assert sharding.gather_pieces(mesh, rows) == pieces
    got, want = _forward_both_ways(mesh, x, rows)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    text = jax.jit(lambda x, rows: sharding.project_split_rows(
        mesh, x, rows, _bias(rows))).lower(x, rows).as_text()
    gathers = len(re.findall(r"stablehlo\.all_gather", text))
    assert gathers == (pieces if pieces > 1 else 0)     # whole: a constraint


@pytest.mark.parametrize("order", [(0, 1, 3, 2), (2, 0, 3, 1), (3, 2, 1, 0)])
def test_ring_in_any_order_of_the_chips_leaves_each_its_own_rows(
        order, monkeypatch, ring_at_any_width):
    """`data_ring` decides which chip a sum visits next and nothing of what
    arrives where: a v5e 2x2's ring by coordinates is (0, 2, 3, 1)."""
    monkeypatch.setattr(sharding, "data_ring", lambda mesh: order)
    mesh = _mesh(4)
    x, rows, dxw = _operands(mesh, 256, jnp.float32)
    got = _ring(mesh, x, rows, dxw)
    exact = jnp.einsum("btf,etbg->efg", x, dxw)
    assert float(jnp.max(jnp.abs(got - exact))) <= 2.0 ** -21 * float(
        jnp.max(jnp.abs(exact)))


class _Chip:
    def __init__(self, *coords):
        self.coords = coords


@pytest.mark.parametrize("coords, ring", [
    # a v5e 2x2 in `jax.devices()` order: 1 and 2 lie across the diagonal
    ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)], (0, 2, 3, 1)),
    # two columns of four: up one, down the other
    ([(x, y, 0) for y in range(4) for x in range(2)],
     (0, 2, 4, 6, 7, 5, 3, 1)),
    ([(0, 0, 0), (1, 0, 0)], (0, 1)),
])
def test_data_ring_walks_neighbours_where_the_chips_say_where_they_sit(
        coords, ring):
    class Described:
        shape = {"data": len(coords)}
        devices = np.array([_Chip(*c) for c in coords], object).reshape(
            len(coords), 1, 1)

    got = data_ring(Described)
    assert got == ring
    hops = [sum(abs(a - b) for a, b in zip(coords[i], coords[j]))
            for i, j in zip(got, got[1:] + got[:1])]
    assert max(hops) == 1


def test_data_ring_on_devices_that_say_nothing_is_the_axis_order():
    assert data_ring(_mesh(4)) == (0, 1, 2, 3)
    assert mesh_module.data_ring is data_ring


def _model(mesh, dtype):
    cfg = ModelConfig(feature_dim=512, num_metrics=E, hidden_size=16,
                      quantiles=(0.05, 0.5, 0.95), dropout_rate=0.0,
                      compute_dtype=dtype)
    return QuantileGRU(cfg, mesh=mesh), cfg


def _loss_of_rows(mesh, dtype, width=128):
    """(loss as a function of the two directions' carried rows, the rows):
    the model called as the compact superstep calls it under ``mesh``."""
    model, cfg = _model(mesh, dtype)
    key = jax.random.PRNGKey(7)
    params = model.init(key, jnp.zeros((1, 6, cfg.feature_dim)))["params"]
    live = jnp.arange(width, dtype=jnp.int32) * 3
    x = jax.device_put(jax.random.normal(key, (8, 6, width)),
                       NamedSharding(mesh, P("data")))
    split = sharding.carried_rows_split(mesh, width) > 1
    rows = {k: jax.device_put(params[k][:, live], NamedSharding(
        mesh, P("expert", "data" if split else None, None)))
        for k in MASKED_PARAM_NAMES}

    def loss(rows):
        preds = model.apply({"params": params}, x, live_cols=live,
                            live_w_ih=rows)
        return jnp.mean(jnp.square(preds.astype(jnp.float32)))

    return loss, rows


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("data", [2, 4])
def test_gradient_through_the_model_equals_autodiff_of_the_pinned_einsum(
        data, dtype, monkeypatch, ring_at_any_width):
    """Both directions' rows: `jax.grad` through `project_split_rows` against
    autodiff of the path it replaces (the rows pinned whole and the plain
    einsum, the partitioner's reduction), and the forward's values."""
    from deeprest_tpu.models import qrnn

    mesh = _mesh(data)
    loss, rows = _loss_of_rows(mesh, dtype)
    value, grads = jax.jit(jax.value_and_grad(loss))(rows)
    monkeypatch.setattr(
        qrnn, "project_split_rows", lambda mesh, x, rows, bias: jnp.einsum(
            "btf,efg->etbg", x, sharding.pin_folded_rows(mesh, rows))
        + bias[:, None, None, :])
    loss, rows = _loss_of_rows(mesh, dtype)
    value_was, grads_was = jax.jit(jax.value_and_grad(loss))(rows)
    assert float(value) == pytest.approx(float(value_was), rel=1e-5)
    spacing = SPACING[jnp.dtype(dtype).type]
    for name in MASKED_PARAM_NAMES:
        assert _split_over_data(mesh, grads[name])
        top = float(jnp.max(jnp.abs(grads_was[name])))
        gap = float(jnp.max(jnp.abs(grads[name] - grads_was[name])))
        assert 0 < top and gap <= spacing * top, (name, gap, top)


@pytest.mark.parametrize("dtype, wire", [("bfloat16", "bf16"),
                                         ("float32", "f32")])
def test_the_wire_carries_the_models_compute_dtype(dtype, wire,
                                                  ring_at_any_width):
    """Six permutes a direction at `data`=4 (three stages, a half each way),
    of half a chunk's rows in the compute dtype: a float32 model takes the
    same ring with float32 on the wire."""
    mesh = _mesh(4)
    loss, rows = _loss_of_rows(mesh, dtype)
    text = jax.jit(jax.grad(loss)).lower(rows).as_text()
    sent = re.findall(r"collective_permute.*?-> tensor<([\dx]+)x(\w+)>", text)
    assert len(sent) == 2 * 6, text.count("collective_permute")
    assert set(sent) == {(f"{E}x{128 // 4 // 2}x{G}", wire)}


def test_one_device_and_a_width_the_axis_does_not_divide_take_the_einsum(
        ring_at_any_width):
    """The split engages on `carried_rows_split`: without a `data` axis that
    divides the table the model's text holds no permute."""
    for mesh, width in ((_mesh(1), 128), (_mesh(4), 130)):
        loss, rows = _loss_of_rows(mesh, "float32", width)
        text = jax.jit(jax.grad(loss)).lower(rows).as_text()
        assert "collective_permute" not in text


@pytest.mark.parametrize("table, dtype, data, ring, pieces", [
    # `tenk-train-live4k-dp4`: 15.7 MB a hop; eight groups of five experts
    (4096, jnp.bfloat16, 4, True, 8),
    (256, jnp.bfloat16, 4, False, 1),   # `tenk-train-dp4`: 0.98 MB, and slower
    (2048, jnp.bfloat16, 4, True, 5),   # read at +11.0%; in five pieces +5.3%
    (1024, jnp.bfloat16, 4, True, 1),   # read at +5.9%; in two pieces -1.2%
    (512, jnp.bfloat16, 4, False, 1),   # not read; 1.97 MB
    (512, jnp.float32, 4, True, 1), (1024, jnp.bfloat16, 8, False, 1),
    (8192, jnp.bfloat16, 4, True, 8),
])
def test_the_ring_engages_by_the_bytes_of_a_hop(table, dtype, data, ring,
                                                pieces):
    """`sharding.ring_scatters`, the one rule beside `carried_rows_split`:
    the two four-chip cells lie on either side of it, as the chips read them
    (PERF.md section 6, PR 47), and it reads shapes and nothing else.  Where
    it engages, the forward's gather comes in `sharding.gather_pieces`
    groups of experts, four to eight of at least the same bytes from a chip
    each, as the chips read them (PERF.md section 6, PR 49); else whole."""
    rows = jax.ShapeDtypeStruct((40, table, 384), dtype)
    assert sharding.ring_scatters(_mesh(data), rows) is ring
    assert sharding.gather_pieces(_mesh(data), rows) == pieces


def test_a_narrow_table_leaves_its_gradients_to_the_partitioner():
    """Under the rule as it stands this file's toy tables take the pinned
    einsum and autodiff's transpose: no permute in the model's text."""
    loss, rows = _loss_of_rows(_mesh(4), "float32")
    text = jax.jit(jax.grad(loss)).lower(rows).as_text()
    assert "collective_permute" not in text
