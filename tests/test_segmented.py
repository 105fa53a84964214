"""Unit tests for the shared segmented-reduction epilogue
(core/segmented.py), independent of any particular ich_* kernel: minimal
pallas_call harnesses scatter per-slot values through real build_schedule
item-id schedules in the lane-dense form the TPU kernels run, and must
match the per-slot scalar-RMW oracle."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.segmented import (LANES, acc_rows, fold_tiles,
                                  slots_on_lanes, unpack_acc, window_rows,
                                  window_starts)
from repro.core import tiling as T
from repro.core.tiling import build_schedule


def _oracle(rowid, vals, n_out, combine, dtype):
    out = np.zeros(n_out, dtype)
    for t in range(rowid.shape[0]):
        for j in range(rowid.shape[1]):
            r = int(rowid[t, j])
            if r < 0:
                continue
            if combine == "add":
                out[r] += vals[t, j]
            elif combine == "max":
                out[r] = max(out[r], vals[t, j])
            else:
                out[r] = vals[t, j]
    return out


def _schedule_and_values(n, R, W, seed, split_aware):
    rng = np.random.default_rng(seed)
    sizes = np.minimum(rng.zipf(1.6, n), 10 * max(W or 8, 8)).astype(np.int64)
    sizes[rng.random(n) < 0.1] = 0
    sched = build_schedule(sizes, rows_per_tile=R, width=W)
    if split_aware:
        # "store" semantics need duplicate slots to agree (the K-Means
        # idempotence contract): value is a function of the item alone
        per_item = rng.integers(0, 7, n).astype(np.float32)
        vals = np.where(sched.item_id >= 0,
                        per_item[np.clip(sched.item_id, 0, n - 1)], 0.0)
    else:
        vals = rng.standard_normal(sched.item_id.shape).astype(np.float32)
    return sched, vals.astype(np.float32)


# ------------------------------------------------------------ lane-dense
def _fold_kernel(starts_ref, rows_ref, vals_ref, acc_ref, *, R, combine):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fold_tiles(acc_ref, starts_ref[g], rows_ref[...], vals_ref[...],
               rows_per_tile=R, combine=combine)


def _run_lanes(rowid, vals, n_out, combine, tiles):
    """Fold (T, R) slot values in groups of `tiles` tiles (T padded with
    all-padding tiles to whole groups)."""
    T, R = rowid.shape
    pad = -T % tiles
    rowid = np.concatenate([rowid, np.full((pad, R), -1, rowid.dtype)])
    vals = np.concatenate([vals, np.zeros((pad, R), vals.dtype)])
    G, K = (T + pad) // tiles, tiles * R
    n_acc = acc_rows(n_out, K)
    acc = pl.pallas_call(
        functools.partial(_fold_kernel, R=R, combine=combine),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G,),
            in_specs=[pl.BlockSpec((None, 1, K), lambda g, st: (g, 0, 0))] * 2,
            out_specs=pl.BlockSpec((None, n_acc, LANES),
                                   lambda g, st: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, n_acc, LANES), vals.dtype),
        interpret=True,
    )(window_starts(jnp.asarray(rowid), K),
      slots_on_lanes(jnp.asarray(rowid), tiles),
      slots_on_lanes(jnp.asarray(vals), tiles))
    return np.asarray(unpack_acc(acc, n_out)[0])


@pytest.mark.parametrize("n,R,W,seed", [
    (64, 8, None, 0), (100, 4, 16, 1), (37, 16, 8, 2), (300, 8, None, 3),
    (5, 8, 4, 4),
])
@pytest.mark.parametrize("tiles", [1, 3, 8])
@pytest.mark.parametrize("combine", ["add", "max"])
def test_fold_tiles_matches_scalar_rmw(n, R, W, seed, tiles, combine):
    sched, vals = _schedule_and_values(n, R, W, seed, split_aware=False)
    out = _run_lanes(sched.item_id, vals, n, combine, tiles)
    np.testing.assert_allclose(
        out, _oracle(sched.item_id, vals, n, combine, np.float32), atol=1e-5)


@pytest.mark.parametrize("n,R,W,seed", [
    (64, 8, None, 0), (100, 4, 8, 1), (37, 16, 4, 2), (5, 8, 4, 3),
])
def test_fold_tiles_store_matches_idempotent_writes(n, R, W, seed):
    sched, vals = _schedule_and_values(n, R, W, seed, split_aware=True)
    out = _run_lanes(sched.item_id, vals.astype(np.int32), n, "store", 4)
    np.testing.assert_array_equal(
        out, _oracle(sched.item_id, vals, n, "store", np.float32))


def test_fold_tiles_add_is_independent_of_grouping():
    """A row's sum is (((0 + tile_1) + tile_2) + ...) whatever the group
    size, so the sequential and sharded grids agree bit for bit."""
    sched, vals = _schedule_and_values(400, 8, 8, 5, split_aware=False)
    ref = _run_lanes(sched.item_id, vals, 400, "add", 1)
    for tiles in (2, 5, 8):
        np.testing.assert_array_equal(
            _run_lanes(sched.item_id, vals, 400, "add", tiles), ref)


def test_window_starts_cover_every_group_of_any_schedule():
    """Every valid slot of a group lies inside the group's window: from
    the 128-aligned row of its smallest item, `window_rows` rows long."""
    rng = np.random.default_rng(9)
    for _ in range(30):
        n = int(rng.integers(1, 2000))
        R = int(rng.choice([1, 4, 8, 16]))
        tiles = int(rng.choice([1, 2, 8, 16]))
        sizes = np.minimum(rng.zipf(1.5, n), 5000).astype(np.int64)
        sizes[rng.random(n) < 0.2] = 0
        rowid = T.build_schedule(sizes, rows_per_tile=R).item_id
        rowid = np.concatenate(
            [rowid, np.full((-len(rowid) % tiles, R), -1, np.int32)])
        K = tiles * R
        starts = np.asarray(window_starts(jnp.asarray(rowid), K))
        groups = rowid.reshape(-1, K)
        for g, row0 in zip(groups, starts):
            valid = g[g >= 0]
            if not valid.size:
                assert row0 == 0
                continue
            lo = row0 * LANES
            assert lo <= valid.min()
            assert valid.max() < lo + window_rows(K) * LANES
            assert row0 + window_rows(K) <= acc_rows(n, K)


def test_slots_on_lanes_layout():
    """Slot j of tile b of a group sits at lane b*R + j; payload width on
    the sublane axis; whole_lanes pads the lane axis to 128 with zeros."""
    pay = np.arange(6 * 4 * 3, dtype=np.float32).reshape(6, 4, 3)
    out = np.asarray(slots_on_lanes(jnp.asarray(pay), 2))
    assert out.shape == (3, 3, 8)
    for g in range(3):
        for b in range(2):
            for j in range(4):
                np.testing.assert_array_equal(out[g, :, b * 4 + j],
                                              pay[2 * g + b, j])
    rows = np.arange(24, dtype=np.int32).reshape(6, 4)
    np.testing.assert_array_equal(
        np.asarray(slots_on_lanes(jnp.asarray(rows), 3)),
        rows.reshape(2, 1, 12))
    padded = np.asarray(slots_on_lanes(jnp.asarray(pay), 2,
                                       whole_lanes=True))
    assert padded.shape == (3, 3, LANES)
    np.testing.assert_array_equal(padded[..., :8], out)
    assert (padded[..., 8:] == 0).all()


def test_fold_tiles_rejects_unknown_combine():
    with pytest.raises(ValueError, match="combine"):
        fold_tiles(None, 0, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8)),
                   rows_per_tile=8, combine="mul")
