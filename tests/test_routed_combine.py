"""ops/pallas/routed_combine.py in interpret mode on the CPU against the
scatter-add it replaces, ``zeros.at[r].add(where(w > 0, ys * w, 0))``:
the Mellum cell's geometry cut down, 128 held experts with runs of 0-3
rows, empty groups and an empty token tile, runs longer than a step's
staging buffer, every pair on one expert, an `every` chunk with its
`part` sizes, NaN in the rows past the groups and in the rows a piece
over-reads, and the shapes the kernel refuses. Equal to float32 rounding,
not to bfloat16's."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import routed_combine as rc


def sorted_pairs(idx, weight, e_held):
    """What `routed_experts_share` hands on: (rows, w_sorted, sizes) of the
    chosen experts idx [T, k] with experts 0 .. e_held-1 held."""
    t, k = idx.shape
    held = idx < e_held
    key = np.where(held, idx, e_held).reshape(-1)
    order = np.argsort(key, kind="stable")
    sizes = np.bincount(key, minlength=e_held + 1)[:e_held]
    rows = (order // k).astype(np.int32)
    w = np.where(held, weight, 0.0).reshape(-1)[order].astype(np.float32)
    return rows, w, sizes.astype(np.int32)


def random_choice(rng, t, k, e):
    return np.stack([rng.permutation(e)[:k] for _ in range(t)])


def case(name):
    """-> (ys, rows, w, sizes, t, tile, stage) of one routing pattern."""
    rng = np.random.RandomState(len(name))
    h = 128
    if name == "mellum_cut":            # top-8 of 64, 16 held, 1.25 x even
        t, k, e, eh, tile, stage, n, h = 256, 8, 64, 16, 32, 64, 640, 256
        idx = random_choice(rng, t, k, e)
    elif name == "128_held":            # top-10 of 512: runs of 0-3 rows
        t, k, e, eh, tile, stage, n = 128, 10, 512, 128, 32, 256, 704
        idx = random_choice(rng, t, k, e)
    elif name == "empty_groups_and_tile":
        t, k, e, eh, tile, stage, n = 64, 2, 8, 4, 16, 32, 128
        idx = np.stack([rng.choice([0, 2, 5, 6], 2, replace=False)
                        for _ in range(t)])
        idx[16:32] = [6, 7]             # the second tile holds nothing
    elif name == "several_steps":       # 16 staged rows a step
        t, k, e, eh, tile, stage, n = 64, 4, 8, 4, 32, 16, 256
        idx = random_choice(rng, t, k, e)
    elif name == "one_expert":          # a run of `tile` rows a tile
        t, k, e, eh, tile, stage, n = 64, 2, 8, 4, 32, 16, 128
        idx = np.stack([np.full(t, 2), 4 + rng.randint(0, 4, t)], axis=1)
    else:
        assert name in ("every_chunk", "nan_in_the_overread")
        t, k, e, eh, tile, stage, n = 64, 4, 8, 6, 16, 32, 256
        idx = random_choice(rng, t, k, e)
    rows, w, sizes = sorted_pairs(idx, rng.uniform(0.1, 1.0, idx.shape), eh)
    held = int(sizes.sum())
    assert held <= n
    ys = rng.randn(t * k, h).astype(np.float32)
    ys[held:] = np.nan                  # past the groups: anything
    if name == "nan_in_the_overread":
        # rows inside the groups that count for nothing sit in pieces
        # that are staged for their neighbours
        dead = rng.choice(held, held // 3, replace=False)
        w[dead], ys[dead] = 0.0, np.nan
    if name == "every_chunk":           # the chunk the groups end in
        lo, few = 160, 64
        ends = np.cumsum(sizes)
        sizes = (np.clip(ends, lo, lo + few)
                 - np.clip(ends - sizes, lo, lo + few)).astype(np.int32)
        assert 0 < sizes.sum() < few and held > lo
        return ys[lo:lo + few], rows[lo:lo + few], w[lo:lo + few], sizes, \
            t, tile, stage
    return ys[:n], rows[:n], w[:n], sizes, t, tile, stage


def scatter_add(ys, rows, w, t):
    out = np.zeros((t, ys.shape[1]), np.float32)
    np.add.at(out, rows, np.where(w[:, None] > 0, ys * w[:, None],
                                  np.float32(0)))
    return out


@pytest.mark.parametrize("name", [
    "mellum_cut", "128_held", "empty_groups_and_tile", "several_steps",
    "one_expert", "every_chunk", "nan_in_the_overread", "refused",
    "dispatched"])
def test_the_kernel_is_the_scatter_add(monkeypatch, name):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    if name in ("refused", "dispatched"):
        # through the dispatcher: one tile is the scatter-add's, two are
        # the kernel's
        ys, rows, w, sizes, t, tile, stage = case("several_steps")
        monkeypatch.setattr(rc, "TOKEN_TILE", 64 if name == "refused" else 32)
        assert (rc._tiles(t, len(rows), ys.shape[1]) is None) \
            == (name == "refused")
        got = rc.routed_combine(*map(jnp.asarray, (ys, rows, w, sizes)), t)
        kernel = int(name == "dispatched")
        assert telemetry.counter_get(
            "pallas.routed_combine_dispatches") == kernel
        assert telemetry.counter_get(
            "pallas.routed_combine_fallbacks") == 1 - kernel
    else:
        ys, rows, w, sizes, t, tile, stage = case(name)
        tid, count, total, block = map(np.asarray, rc._plan(
            jnp.asarray(rows), jnp.asarray(sizes), t, tile, rc.PIECE_ROWS,
            stage // rc.PIECE_ROWS))
        total = int(total[0])
        assert t // tile <= total <= len(tid)
        # every tile has a step, in order; a tile's pieces are distinct
        assert sorted(set(tid[:total])) == list(range(t // tile))
        assert (np.diff(tid) >= 0).all() and (tid[total:] == tid[-1]).all()
        if name in ("several_steps", "one_expert"):
            assert total > t // tile
        if name == "empty_groups_and_tile":
            assert count[list(tid).index(1)] == 0 and (sizes == 0).any()
        slots = stage // rc.PIECE_ROWS
        for i in range(t // tile):
            held = [b for s in np.flatnonzero(tid[:total] == i)
                    for b in block[s * slots:s * slots + count[s]]]
            assert len(held) == len(set(held))
        got = rc._pallas_routed_combine(
            *map(jnp.asarray, (ys, rows, w, sizes)), t=t, tile=tile,
            piece=rc.PIECE_ROWS, stage=stage, lanes=128, interpret=True)
    got, want = np.asarray(got), scatter_add(ys, rows, w, t)
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
    # the stock form, the fallback, is the same sum too
    stock = rc.stock_routed_combine(*map(jnp.asarray, (ys, rows, w)), t)
    np.testing.assert_allclose(np.asarray(stock), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("case_,reason", [("mode_off", "mode_off"),
                                          ("bfloat16_rows", "dtype"),
                                          ("odd_rows", "shape"),
                                          ("narrow", "shape"),
                                          ("ragged_tokens", "shape")])
def test_what_the_kernel_cannot_tile_is_counted(monkeypatch, case_, reason):
    monkeypatch.setenv("PT_PALLAS",
                       "off" if case_ == "mode_off" else "interpret")
    monkeypatch.setattr(rc, "TOKEN_TILE", 32)
    counted = []
    monkeypatch.setattr(telemetry, "counter_add",
                        lambda name, delta=1, **attrs:
                        counted.append((name, delta, attrs)))
    t = 80 if case_ == "ragged_tokens" else 64
    n = 20 if case_ == "odd_rows" else 64
    h = 64 if case_ == "narrow" else 128
    rng = np.random.RandomState(0)
    ys = jnp.asarray(rng.randn(n, h), jnp.bfloat16 if case_ == "bfloat16_rows"
                     else jnp.float32)
    rows = jnp.asarray(np.sort(rng.randint(0, t, n)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1, n), jnp.float32)
    got = rc.routed_combine(ys, rows, w, jnp.asarray([n], jnp.int32), t)
    assert got.shape == (t, h) and got.dtype == jnp.float32
    assert counted == [("pallas.routed_combine_fallbacks", 1,
                        {"reason": reason})]


def test_the_tiles_follow_the_shapes():
    """The four cells' shares: (tokens, sorted rows, width) -> the staged
    rows a step and the columns a product; a decode step's rows and a
    batch of one tile are refused."""
    assert rc._tiles(16384, 40960, 2304) == (256, 8, 256, 384)   # Mellum
    assert rc._tiles(16384, 81984, 2048) == (256, 8, 256, 512)   # Qwen3-Next
    assert rc._tiles(4096, 2112, 7168) == (256, 8, 256, 512)     # Kimi
    assert rc._tiles(4096, 8256, 3072) == (256, 8, 256, 512)     # Trinity
    for t, n in ((64, 384), (256, 2112), (640, 5184)):
        assert rc._tiles(t, n, 2048) is None
    assert rc._tiles(512, 2112, 2000) is None
    assert rc._tiles(512, 2112, 1 << 15) is None    # over the VMEM limit
