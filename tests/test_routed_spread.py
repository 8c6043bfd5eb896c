"""ops/pallas/routed_spread.py in interpret mode on the CPU against the
gather and the rounding passes it replaces (`stock_routed_spread`), bit
for bit on every row inside the groups and zero past them: the routing
patterns of test_routed_combine.py (the Mellum cell's geometry cut down,
128 held experts with runs of 0-3 rows, empty groups and an empty token
tile, runs longer than a step's staging buffer, every pair on one expert,
an `every` chunk with its `part` sizes, rows of weight 0 inside the
groups) and one whose runs end inside a piece in neighbouring tiles;
plain and weighted, from float32 and bfloat16, to bfloat16 and float32;
the shapes the kernel refuses, counted by reason; and NaN in the output
past the groups carried through `grouped_swiglu`, both backward kernels
and `routed_combine` to the stock path's gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import routed_combine as rc
from paddle_tpu.ops.pallas import routed_spread as rs

from test_routed_combine import case, sorted_pairs

F32, BF16 = jnp.float32, jnp.bfloat16


def routing(name):
    """-> (rows, w, sizes, t, h, tile, stage) of one routing pattern."""
    if name != "edge_pieces":
        ys, rows, w, sizes, t, tile, stage = case(name)
        return rows, w, sizes, t, ys.shape[1], tile, stage
    # three tokens a tile of 16 on expert 0, five on expert 1, none on
    # expert 2: runs of 3 and 5 rows against pieces of 8, so a piece holds
    # rows of three tiles, and the piece group 0 ends in holds group 1's
    # first rows
    t, k, eh, tile = 64, 2, 4, 16
    idx = np.full((t, k), 7)
    at = np.arange(t) % tile
    idx[np.isin(at, (0, 5, 9)), 0] = 0
    idx[np.isin(at, (1, 2, 6, 10, 15)), 1] = 1
    idx[at == 3] = [3, 7]
    rng = np.random.RandomState(7)
    rows, w, sizes = sorted_pairs(idx, rng.uniform(0.1, 1.0, idx.shape), eh)
    assert list(sizes) == [12, 20, 0, 4]
    return rows, w, sizes, t, 128, tile, 32


USES = {"plain": (F32, BF16, False), "weighted": (F32, BF16, True),
        "float32_rows": (F32, F32, False),
        "bfloat16_source": (BF16, BF16, False),
        "weighted_float32_rows": (F32, F32, True)}


@pytest.mark.parametrize("name,use", [
    (name, use) for name in (
        "mellum_cut", "128_held", "empty_groups_and_tile", "several_steps",
        "one_expert", "every_chunk", "nan_in_the_overread", "edge_pieces")
    for use in ("plain", "weighted")] + [
        ("edge_pieces", "float32_rows"), ("edge_pieces", "bfloat16_source"),
        ("128_held", "weighted_float32_rows")])
def test_the_kernel_is_the_gather(name, use):
    rows, w, sizes, t, h, tile, stage = routing(name)
    src_dtype, dtype, weighted = USES[use]
    n, held = len(rows), int(sizes.sum())
    rng = np.random.RandomState(len(name))
    src = jnp.asarray(rng.randn(t, h) * 3, F32).astype(src_dtype)
    first, last, slot, tok, tail = map(np.asarray, rs._pieces(
        jnp.asarray(rows), jnp.asarray(sizes), tile, rc.PIECE_ROWS))
    # a piece's tiles from its rows inside the groups; its slot from the
    # groups it lies in
    assert tail[0] == -(-held // rc.PIECE_ROWS)
    assert (tok[:held] == rows[:held]).all() and (tok[held:] == -1).all()
    for b in range(tail[0]):
        of = rows[b * 8:min(b * 8 + 8, held)] // tile
        assert (first[b], last[b]) == (of.min(), of.max())
    assert (first[tail[0]:] == -1).all() and (last[tail[0]:] == -1).all()
    shared = first[:tail[0]] < last[:tail[0]]
    if name in ("edge_pieces", "128_held"):
        # carried over a tile that adds to it and does not end it
        assert (last[:tail[0]] - first[:tail[0]] >= 2).any()
        assert (slot[:tail[0]][shared] >= len(sizes)).any()
    if name == "edge_pieces":
        assert list(first[:3]) == [0, 0, 0] and list(last[:3]) == [2, 3, 2]
        assert list(slot[:3]) == [0, 4, 1]
    # slots in use at once are distinct: after every tile, the open pieces
    for i in range(t // tile):
        open_ = slot[:tail[0]][(first[:tail[0]] <= i) & (last[:tail[0]] > i)]
        assert len(open_) == len(set(open_))

    got = rs._pallas_routed_spread(
        src, *map(jnp.asarray, (rows, w, sizes)), dtype=jnp.dtype(dtype),
        weighted=weighted, tile=tile, piece=rc.PIECE_ROWS, stage=stage,
        lanes=128, interpret=True)
    # the stock form is the expressions `moe.py` held until PR 50
    want = rs.stock_routed_spread(src, jnp.asarray(rows), jnp.asarray(w),
                                  dtype, weighted)
    if not weighted:
        # rounding and gathering commute
        assert (src.astype(dtype)[jnp.asarray(rows)] == want).all()
        got, want = (got,), (want,)
    for g, v in zip(got, want):
        assert g.shape == (n, h) and g.dtype == dtype
        g, v = (np.asarray(a.astype(F32)) for a in (g, v))
        assert (g[:held] == v[:held]).all() and (g[held:] == 0).all()
    if name == "nan_in_the_overread" and weighted:
        dead = (w[:held] == 0)
        assert dead.any() and (np.asarray(got[0].astype(F32))[:held][dead]
                               == 0).all()


@pytest.mark.parametrize("case_,reason", [
    ("dispatched", None), ("one_tile", "shape"), ("decode_step", "shape"),
    ("short_runs", "shape"),
    ("mode_off", "mode_off"), ("float16_source", "dtype"),
    ("odd_rows", "shape"), ("narrow", "shape"), ("ragged_tokens", "shape"),
    ("carried_pieces_over_vmem", "shape")])
def test_what_the_kernel_cannot_tile_is_counted(monkeypatch, case_, reason):
    """Through the dispatcher: two tiles of runs of four pieces are the
    kernel's; one tile, a decode step's 384 rows of 64 tokens at the real
    tile, 128 held experts' runs of 0-3 rows, and what the combine's shape
    rule refuses are the gather's, counted by reason."""
    monkeypatch.setenv("PT_PALLAS",
                       "off" if case_ == "mode_off" else "interpret")
    if case_ != "decode_step":
        monkeypatch.setattr(rc, "TOKEN_TILE",
                            64 if case_ == "one_tile" else 32)
    counted = []
    monkeypatch.setattr(telemetry, "counter_add",
                        lambda name, delta=1, **attrs:
                        counted.append((name, delta, attrs)))
    rows, w, sizes, t, h, _tile, _stage = routing(
        "128_held" if case_ == "short_runs" else "several_steps")
    if case_ == "decode_step":
        rows, w = np.tile(rows[:128] % 64, 3), np.tile(w[:128], 3)
        sizes = np.asarray([40, 30, 20, 10], np.int32)
    elif case_ == "odd_rows":
        rows, w = rows[:20], w[:20]
        sizes = np.minimum(sizes, 5)
    elif case_ == "ragged_tokens":
        t = 80
    elif case_ == "carried_pieces_over_vmem":
        sizes = np.concatenate([sizes, np.zeros(400_000, np.int32)])
    if case_ == "narrow":
        h = 64
    src = jnp.asarray(np.random.RandomState(0).randn(t, h),
                      jnp.float16 if case_ == "float16_source" else F32)
    got = rs.routed_spread(src, *map(jnp.asarray, (rows, w, sizes)), BF16)
    want = src[jnp.asarray(rows)].astype(BF16)
    held = int(sizes.sum())
    assert got.shape == (len(rows), h) and got.dtype == BF16
    assert (np.asarray(got.astype(F32))[:held]
            == np.asarray(want.astype(F32))[:held]).all()
    if reason is None:
        assert counted == [("pallas.routed_spread_dispatches", 1,
                            {"mode": "interpret"})]
    else:
        assert counted == [("pallas.routed_spread_fallbacks", 1,
                            {"reason": reason})]
        # the fallback is today's expression, past the groups too
        assert (np.asarray(got.astype(F32))
                == np.asarray(want.astype(F32))).all()


def test_the_spread_follows_the_combines_shapes():
    """The four cells' shares: the combine's tiles, the parts a source goes
    through the product as, and the kernel's VMEM at each (E held experts'
    carried pieces beside the staged rows)."""
    # the rows a call holds for each (token tile, expert) decide: Mellum's
    # runs of five pieces are the kernel's, the served shares' of one or
    # two the gather's
    for t, n, h, e, kernel in ((16384, 40960, 2304, 16, True),
                               (16384, 81984, 2048, 128, False),
                               (4096, 2112, 7168, 12, False),
                               (4096, 8256, 3072, 32, False)):
        assert rc._tiles(t, n, h)[:3] == (256, 8, 256)
        for weighted in (False, True):
            assert (rs._tiling(F32, BF16, weighted, t, n, e, h)
                    is not None) == kernel
    assert rs._parts(F32, BF16, False) == 1     # rounded, then selected
    assert rs._parts(BF16, BF16, True) == 1
    assert rs._parts(F32, BF16, True) == 3      # selected whole, then rounded
    assert rs._parts(F32, F32, False) == 3
    # 4,096 groups' carried pieces are over the kernel's VMEM
    assert rs._tiling(F32, BF16, False, 16384, 1 << 24, 4096, 2304) is None
    assert rs._tiling(F32, BF16, False, 16384, 1 << 24, 16, 2304)
    assert rs.KERNEL_NAME == "routed_spread" \
        and not rs.KERNEL_NAME.startswith("grouped_swiglu")


@pytest.mark.parametrize("branch", ["leading", "every"])
def test_nan_past_the_groups_reaches_no_gradient(monkeypatch, branch):
    """The sorted rows past the groups are nobody's: with NaN there in
    every output of the spread (what a buffer may hold where nothing is
    written), `grouped_swiglu`, the two `grouped_swiglu_bwd` kernels and
    `routed_combine` give the stock path's output and gradients, to
    test_routed_experts_train.py's tolerances."""
    from test_routed_experts_train import E, EH, K, _weights

    from paddle_tpu.parallel.moe import routed_experts_share

    x, rw, w1, w3, w2, co = _weights(seed=3, h=128, f=128)
    if branch == "every":
        x = jnp.abs(x) + 0.5
        rw = jnp.where(jnp.arange(E)[None, :] < EH, jnp.abs(rw),
                       -jnp.abs(rw))

    def run():
        (out, counts), vjp = jax.vjp(
            lambda *a: routed_experts_share(
                a[0], a[1], jnp.zeros((E,)), *a[2:], top_k=K, held_lo=0,
                score_func="softmax", trainable=True), x, rw, w1, w3, w2)
        return out, counts, vjp((co, np.zeros(counts.shape,
                                              jax.dtypes.float0)))

    monkeypatch.setenv("PT_PALLAS", "off")
    want, counts, want_grads = run()
    assert (int(counts[1]) > 128) == (branch == "every")

    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(rc, "TOKEN_TILE", 32)
    monkeypatch.setattr(rs, "RUN_PIECES", 1)    # 128 rows of 2 tiles x 4
    spread, planted = rs.routed_spread, []

    def nan_past_the_groups(src, rows, w, sizes, dtype, weighted=False):
        got = spread(src, rows, w, sizes, dtype, weighted)
        past = (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]
        planted.append(weighted)
        if weighted:
            return tuple(jnp.where(past, jnp.nan, g) for g in got)
        return jnp.where(past, jnp.nan, got)

    monkeypatch.setattr(rs, "routed_spread", nan_past_the_groups)
    telemetry.reset()
    got, _, grads = run()
    # forward once, backward twice (x again, dout weighted), a branch
    assert sorted(planted) == [False] * 4 + [True] * 2
    assert telemetry.counter_get("pallas.routed_spread_dispatches") == 6
    assert telemetry.counter_get("pallas.routed_spread_fallbacks") == 0
    assert telemetry.counter_get("pallas.grouped_swiglu_bwd_dispatches") == 2
    assert telemetry.counter_get("pallas.routed_combine_dispatches") == 4
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, ref in zip(grads, want_grads):
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.max(jnp.abs(g - ref))) \
            <= 1e-5 * float(jnp.max(jnp.abs(ref)))
