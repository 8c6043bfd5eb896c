"""ops/pallas/grouped_swiglu.py in interpret mode on the CPU against the
three ``jax.lax.ragged_dot`` it replaces: both callers' widths cut to lane
multiples (3072 x 3072 -> 384 x 384, 7168 x 2048 -> 896 x 256), a step's
64 and 128 sorted rows and a prefill's (two row tiles, the last cut short),
groups that are empty, one group that owns every row, sizes that sum to
less than the rows; then the routed layer through the kernel on both of
its branches, and the counters."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import grouped_swiglu as gs
from paddle_tpu.parallel.moe import routed_experts_share

TRINITY, KIMI = (384, 384, 8), (896, 256, 6)        # H, F, experts held


def operands(seed, n, h, f, e, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(n, h), dtype)
    w1 = jnp.asarray(rng.randn(e, h, f) * h ** -0.5, dtype)
    w3 = jnp.asarray(rng.randn(e, h, f) * h ** -0.5, dtype)
    w2 = jnp.asarray(rng.randn(e, f, h) * f ** -0.5, dtype)
    return xs, w1, w3, w2


def sizes_of(kind, n, e):
    """Group sizes of one routing pattern over n sorted rows."""
    rng = np.random.RandomState(n + e)
    if kind == "few_hit":           # a step: one or two rows a hit expert
        s = rng.randint(0, 3, e)
    elif kind == "one_owns_all":
        s = np.zeros(e, int)
        s[e // 2] = n
    elif kind == "full":            # every row is somebody's
        s = rng.multinomial(n, np.ones(e) / e)
    elif kind == "short":           # about half the rows are nobody's
        s = rng.multinomial(n // 2, np.ones(e) / e)
        s[rng.randint(e)] = 0
    else:
        assert kind == "none"
        s = np.zeros(e, int)
    return np.asarray(s, np.int32)


@pytest.mark.parametrize("kind", ["few_hit", "one_owns_all", "full", "short",
                                  "none"])
@pytest.mark.parametrize("n", [64, 128, 576])
@pytest.mark.parametrize("widths", [TRINITY, KIMI], ids=["3072x3072_cut",
                                                         "7168x2048_cut"])
def test_the_kernel_is_the_three_ragged_dots(monkeypatch, widths, n, kind):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    h, f, e = widths
    # row tiles of 512 at these widths too: 576 rows are two, the last cut
    monkeypatch.setattr(gs, "TILE_BYTES", 512 * 2 * h * (4 + 4))
    assert gs._tiles(n, h, f, jnp.float32)[:2] == (min(n, 512), min(n, 128))
    xs, w1, w3, w2 = operands(n, n, h, f, e)
    sizes = sizes_of(kind, n, e)
    held = int(sizes.sum())
    got = np.asarray(gs.grouped_swiglu(xs, w1, w3, w2, jnp.asarray(sizes)))
    want = np.asarray(gs.stock_grouped_swiglu(xs, w1, w3, w2,
                                              jnp.asarray(sizes)))
    assert telemetry.counter_get("pallas.grouped_swiglu_dispatches") == 1
    assert telemetry.counter_get("pallas.grouped_swiglu_fallbacks") == 0
    assert got.shape == (n, h) and got.dtype == np.float32
    if held:
        np.testing.assert_allclose(got[:held], want[:held], rtol=2e-5,
                                   atol=2e-5 * np.abs(want[:held]).max())


def test_bfloat16_rounds_where_the_stock_path_rounds(monkeypatch):
    """The serving dtype: the products accumulate in float32 and
    silu(g) * u is rounded to the weights' dtype before the down product,
    as `mid.astype(w2.dtype)` does."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    h, f, e = TRINITY
    xs, w1, w3, w2 = operands(3, 128, h, f, e, jnp.bfloat16)
    sizes = jnp.asarray(sizes_of("full", 128, e))
    got = np.asarray(gs.grouped_swiglu(xs, w1, w3, w2, sizes))
    want = np.asarray(gs.stock_grouped_swiglu(xs, w1, w3, w2, sizes))
    # an element of `mid` on a rounding boundary may fall either way (the
    # float32 sums differ in their last bit): a few 2**-9 steps of one
    # term of 384
    np.testing.assert_allclose(got, want, atol=2e-3 * np.abs(want).max())


@pytest.mark.parametrize("n,tile", [(64, 64), (128, 128), (576, 512),
                                    (1088, 512), (8256, 512)])
def test_the_visits_cover_each_groups_rows_once(n, tile):
    """The prefetched metadata against a walk over the rows: every (expert,
    row tile) pair with a row in it is visited once, in row order; the
    visits past them repeat the last one."""
    e = 12
    for kind in ("few_hit", "one_owns_all", "full", "short", "none"):
        sizes = sizes_of(kind, n, e)
        gid, tid, off, total = (np.asarray(a) for a in gs._visits(
            jnp.asarray(sizes), n, tile))
        owner = np.repeat(np.arange(e), sizes)
        walk = sorted({(int(r) // tile, int(g)) for r, g in
                       enumerate(owner)})
        assert int(total[0]) == len(walk)
        assert len(gid) == e + -(-n // tile) - 1 >= len(walk)
        assert [(int(t), int(g)) for g, t in
                zip(gid[:len(walk)], tid[:len(walk)])] == walk
        if walk:
            assert (gid[len(walk):] == gid[len(walk) - 1]).all()
            assert (tid[len(walk):] == tid[len(walk) - 1]).all()
        assert list(off) == [0] + list(np.cumsum(sizes))


def routed_layer(seed, t, e_all, e_held, h, f, top_k, bias_held=0.0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    router = jnp.asarray(rng.randn(h, e_all) * h ** -0.5, jnp.float32)
    bias = jnp.zeros(e_all).at[:e_held].set(bias_held)
    _, w1, w3, w2 = operands(seed + 1, 8, h, f, e_held)
    return x, router, bias, w1, w3, w2


@pytest.mark.parametrize("branch,bias", [
    ("few", 0.0),               # the leading rows hold the held pairs
    ("every", 10.0),            # a selection bias sends every pair here
], ids=["few_rows", "every_row"])
def test_the_routed_layer_through_the_kernel(monkeypatch, branch, bias):
    """48 tokens top-4 of 32 with 4 held: 128 of the 192 sorted rows at a
    time. With the bias the held pairs outnumber them and the layer runs
    its `fori_loop` with clipped sizes through the same kernel; the rows
    past the groups (half of them without the bias) reach nothing."""
    x, router, sel, w1, w3, w2 = routed_layer(7, 48, 32, 4, 128, 256, 4,
                                              bias)
    live = jnp.arange(48) < 41

    def layer():
        return routed_experts_share(x, router, sel, w1, w3, w2, top_k=4,
                                    held_lo=0, route_scale=2.0, live=live)

    monkeypatch.setenv("PT_PALLAS", "off")
    telemetry.reset()
    want, counts = layer()
    fallbacks = telemetry.counter_get("pallas.grouped_swiglu_fallbacks")
    assert fallbacks == 2       # one a `cond` branch
    monkeypatch.setenv("PT_PALLAS", "interpret")
    got, counts_k = layer()
    assert telemetry.counter_get("pallas.grouped_swiglu_dispatches") == 2
    assert telemetry.counter_get("pallas.grouped_swiglu_fallbacks") \
        == fallbacks
    assert list(np.asarray(counts)) == list(np.asarray(counts_k))
    if branch == "every":
        assert int(counts[1]) == 41 * 4 > 128
    else:
        assert 0 < int(counts[1]) <= 128
    assert not np.asarray(got[41:]).any()
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5 * scale


@pytest.mark.parametrize("case,reason", [("mode_off", "mode_off"),
                                         ("odd_rows", "shape"),
                                         ("narrow", "shape"),
                                         ("mixed_dtypes", "dtype")])
def test_what_the_kernel_cannot_tile_takes_the_stock_path_and_is_counted(
        monkeypatch, case, reason):
    monkeypatch.setenv("PT_PALLAS",
                       "off" if case == "mode_off" else "interpret")
    counted = []
    monkeypatch.setattr(telemetry, "counter_add",
                        lambda name, delta=1, **attrs:
                        counted.append((name, delta, attrs)))
    n, h = (20, 128) if case == "odd_rows" else (64, 128)
    h = 64 if case == "narrow" else h
    xs, w1, w3, w2 = operands(1, n, h, 128, 4)
    if case == "mixed_dtypes":
        xs = xs.astype(jnp.bfloat16)
    sizes = jnp.asarray([3, 0, 5, 1], jnp.int32)
    got = gs.grouped_swiglu(xs, w1, w3, w2, sizes)
    want = gs.stock_grouped_swiglu(xs, w1, w3, w2, sizes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert counted == [("pallas.grouped_swiglu_fallbacks", 1,
                        {"reason": reason})]


def test_the_tiles_follow_the_shapes():
    """A step's rows are one tile and one window; a prefill's rows go in
    tiles of as many rows as TILE_BYTES hold at H (or in one, when they
    are fewer) and windows of 128; a weight block stays under BLOCK_BYTES
    and divides F."""
    bf = jnp.bfloat16
    assert gs._tiles(128, 3072, 3072, bf) == (128, 128, 1024)
    assert gs._tiles(576, 3072, 3072, bf) == (576, 128, 1024)
    assert gs._tiles(4160, 3072, 3072, bf) == (1024, 128, 1024)
    assert gs._tiles(64, 7168, 2048, bf) == (64, 64, 512)
    assert gs._tiles(2112, 7168, 2048, bf) == (512, 128, 512)
    assert gs._tiles(72, 3072, 3072, bf) is None        # bf16 packs 16 rows
    assert gs._tiles(64, 128, 128, jnp.float32) == (64, 64, 128)
