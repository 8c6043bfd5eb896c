"""ops/pallas/grouped_swiglu_bwd.py in interpret mode on the CPU against the
eight ragged products it replaces (`stock_grouped_swiglu_bwd`, beside it):
Mellum's widths cut to lane multiples (2304 x 896 -> 384 x 128) and one
serving width (3072 x 3072 -> 384 x 384), group layouts that are even,
one group that owns every row, an expert with no row, a group across two
row tiles, rows past the groups; float32 and bfloat16; the counted
fallbacks; the visits, which take in the experts with no row; the names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import grouped_swiglu_bwd as gb

MELLUM, TRINITY = (384, 128, 4), (384, 384, 8)      # H, F, experts held
ROWS, TILE = 192, 64
NAMES = ("dxs", "dw", "dW1", "dW3", "dW2")


def sizes_of(layout, n, e):
    """Group sizes of one layout over n sorted rows in tiles of 64."""
    s = np.zeros(e, int)
    if layout == "even":
        s[:] = n // e
    elif layout == "one_owns_all":
        s[e // 2] = n
    elif layout == "an_expert_with_no_row":
        s[:] = n // e
        s[1] = 0
    elif layout == "a_group_across_two_tiles":
        s[0], s[1], s[-1] = 40, 70, 30      # rows 40-110 lie in tiles 0, 1
    else:
        assert layout == "rows_past_the_groups"
        s[:] = n // (2 * e) - 1             # under half the rows, uneven
    return np.asarray(s, np.int32)


def operands(seed, n, h, f, e, sizes, dtype):
    """What `_held_experts_bwd.back()` hands over: the rows, their
    cotangents rounded, `dy * w` rounded, the weights (0 past the groups)."""
    rng = np.random.RandomState(seed)
    held = int(sizes.sum())
    w = jnp.asarray(np.where(np.arange(n) < held, rng.rand(n) + 0.1, 0.0),
                    jnp.float32)
    dy = jnp.where((w > 0)[:, None], jnp.asarray(rng.randn(n, h),
                                                 jnp.float32), 0.0)
    return (jnp.asarray(rng.randn(n, h), dtype), dy.astype(dtype),
            (dy * w[:, None]).astype(dtype), w,
            jnp.asarray(rng.randn(e, h, f) * h ** -0.5, dtype),
            jnp.asarray(rng.randn(e, h, f) * h ** -0.5, dtype),
            jnp.asarray(rng.randn(e, f, h) * f ** -0.5, dtype),
            jnp.asarray(sizes))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", [
    "even", "one_owns_all", "an_expert_with_no_row",
    "a_group_across_two_tiles", "rows_past_the_groups"])
@pytest.mark.parametrize("widths", [MELLUM, TRINITY],
                         ids=["2304x896_cut", "3072x3072_cut"])
def test_the_two_kernels_are_the_eight_ragged_products(monkeypatch, widths,
                                                       layout, dtype):
    """bfloat16 rounds where the stock path rounds (`dgate`, `dup`, `mid`
    to the weights' dtype, every product out in float32): an element on a
    rounding boundary may fall either way, a few 2**-9 steps of one term."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(gb, "TILE_ROWS", TILE)      # three row tiles
    telemetry.reset()
    h, f, e = widths
    sizes = sizes_of(layout, ROWS, e)
    held = int(sizes.sum())
    assert gb._tile(ROWS, h, f, jnp.dtype(dtype)) == TILE
    ops = operands(ROWS + e, ROWS, h, f, e, sizes, jnp.dtype(dtype))
    got = gb.grouped_swiglu_bwd(*ops)
    want = gb.stock_grouped_swiglu_bwd(*ops)
    assert telemetry.counter_get("pallas.grouped_swiglu_bwd_dispatches") == 1
    assert telemetry.counter_get("pallas.grouped_swiglu_bwd_fallbacks") == 0
    tol = 2e-5 if dtype == "float32" else 4e-3
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape, name
        a, b = np.asarray(a), np.asarray(b)
        if name in ("dxs", "dw"):
            # the caller selects the rows past the groups away, as it
            # does the ragged products'; within a visited tile they are 0
            assert np.isfinite(a[held:held // TILE * TILE + TILE]).all()
            a, b = a[:held], b[:held]
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * np.abs(b).max(), err_msg=name)
    for i in np.flatnonzero(sizes == 0):
        for a in got[2:]:
            assert not np.asarray(a[i]).any()       # exactly zero


@pytest.mark.parametrize("n,tile", [(64, 64), (192, 64), (576, 512),
                                    (1088, 512)])
def test_the_visits_take_in_every_expert_once_at_least(n, tile):
    """`_visits_every_expert` against a walk over the rows: every (expert,
    row tile) pair with a row in it once, and one visit for each expert
    with no row, in the experts' order; the visits past them repeat the
    last one."""
    e = 6
    for layout in ("even", "one_owns_all", "an_expert_with_no_row",
                   "rows_past_the_groups", "none"):
        sizes = np.zeros(e, np.int32) if layout == "none" \
            else sizes_of(layout, n, e)
        gid, tid, off, total = (np.asarray(a) for a in
                                gb._visits_every_expert(jnp.asarray(sizes),
                                                        n, tile))
        owner = np.repeat(np.arange(e), sizes)
        walk = sorted({(int(g), int(r) // tile) for r, g in enumerate(owner)}
                      | {(int(g), None) for g in np.flatnonzero(sizes == 0)},
                      key=lambda v: (v[0], v[1] or 0))
        assert int(total[0]) == len(walk) >= e
        assert len(gid) == e + -(-n // tile) - 1 >= len(walk)
        for (g, t), got_g, got_t in zip(walk, gid, tid):
            assert got_g == g and 0 <= got_t < -(-n // tile)
            assert t is None or got_t == t
        assert (gid[len(walk):] == gid[len(walk) - 1]).all()
        assert (tid[len(walk):] == tid[len(walk) - 1]).all()
        assert list(off) == [0] + list(np.cumsum(sizes))


@pytest.mark.parametrize("case,reason", [("mode_off", "mode_off"),
                                         ("odd_rows", "shape"),
                                         ("narrow", "shape"),
                                         ("no_room", "shape"),
                                         ("mixed_dtypes", "dtype")])
def test_what_the_kernels_cannot_take_goes_to_the_ragged_products_counted(
        monkeypatch, case, reason):
    monkeypatch.setenv("PT_PALLAS",
                       "off" if case == "mode_off" else "interpret")
    counted = []
    monkeypatch.setattr(telemetry, "counter_add",
                        lambda name, delta=1, **attrs:
                        counted.append((name, delta, attrs)))
    if case == "no_room":       # an expert's matrices and a tile: over VMEM
        monkeypatch.setattr(gb, "BLOCKS_BYTES", 1 << 18)
    n = 20 if case == "odd_rows" else 64
    h = 64 if case == "narrow" else 128
    ops = list(operands(1, n, h, 128, 4, np.asarray([3, 0, 5, 1], np.int32),
                        jnp.float32))
    if case == "mixed_dtypes":
        ops[6] = ops[6].astype(jnp.bfloat16)
    got = gb.grouped_swiglu_bwd(*ops)
    want = gb.stock_grouped_swiglu_bwd(*ops)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert counted == [("pallas.grouped_swiglu_bwd_fallbacks", 1,
                        {"reason": reason})]


def test_the_tile_follows_the_shapes():
    """At Mellum's widths both kernels take 512-row tiles beside an
    expert's three matrices whole (and fewer rows when there are fewer);
    wider, the weights-side's three float32 results leave fewer rows'
    room; a serving width's matrices do not fit whole, and the ragged
    products run."""
    bf = jnp.bfloat16
    assert gb._tile(40960, 2304, 896, bf) == 512
    assert gb._tile(4096, 2304, 896, bf) == 512
    assert gb._tile(320, 2304, 896, bf) == 320
    assert gb._tile(328, 2304, 896, bf) is None         # bf16 packs 16 rows
    assert gb._tile(64, 128, 128, jnp.float32) == 64
    assert gb._tile(8192, 2048, 1536, bf) == 256
    assert gb._tile(8192, 3072, 3072, bf) is None
    assert gb._tile(8192, 7168, 2048, bf) is None


def test_the_names_are_what_the_trace_prints_and_the_reader_sums(monkeypatch):
    """`benchmark/readers/routed_experts_train_roofline.py` divides the
    nine counted products' time by the device seconds of the operation
    families whose names start with `grouped_swiglu`, `ragged-dot` or
    `ragged_dot`. Kernels under any other name would leave the forward's
    8.8 ms a step as the whole denominator for 24.5 ms of counted work: a
    reading near 280%, which the driver refuses as impossible. A kernel's
    `name` is its instruction's name in the compiled program, and
    `trace_reduce.op_family` strips the instruction's number alone."""
    from benchmark.readers.routed_experts_train_roofline import FAMILIES
    from benchmark.trace_reduce import op_family

    monkeypatch.setenv("PT_PALLAS", "interpret")
    h, f, e = MELLUM
    ops = operands(3, 64, h, f, e, sizes_of("even", 64, e), jnp.float32)
    jaxpr = jax.make_jaxpr(gb.grouped_swiglu_bwd)(*ops)

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    names = list(kernels(jaxpr.jaxpr))
    assert names == [gb.ROWS_KERNEL_NAME, gb.WEIGHTS_KERNEL_NAME] \
        == ["grouped_swiglu_bwd_rows", "grouped_swiglu_bwd_weights"]
    for i, name in enumerate(names):
        assert name.startswith("grouped_swiglu")
        assert any(name.startswith(fam) for fam in FAMILIES)
        line = f"%{name}.{i + 7} = (f32[64,{h}]{{1,0}}) custom-call(...)"
        assert op_family(line) == name
