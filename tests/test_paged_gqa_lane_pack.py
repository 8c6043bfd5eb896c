"""`paged_gqa_attention` at a head narrower than a lane tile (64: two K/V
heads a tile; 32: four), in interpret mode against
`stock_paged_gqa_attention`, beside its cases at heads of 128 and 256, which
do not pass through the packing; and the dispatcher's rule on the chip."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import paged_gqa_attention as pg


def case(rng, b, n, nkv, hd, page, mp, dtype=jnp.bfloat16):
    pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, n * hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(pages, page, nkv * hd)), dtype)
    pv = jnp.asarray(rng.normal(size=(pages, page, nkv * hd)), dtype)
    table = jnp.asarray(1 + rng.permutation(b * mp).reshape(b, mp),
                        jnp.int32)
    pos = jnp.asarray(rng.randint(0, mp * page, b), jnp.int32)
    return q, pk, pv, table, pos


@pytest.mark.parametrize("n,nkv,hd,pack", [
    (32, 8, 64, 2),       # the lfm2 stage: two K/V heads a lane tile
    (8, 2, 64, 2), (8, 4, 32, 4),
    (6, 1, 128, 1), (4, 1, 256, 1),      # as they were: no packing
    (4, 1, 64, 0), (6, 3, 64, 0)])       # K/V heads in no whole packs
def test_the_kernel_is_the_stock_lowering_at_every_head(monkeypatch, n, nkv,
                                                        hd, pack):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    assert pg.lane_pack(nkv, hd) == pack
    rng = np.random.RandomState(hd + n)
    q, pk, pv, table, pos = case(rng, 3, n, nkv, hd, 16, 5)
    telemetry.reset()
    got = pg.paged_gqa_decode_attention(q, pk, pv, table, pos, n, nkv, hd,
                                        hd ** -0.5)
    assert telemetry.counter_get("pallas.paged_attn_dispatches") == 1
    want = pg.stock_paged_gqa_attention(q, pk, pv, table, pos, n, nkv, hd,
                                        hd ** -0.5, 0, False)
    assert got.shape == (3, n * hd) and got.dtype == jnp.float32
    # bfloat16 probabilities, summed in another order
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-2)


def test_a_packed_head_reads_its_own_lanes_alone():
    """Every query head against a pool in which only ITS K/V head's lanes
    hold anything: the zeros a packed query carries in its neighbour's lanes
    keep the neighbour out of its scores, and of the output each row keeps
    its own head's lanes."""
    rng = np.random.RandomState(0)
    qh = jnp.asarray(rng.normal(size=(2, 4, 3, 64)), jnp.float32)
    packed = pg._pack_queries(qh, 2)
    assert packed.shape == (2, 2, 6, 128)
    np.testing.assert_array_equal(packed[:, 0, :3, :64], qh[:, 0])
    np.testing.assert_array_equal(packed[:, 0, 3:, 64:], qh[:, 1])
    assert not np.asarray(packed[:, 0, :3, 64:]).any()
    assert not np.asarray(packed[:, 0, 3:, :64]).any()
    out = jnp.asarray(rng.normal(size=(2, 2, 6, 128)), jnp.float32)
    own = pg._unpack_output(out, 2, 3, 64)
    np.testing.assert_array_equal(own[:, 0], out[:, 0, :3, :64])
    np.testing.assert_array_equal(own[:, 1], out[:, 0, 3:, 64:])
    np.testing.assert_array_equal(own[:, 3], out[:, 1, 3:, 64:])


def test_on_the_chip_a_head_of_64_is_dispatched_and_one_of_48_falls_back(
        monkeypatch):
    """The dispatcher's rule for the compiled route, traced here without
    compiling: tests/test_chip_compile.py compiles the packed form."""
    import paddle_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "_requested_mode", lambda: "tpu")
    calls = []
    monkeypatch.setattr(pg, "_pallas_paged_gqa_attention",
                        lambda q, *a, **kw: calls.append(a) or q)
    rng = np.random.RandomState(1)
    telemetry.reset()
    q, pk, pv, table, pos = case(rng, 2, 8, 2, 64, 64, 2)
    pg.paged_gqa_decode_attention(q, pk, pv, table, pos, 8, 2, 64, 0.125)
    assert len(calls) == 1
    assert telemetry.counter_get("pallas.paged_attn_fallbacks") == 0
    q, pk, pv, table, pos = case(rng, 2, 4, 2, 48, 64, 2)
    pg.paged_gqa_decode_attention(q, pk, pv, table, pos, 4, 2, 48, 0.14)
    assert len(calls) == 1
    assert telemetry.counter_get("pallas.paged_attn_fallbacks") == 1
