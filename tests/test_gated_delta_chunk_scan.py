"""ops/pallas/gated_delta_chunk_scan.py: the chunked gated delta rule of a
whole prompt as one kernel (interpret mode on the CPU) against its stock
lowering and against the token-by-token rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import telemetry
from paddle_tpu.core.registry import lookup
from paddle_tpu.ops import linear_attention_ops as la
from paddle_tpu.ops.pallas import gated_delta_chunk_scan as gdc
from paddle_tpu.ops.pallas import gated_delta_state_update as gdu

NK, NV, DK, DV, CHUNK = 4, 8, 128, 128, 64
DISPATCHES = "pallas.gated_delta_chunk_scan_dispatches"
FALLBACKS = "pallas.gated_delta_chunk_scan_fallbacks"


def terms(seed, batch, s, lengths, nk=NK, nv=NV, dk=DK, dv=DV,
          overlap=None, decay=0.3):
    """A layer's terms as `delta_rule_terms` leaves them (each key head's q
    and k repeated over its value heads), g = 0 and beta = 0 past
    `lengths`. `overlap`: every key within that of one direction a head."""
    rng = np.random.RandomState(seed)
    k = rng.randn(batch, s, nk, dk)
    if overlap is not None:
        k = rng.randn(batch, 1, nk, dk) + overlap * k
    q, k = (np.asarray(la._l2norm(jnp.asarray(x, jnp.float32)))
            for x in (rng.randn(batch, s, nk, dk), k))
    q, k = (np.repeat(x, nv // nk, axis=2) for x in (q * dk ** -0.5, k))
    real = (np.arange(s)[None, :] < np.asarray(lengths)[:, None])[..., None]
    g = np.where(real, -decay * rng.rand(batch, s, nv), 0.0)
    beta = np.where(real, rng.rand(batch, s, nv), 0.0)
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, rng.randn(batch, s, nv, dv), g, beta))


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()


def kernel(*args, per_key=NV // NK):
    # not under a jit of the test's own: the dispatcher's jitted wrapper
    # compiles once a shape for the whole file (~20 s of XLA's CPU compiler)
    return gdc.gated_delta_chunk_scan(*args, CHUNK, heads_per_key=per_key)


@pytest.mark.parametrize("lengths", [
    [192, 192],         # every chunk whole
    [100, 65],          # ends inside a chunk; a chunk all padding; one
    [1, 128],           # token into a chunk; two chunks (a grid step)
    [70],               # all padding: the step that only reads the state
])
def test_the_kernel_is_its_stock_lowering(interpret, lengths):
    """Chunks of 64 at the served share's widths (4 key heads, 8 value
    heads of [128, 128]), batch 2 and 1: every position's output, padded
    ones too, and the state after the last real token."""
    args = terms(1, len(lengths), 192 if len(lengths) == 2 else 128, lengths)
    o0, s0 = jax.jit(lambda *a: gdc.stock_gated_delta_chunk_scan(
        *a, CHUNK))(*args)
    o1, s1 = kernel(*args)
    assert telemetry.counter_get(DISPATCHES) == 1
    assert telemetry.counter_get(FALLBACKS) == 0
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-6)


def test_the_solve_holds_where_the_keys_overlap(interpret, monkeypatch):
    """A chunk a grid step (the file's other cases take two) where the
    system is far from the identity (keys within 0.05 of one direction,
    beta near 1, hardly any decay): the stock form's forward substitution,
    column by column."""
    monkeypatch.setattr(gdc, "CHUNKS_A_STEP", 1)
    q, k, v, g, _ = terms(2, 1, 192, [192], overlap=0.05, decay=1e-3)
    beta = jnp.full(g.shape, 0.98, jnp.float32)
    want = gdc.stock_gated_delta_chunk_scan(q, k, v, g, beta, CHUNK)
    got = kernel(q, k, v, g, beta)
    assert telemetry.counter_get(DISPATCHES) == 1
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("per_key,overlap", [(2, None), (2, 0.01), (1, 0.01)])
def test_the_kernel_is_the_token_by_token_rule(interpret, per_key, overlap):
    """A short prompt (100 tokens of a bucket of 128) against the decode
    step's stock form stepped a token at a time from a zero state, keys
    nearly parallel included: the case forward substitution is kept for."""
    nk = NV // per_key
    q, k, v, g, beta = terms(3, 1, 128, [100], nk=nk, overlap=overlap,
                             decay=0.05)
    o, last = kernel(q, k, v, g, beta, per_key=per_key)
    state = jnp.zeros((1, NV, DK, DV), jnp.float32)
    slot = jnp.zeros((1,), jnp.int32)
    step = jax.jit(gdu.stock_gated_delta_state_update)
    for t in range(100):
        o_t, state = step(state, slot, q[:, t], k[:, t], v[:, t],
                          jnp.exp(g[:, t]), beta[:, t])
        np.testing.assert_allclose(np.asarray(o)[0, t], np.asarray(o_t)[0],
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(last)[0], np.asarray(state)[0],
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("reason,mode,change", [
    ("mode_off", "off", {}),
    ("shape", "interpret", {"chunk": 12}),         # no tile of 8 rows
    ("shape", "interpret", {"per_key": 3}),        # 8 heads in runs of 3
])
def test_a_fallback_is_counted_with_its_reason(monkeypatch, reason, mode,
                                               change):
    monkeypatch.setenv("PT_PALLAS", mode)
    seen = []
    add = telemetry.counter_add
    monkeypatch.setattr(
        telemetry, "counter_add",
        lambda name, delta=1, **attrs: (seen.append((name, attrs)),
                                        add(name, delta, **attrs))[1])
    telemetry.reset()
    args = terms(4, 1, 96, [96], dk=16, dv=8)
    chunk = change.get("chunk", 32)
    got = jax.jit(lambda *a: gdc.gated_delta_chunk_scan(
        *a, chunk, heads_per_key=change.get("per_key", 2)))(*args)
    assert seen == [(FALLBACKS, {"reason": reason})]
    assert telemetry.counter_get(DISPATCHES) == 0
    want = jax.jit(lambda *a: gdc.stock_gated_delta_chunk_scan(
        *a, chunk))(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_chip_asks_for_lane_blocks_of_128(monkeypatch):
    """Mode 'tpu' (the dispatcher's word for a compiled kernel) hands a head
    of 64 lanes to the stock form; interpret mode runs it."""
    import paddle_tpu.ops.pallas as pallas

    monkeypatch.delenv("PT_PALLAS", raising=False)
    monkeypatch.setattr(pallas, "_requested_mode", lambda: "tpu")
    telemetry.reset()
    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in [(1, 128, 8, 64)] * 3 + [(1, 128, 8)] * 2]
    jax.eval_shape(lambda *a: gdc.gated_delta_chunk_scan(
        *a, CHUNK, heads_per_key=2), *args)
    assert telemetry.counter_get(FALLBACKS) == 1
    assert telemetry.counter_get(DISPATCHES) == 0


def test_the_op_writes_the_slot_through_the_kernel(interpret):
    """`gated_delta_chunk_scan` the op at the share's widths: the kernel
    dispatched beside the op's own counter, the padded tail neither
    decaying nor feeding the state, the slot written and no other."""
    rng = np.random.RandomState(5)
    s, length = 128, 70
    ins = {"Q": rng.randn(1, s, NK * DK), "K": rng.randn(1, s, NK * DK),
           "V": rng.randn(1, s, NV * DV), "A": rng.randn(1, s, NV),
           "B": rng.randn(1, s, NV), "ALog": rng.randn(NV) * 0.1,
           "DtBias": rng.randn(NV) * 0.1}
    ins = {k: [jnp.asarray(x, jnp.float32)] for k, x in ins.items()}
    ins.update(State=[jnp.full((3, NV, DK, DV), 7.0, jnp.float32)],
               Slots=[jnp.asarray([1], jnp.int32)],
               Lengths=[jnp.asarray([length], jnp.int32)])
    attrs = {"key_heads": NK, "key_dim": DK, "value_heads": NV,
             "value_dim": DV, "chunk": CHUNK}
    op = lookup("gated_delta_chunk_scan").forward
    out = op(ins, attrs)
    assert telemetry.counter_get(DISPATCHES) == 1
    assert telemetry.counter_get("ops.gated_delta_chunk_scan_dispatches") == 1
    # the same prompt in a bucket of its own length rounded to a chunk
    short = {k: [x[0][:, :length]] if k in "QKVAB" else x
             for k, x in ins.items()}
    q, k, v, g, beta = la.delta_rule_terms(
        *(short[n][0] for n in ("Q", "K", "V", "A", "B", "ALog", "DtBias")),
        NK, DK, NV, DV)
    state = jnp.zeros((1, NV, DK, DV), jnp.float32)
    for t in range(length):
        o_t, state = gdu.stock_gated_delta_state_update(
            state, jnp.zeros((1,), jnp.int32), q[:, t], k[:, t], v[:, t],
            jnp.exp(g[:, t]), beta[:, t])
    new = np.asarray(out["StateOut"])
    np.testing.assert_allclose(new[1], np.asarray(state)[0], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["Y"])[0, length - 1],
                               np.asarray(o_t).reshape(-1), rtol=2e-4,
                               atol=2e-5)
    assert (new[0] == 7.0).all() and (new[2] == 7.0).all()
