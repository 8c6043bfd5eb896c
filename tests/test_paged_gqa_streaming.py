"""`paged_gqa_attention` streams: two halves of K/V scratch taken in turn,
the next chunk's copies (the row's next, or the next row's first) started
before this chunk is waited for, and of a chunk only the pieces a row holds
scored. Interpret mode against `stock_paged_gqa_attention` at the edges of a
page, a piece and a chunk, with the two constants made small (four pieces a
chunk, as on the chip) so that a table of a few hundred tokens walks three
chunks; and `tokens_scored` against a count made column by column."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.core import telemetry
from paddle_tpu.ops.pallas import paged_gqa_attention as pg

PAGE, PIECE, CHUNK = 16, 32, 128     # a piece two pages, a chunk four pieces
TABLE = 28                           # pages: three chunks and a half


def rows(*tokens, table=TABLE, n=6, nkv=1, hd=128, window=0, ring=False):
    """A case: rows that hold that many tokens each, as (positions, table
    pages, query heads, K/V heads, head, window, ring)."""
    return [t - 1 for t in tokens], table, n, nkv, hd, window, ring


CASES = {
    "one_token": rows(1, 1),
    "page_less_one": rows(PAGE - 1, PAGE - 1),
    "one_page": rows(PAGE, PAGE),
    "page_and_one": rows(PAGE + 1, PAGE + 1),
    "one_chunk": rows(CHUNK, CHUNK),
    "chunk_and_one": rows(CHUNK + 1, CHUNK + 1),
    "three_chunks": rows(3 * CHUNK, 3 * CHUNK - 5),
    # every cross-row start lands in the half the row before did not end in
    "alternating": rows(1, 3 * CHUNK, 1, 3 * CHUNK - 9, 1),
    "no_next_row": rows(2 * CHUNK + 3),
    # a window without a ring: row 1 alone skips its first chunk, so row 0
    # starts row 1's chunk 1
    "window_skips_next_rows_first": rows(CHUNK - 1, 2 * CHUNK + 21, 6,
                                         window=CHUNK),
    # a ring of 9 pages under a window of 128: wrapped, not wrapped, just
    # full, many laps on
    "ring_wrapped_and_not": rows(9 * PAGE + 38, 42, 9 * PAGE, 1001, table=9,
                                 window=128, ring=True),
    "group_of_five": rows(3, 2 * CHUNK + 7, CHUNK, n=20, nkv=4),
    "head_256": rows(CHUNK + 9, 2, 3 * CHUNK, n=4, hd=256),
    "head_64_packed": rows(PIECE + 1, 3 * CHUNK, 7, n=32, nkv=8, hd=64),
    # every count of pieces a chunk can hold, and a piece's edges
    "each_count_of_pieces": rows(PIECE, PIECE + 1, 2 * PIECE, 3 * PIECE - 1,
                                 3 * PIECE + 1, CHUNK + 2 * PIECE + 1),
}


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(pg, "PIECE_TOKENS", PIECE)
    monkeypatch.setattr(pg, "CHUNK_TOKENS", CHUNK)


def columns_scored(pos, table_pages, window, ring):
    """The kernel's walk, one column at a time: every column of every piece
    that begins below what a row holds, in the chunks the row walks (the
    last chunk's scratch is as wide as any: a piece there is scored whole
    though the table ends inside it)."""
    cap = table_pages * PAGE
    width = -(-cap // CHUNK) * CHUNK
    total = 0
    for p in pos:
        held = min(p + 1, cap) if ring else p + 1
        first = max(p - window + 1, 0) // CHUNK if window and not ring else 0
        for col in range(first * CHUNK, width):
            piece_start = col - col % PIECE
            total += piece_start < held
    return total


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_streamed_kernel_is_the_stock_lowering(small_tiles, name):
    pos, mp, n, nkv, hd, window, ring = CASES[name]
    b = len(pos)
    rng = np.random.RandomState(len(name) + n)
    pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, n * hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(pages, PAGE, nkv * hd)), jnp.bfloat16)
    pv = jnp.asarray(rng.normal(size=(pages, PAGE, nkv * hd)), jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(b * mp).reshape(b, mp),
                        jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    assert pg._tiling(PAGE, mp) == (PIECE, CHUNK // PAGE)
    telemetry.reset()
    got = pg.paged_gqa_decode_attention(q, pk, pv, table, pos, n, nkv, hd,
                                        hd ** -0.5, window=window, ring=ring)
    assert telemetry.counter_get("pallas.paged_attn_dispatches") == 1
    assert telemetry.counter_get("pallas.paged_attn_fallbacks") == 0
    want = pg.stock_paged_gqa_attention(q, pk, pv, table, pos, n, nkv, hd,
                                        hd ** -0.5, window, ring)
    # bfloat16 probabilities, summed in another order
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("name", sorted(CASES))
def test_tokens_scored_is_the_count_column_by_column(small_tiles, name):
    pos, mp, _n, _nkv, _hd, window, ring = CASES[name]
    assert pg.tokens_scored(pos, PAGE, mp, window, ring) \
        == columns_scored(pos, mp, window, ring)


def test_a_row_is_scored_over_what_it_holds():
    """At the falcon share (tables of one chunk, ~450 tokens a row) a row
    is scored over two pieces of 256 where the whole chunk of 2,048 was; at
    the lfm2 stage (~1,900 tokens a row) over all eight, as before."""
    assert (pg.PIECE_TOKENS, pg.CHUNK_TOKENS) == (256, 2048)
    assert pg._tiling(64, 32) == (256, 32) and pg._tiling(64, 128) == (256, 32)
    # a ring of 65 pages: 4,160 tokens are two chunks and one page more
    assert pg._tiling(64, 65) == (256, 32)
    assert pg.tokens_scored([449, 449], 64, 32) == 2 * 512
    assert pg.tokens_scored([1899], 64, 128) == 2048
    assert pg.tokens_scored([2048], 64, 128) == 2048 + 256
    assert pg.tokens_scored([9000], 64, 65, 4096, True) == 2 * 2048 + 256
    # a table narrower than a piece is one piece of its own width
    assert pg._tiling(16, 5) == (80, 5)
    assert pg.tokens_scored([3, 79], 16, 5) == 160
    # cell 5's own lengths (its traffic's medians): before, every row was
    # scored over its table's whole chunk
    rng = np.random.RandomState(5)
    live = np.clip(np.exp(rng.normal(np.log(112), 0.9, 4096)), 16, 1024) \
        + rng.uniform(0, 1, 4096) * np.clip(
            np.exp(rng.normal(np.log(270), 0.7, 4096)), 32, 1024)
    live = live.astype(np.int64)
    assert 2048 * live.size / live.sum() > 4.0
    assert pg.tokens_scored(live - 1, 64, 32) / live.sum() <= 1.6


def test_the_dispatch_counter_names_the_piece(monkeypatch, tmp_path):
    import json

    monkeypatch.setenv("PT_PALLAS", "interpret")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(1, 128)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(3, 16, 128)), jnp.bfloat16)
    telemetry.reset()
    telemetry.configure(str(tmp_path / "log.jsonl"))
    try:
        pg.paged_gqa_decode_attention(q, pool, pool, jnp.asarray([[1, 2]]),
                                      jnp.asarray([20]), 1, 1, 128, 0.1)
        telemetry.flush_sink()
    finally:
        telemetry.configure(None)
    recs = [json.loads(line) for line in open(tmp_path / "log.jsonl")]
    mine, = [r for r in recs if r["name"] == "pallas.paged_attn_dispatches"]
    assert mine["attrs"] == {"delta": 1, "mode": "interpret",
                             "kernel": "paged_gqa_attention",
                             "piece": pg.PIECE_TOKENS}
