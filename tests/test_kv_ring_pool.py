"""Two classes of KV pages under one owner (serving/kv_cache.PagedKVCache):
context pages for full layers, a fixed ring a slot for window layers."""

import numpy as np
import pytest

from paddle_tpu.models.afmoe import AfmoeConfig, afmoe_params
from paddle_tpu.serving.admission import KVCacheExhaustedError
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import (KVPagePool, LayerCache,
                                         PagedKVCache, ring_pages_per_slot)

PAGE, WINDOW = 8, 32
LAYOUT = [LayerCache(16, WINDOW)] * 4 + [LayerCache(16)]


def cache(context_pages=33, ring_pages=11):
    return PagedKVCache(LAYOUT, PAGE, context_pages, ring_pages, "float32")


def test_a_ring_is_the_window_and_one_page():
    assert ring_pages_per_slot(4096, 16) == 257
    assert ring_pages_per_slot(WINDOW, PAGE) == 5
    kv = cache()
    assert kv.ring_slot_pages == 5 and kv.window == WINDOW
    assert kv.context.layers == [4] and kv.ring.layers == [0, 1, 2, 3]
    arrays = kv.make_arrays()
    assert arrays["kv_k_4"].shape == (33, PAGE, 16)
    assert arrays["kv_v_0"].shape == (11, PAGE, 16)
    assert kv.pool_bytes == 2 * PAGE * 16 * 4 * (33 + 4 * 11)


@pytest.mark.parametrize("tokens,context,ring", [
    (1, 1, 1), (8, 1, 1), (9, 2, 2), (40, 5, 5), (41, 6, 5), (256, 32, 5)])
def test_ring_pages_never_exceed_a_slots_ring(tokens, context, ring):
    assert cache().pages_for_tokens(tokens) == (context, ring)


def test_a_model_without_window_layers_keeps_its_one_pool():
    kv = PagedKVCache([LayerCache(64)] * 2, 4, 9, None, "float32")
    assert kv.ring is None and kv.ring_slot_pages == 0
    assert isinstance(kv.context, KVPagePool) and kv.context.klass == ""
    assert sorted(kv.make_arrays()) == ["kv_k_0", "kv_k_1", "kv_v_0",
                                        "kv_v_1"]
    assert kv.try_alloc(3, 0) == ([1, 2, 3], [])
    assert kv.audit(owned=[1, 2, 3]) == []


def test_admission_refuses_by_either_class():
    # context cannot ever hold it
    with pytest.raises(KVCacheExhaustedError):
        cache(context_pages=5).check_fits(100)
    # the ring pool is smaller than one slot's ring
    with pytest.raises(KVCacheExhaustedError, match="ring"):
        cache(ring_pages=4).check_fits(100)
    cache().check_fits(100)
    # seated only if both classes can seat it now: the ring runs out first
    kv = cache(context_pages=33, ring_pages=11)         # 10 ring pages
    first = kv.try_alloc(6, 5)
    second = kv.try_alloc(6, 5)
    assert first is not None and second is not None
    free_before = kv.context.free_pages()
    assert kv.try_alloc(6, 5) is None                   # no ring page left
    assert kv.context.free_pages() == free_before       # nothing was taken
    # ... and the context class refuses though rings are free
    kv.free(*second)
    assert kv.try_alloc(free_before + 7, 5) is None
    assert kv.ring.free_pages() == 5
    kv.free(*first)
    assert kv.audit(owned=[], owned_ring=[]) == []


def test_audit_covers_both_classes():
    kv = cache()
    pages, ring = kv.try_alloc(3, 2)
    assert kv.audit(owned=pages, owned_ring=ring) == []
    leaked = kv.audit(owned=pages, owned_ring=[])
    assert leaked and all(p.startswith("ring:") for p in leaked)
    kv.free(pages, ring)
    assert kv.audit(owned=[], owned_ring=[]) == []


def test_the_prefix_store_refuses_ring_layers_and_says_so():
    cfg = AfmoeConfig(dtype="float32")
    with pytest.raises(ValueError, match="prefix store"):
        DecodeEngine(cfg, afmoe_params(cfg, 0), DecodeConfig(
            max_slots=2, page_size=8, kv_pages=65, kv_ring_pages=11,
            prefix_cache=True))


def test_audit_is_clean_after_200_mixed_requests():
    """Short and long, under and over the window, more than the pools can
    seat at once: every request is answered, a request never holds more
    ring pages than a slot's ring, and both classes come back whole."""
    cfg = AfmoeConfig(dtype="float32", max_seq_len=128)
    engine = DecodeEngine(cfg, afmoe_params(cfg, 1), DecodeConfig(
        max_slots=4, page_size=PAGE, kv_pages=3 * 16 + 1,
        kv_ring_pages=3 * 5 + 1, prefill_buckets=[16, 64, 128],
        prefix_cache=False, max_queue_depth=256, buckets=[4]))
    engine.start(warmup=False)
    rng = np.random.RandomState(2)
    try:
        reqs = []
        for _ in range(200):
            n = int(rng.choice([3, 9, 30, 47, 90]))
            new = int(rng.randint(1, 7))
            reqs.append((engine.submit(rng.randint(3, cfg.vocab_size, n),
                                       max_new_tokens=new,
                                       stop_at_eos=False), new))
        seen_ring = 0
        for req, new in reqs:
            assert len(req.result(300)) == new
        for req in list(engine._active):
            seen_ring = max(seen_ring, len(req.ring_pages))
        assert seen_ring <= engine.kv.ring_slot_pages
    finally:
        engine.close()
    assert engine.kv.audit(owned=[], owned_ring=[]) == []
    stats = engine.kv.stats()
    assert stats["pages_used"] == 0 and stats["ring"]["pages_used"] == 0
    assert stats["ring"]["high_water_pages"] <= 3 * 5
    # three slots' worth of pages in each class: the fourth slot waited
    assert stats["high_water_pages"] <= 3 * 16
