"""The generators are pure functions of the seed, keep to their clips, and
give every seed the same amount of work."""

import json
import os

import numpy as np
import pytest

from benchmark.generators import requests as gen
from benchmark.generators import train_ring

from . import toy

BIG_SEED = 2 ** 31 + 11


def traffic(name):
    """A traffic file of the repo; "open_r17" is the closed loop's lengths
    under Poisson arrivals at 1.7 a second (no cell of the repo has an open
    loop yet, the generator has)."""
    if name == "open_r17":
        return dict(traffic("closed_c16"),
                    arrival={"kind": "poisson", "rate": 1.7})
    with open(os.path.join(toy.REPO, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["closed_c16", "open_r17"])
def test_requests_are_a_function_of_the_seed(name):
    a = gen.make(traffic(name), BIG_SEED, 50, 256008)
    b = gen.make(traffic(name), BIG_SEED, 50, 256008)
    c = gen.make(traffic(name), BIG_SEED + 1, 50, 256008)
    assert a == b
    assert a["requests"][0]["prompt_ids"] != c["requests"][0]["prompt_ids"]


@pytest.mark.parametrize("name", ["closed_c16", "open_r17"])
def test_lengths_keep_to_the_clips_and_the_context(name):
    t = traffic(name)
    reqs = gen.make(t, 7, 50, 256008)["requests"]
    for r in reqs:
        n = len(r["prompt_ids"])
        assert t["prompt_tokens"]["min"] <= n <= t["prompt_tokens"]["max"]
        assert 1 <= r["max_new_tokens"] <= t["new_tokens"]["max"]
        assert n + r["max_new_tokens"] <= t["max_context"] <= 1024
        assert min(r["prompt_ids"]) >= gen.FIRST_TOKEN_ID
        assert max(r["prompt_ids"]) < 256008
        assert 0 <= r["seed"] < 2 ** 31
    median = np.median([len(r["prompt_ids"]) for r in reqs])
    assert 180 <= median <= 340


@pytest.mark.parametrize("name", ["closed_c16", "open_r17"])
def test_every_seed_gets_the_same_work_in_another_order(name):
    def sizes(seed):
        reqs = gen.make(traffic(name), seed, 50, 1000)["requests"]
        return [(len(r["prompt_ids"]), r["max_new_tokens"]) for r in reqs]

    a, b = sizes(1), sizes(BIG_SEED)
    assert a != b and sorted(a) == sorted(b)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    t = traffic("open_r17")
    plan = gen.make(t, 3, 50, 1000)
    due = [r["due_s"] for r in plan["requests"]]
    in_window = [d for d in due if d >= 0]
    assert plan["clients"] is None and due == sorted(due)
    assert len(in_window) == round(t["arrival"]["rate"] * 50)
    assert min(due) >= -t["ramp_s"] and max(due) < 50
    # the same gaps in another order, the stretch after the last arrival
    # among them
    other = [r["due_s"] for r in gen.make(t, 4, 50, 1000)["requests"]
             if r["due_s"] >= 0]
    assert in_window != other
    assert np.allclose(np.sort(np.diff([0.0] + in_window + [50.0])),
                       np.sort(np.diff([0.0] + other + [50.0])))


def test_an_unknown_arrival_kind_is_refused():
    t = dict(traffic("closed_c16"), arrival={"kind": "burst", "rate": 2.0})
    with pytest.raises(ValueError, match="no arrival kind"):
        gen.make(t, 3, 50, 1000)


def test_closed_loop_has_callers_and_no_schedule():
    plan = gen.make(traffic("closed_c16"), 3, 50, 1000)
    assert plan["clients"] == 16 and len(plan["requests"]) == 128
    assert all(r["due_s"] is None for r in plan["requests"])


def test_the_training_ring_is_seeded_and_matches_the_programs_batches():
    from paddle_tpu.models import bert

    t = traffic("ring8_b40_s512")
    small = dict(t, batch_per_replica=3, seq_len=32, ring=2)
    ring = train_ring.make(small, BIG_SEED, 18000, 2)
    again = train_ring.make(small, BIG_SEED, 18000, 2)
    assert len(ring) == 2
    for a, b in zip(ring, again):
        assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(ring[0]["src_ids"], ring[1]["src_ids"])
    assert ring[0]["mask_weight"].sum(axis=1).max() \
        <= t["max_predictions_per_seq"]
    cfg = bert.ernie_large()
    theirs = bert.synthetic_pretraining_batch(
        cfg, 3, 32, seed=5, max_predictions_per_seq=4)
    ours = train_ring.batch(cfg.vocab_size, cfg.type_vocab_size, 3, 32, 5, 4)
    assert set(ours) == set(theirs)
    for k in ours:
        assert ours[k].dtype == theirs[k].dtype
        assert np.array_equal(ours[k], theirs[k]), k
    doubled = train_ring.make(small, 1, 18000, 2, replicas=2)
    assert doubled[0]["src_ids"].shape == (6, 32)
