"""What `families/afmoe.check_correct` can see, at toy sizes on the CPU.

The check prompts are prefilled and decoded with every other slot live, so
a fault that needs neighbours shows: a slot reading another slot's ring of
pages comes out as not correct, where one prompt alone in the engine does
not notice it. A selection bias that sends every pair to the held experts
forces the routed layer's every-row branch, and an engine that never takes
that branch (pairs past the leading rows are dropped) comes out as not
correct. The lower-precision controls: the reference with only the routed
experts' weights, or only the keys and values, rounded to 8 bits."""

import copy

import numpy as np
import pytest

from benchmark import reference_afmoe
from benchmark.families import afmoe as family

from .test_bench_afmoe import TOY_AFMOE

TRAFFIC = {"max_context": 48}
SEED = 2 ** 31 + 11


def toy(**check):
    config = copy.deepcopy(TOY_AFMOE)
    config["check"].update(check)
    return config


def started(config, params=None, seed=SEED):
    cfg = family.model_config(config)
    params = params if params is not None else family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config, TRAFFIC)
    return cfg, params, engine.start(warmup=False)


def check(config, params=None, plant=None):
    cfg, params, engine = started(config, params)
    try:
        if plant is not None:
            plant(engine)
        compared, notes, _ = family.check_correct(
            None, engine, params, cfg, config["check"], SEED)
        return {n: (v, lim) for n, v, lim in compared}, notes
    finally:
        engine.close()


def test_the_check_decodes_with_every_other_slot_live():
    config = toy()
    compared, notes = check(config)
    assert notes == []
    assert compared["rows_not_live_beside_check"] == (0, 0)
    positions = 3 * config["check"]["new_tokens"]
    count, limit = compared["undecided_positions"]
    assert 0 <= count <= limit == int(reference_afmoe.UNDECIDED_SHARE
                                      * positions)
    assert compared["greedy_logit_gap_undecided"][1] \
        == reference_afmoe.UNDECIDED_MARGIN
    assert compared["greedy_logit_gap"][0] < 1e-3
    assert {"prefill_logit_err_p6", "prefill_logit_err_p20",
            "prefill_logit_err_p40"} <= set(compared)


def test_requests_beside_the_check_that_end_early_void_it():
    config = toy()
    config["check"]["beside"]["new_tokens"] = 2
    compared, notes = check(config)
    assert compared["rows_not_live_beside_check"][0] > 0
    assert any("still decoding" in n for n in notes)


def test_more_requests_beside_the_check_than_slots_are_refused():
    config = toy()
    config["check"]["beside"]["requests"] = 6          # + 3 prompts > 8
    with pytest.raises(ValueError, match="slots"):
        family.engine_config(config, TRAFFIC)


def share_slot_zeros_ring(engine):
    """The planted fault: every later live row of a step is fed the first
    row's ring of pages, so window layers of several requests write over
    and read one another. One live row is fed what it always was."""
    feed = engine._feed

    def faulty(phase, bucket, parts):
        if phase == "step":
            ring = parts["ring_table"].copy()
            live = ring.any(axis=1)
            ring[1:][live[1:]] = ring[0]
            parts = dict(parts, ring_table=ring)
        return feed(phase, bucket, parts)

    engine._feed = faulty


def test_a_fault_in_another_slots_ring_table_is_not_correct():
    config = toy()
    _, notes = check(config, plant=share_slot_zeros_ring)
    assert notes and "greedy token" in notes[0]
    # one prompt alone in the engine, as the check once ran, sees nothing
    cfg, params, engine = started(config)
    try:
        share_slot_zeros_ring(engine)
        ref = reference_afmoe.Reference(params, family.reference_config(cfg))
        rng = np.random.RandomState(5)
        for n in config["check"]["prompt_tokens"]:
            sent = family.cut_prompt(ref, rng.randint(3, cfg.vocab_size, n),
                                     8, 64)
            req = engine.submit(sent, max_new_tokens=8, stop_at_eos=False,
                                keep_first_logits=True)
            chosen = req.result(600)
            got = family.judge_prompt(ref, sent, req.first_logits, chosen,
                                      64)
            assert got["logit_err"] < 1e-4
            assert got["gap"] < 1e-3 and got["undecided_gap"] < 1e-3
    finally:
        engine.close()


def every_pair_here(config, seed=SEED):
    """Seeded weights whose selection bias sends every pair of every token
    to the held experts: engine and reference read the same bias."""
    import jax.numpy as jnp

    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    lo, count = cfg.experts_held
    for name in params:
        if name.endswith("select_bias"):
            params[name] = jnp.zeros_like(params[name]) \
                .at[lo:lo + count].set(1.0)
    return params


def test_the_every_row_branch_forced_and_sound_is_correct():
    # 52 live rows x 4 pairs = 208 held pairs, over the 192 leading rows
    # that a 64-token prefill's grouped products usually run over
    config = toy(prompt_tokens=[6, 20, 52])
    _, notes = check(config, every_pair_here(config))
    assert notes == []


def test_an_engine_that_never_takes_the_every_row_branch_is_not_correct(
        monkeypatch):
    import jax

    config = toy(prompt_tokens=[6, 20, 52])
    params = every_pair_here(config)
    # the planted fault: the routed layer's `lax.cond` always runs its
    # products over the leading rows, so the pairs past them are dropped
    monkeypatch.setattr(jax.lax, "cond",
                        lambda pred, few, every, *a: few(*a))
    compared, notes = check(config, params)
    assert notes and compared["prefill_logit_err_p52"][0] \
        > reference_afmoe.LOGIT_ERR
    # the short prompts' pairs fit the leading rows: dropless still
    assert compared["prefill_logit_err_p6"][0] < reference_afmoe.LOGIT_ERR


@pytest.mark.parametrize("only", ["experts", "kv"])
def test_a_control_rounds_one_mechanism_alone(only):
    """The engine's outputs against the reference with one mechanism in 8
    bits: further off than against the reference as it is, and judged by
    the same `judge` the check uses."""
    config = toy()
    cfg, params, engine = started(config)
    try:
        rc = family.reference_config(cfg)
        ref = reference_afmoe.Reference(params, rc)
        low = reference_afmoe.Reference(params, rc, via="float8_e4m3fn",
                                        only=only)
        rng = np.random.RandomState(3)
        sents = [family.cut_prompt(ref, rng.randint(3, cfg.vocab_size, n),
                                   8, 64)
                 for n in config["check"]["prompt_tokens"]]
        outs, live = family.engine_outputs(engine, sents, config["check"],
                                           rng)
        sound = {n: v for n, v, _ in
                 family.judge(ref, sents, outs, live, config["check"])[0]}
        control = {n: v for n, v, _ in
                   family.judge(low, sents, outs, live, config["check"])[0]}
    finally:
        engine.close()
    for name in ("prefill_logit_err_p6", "prefill_logit_err_p20",
                 "prefill_logit_err_p40"):
        assert sound[name] < 1e-4
        assert control[name] > 20 * sound[name]


def test_the_reference_knows_its_controls():
    with pytest.raises(ValueError, match="only"):
        reference_afmoe.forward({}, np.zeros(4, np.int32), {}, only="heads")
