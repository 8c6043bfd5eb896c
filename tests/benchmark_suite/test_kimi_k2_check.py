"""What `families/kimi_k2.check_correct` can see, at toy sizes on the CPU.

The check prompts are prefilled and decoded with every other slot live, so
a fault that needs neighbours shows: a live row fed another slot's page
table comes out as not correct, where one prompt alone in the engine does
not notice it. A decode step that drops the rotary part of the score (the
shared key's 4 of a row's 20 values here, 64 of 576 at the real size) comes
out as not correct too. The lower-precision controls: the reference with
only the latent rows, or only W_kvb, rounded to 8 bits."""

import copy

import numpy as np
import pytest

from benchmark import reference_kimi_k2
from benchmark.families import kimi_k2 as family
from benchmark.readings_kimi_k2 import share_slot_zeros_pages

from .test_bench_kimi_k2 import TOY_KIMI

TRAFFIC = {"max_context": 48}
SEED = 2 ** 31 + 11


def toy(**check):
    config = copy.deepcopy(TOY_KIMI)
    config["check"].update(check)
    return config


def started(config, seed=SEED):
    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config, TRAFFIC)
    return cfg, params, engine.start(warmup=False)


def check(config, plant=None):
    cfg, params, engine = started(config)
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
    assert 0 <= count <= limit == int(reference_kimi_k2.UNDECIDED_SHARE
                                      * positions)
    assert compared["greedy_logit_gap"] == (pytest.approx(0, abs=1e-3),
                                            reference_kimi_k2.MARGIN)
    assert compared["greedy_logit_gap_undecided"][1] \
        == reference_kimi_k2.UNDECIDED_MARGIN
    for n in (6, 20, 40):
        value, limit = compared[f"prefill_logit_err_p{n}"]
        assert value < 1e-4 and limit == reference_kimi_k2.LOGIT_ERR


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


def test_a_live_row_fed_another_slots_page_table_is_not_correct():
    config = toy()
    _, notes = check(config, plant=share_slot_zeros_pages)
    assert notes and "greedy token" in notes[0]
    # one prompt alone in the engine sees nothing of it
    cfg, params, engine = started(config)
    try:
        share_slot_zeros_pages(engine)
        ref = reference_kimi_k2.Reference(params,
                                          family.reference_config(cfg))
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


def test_a_step_that_drops_the_rotary_part_of_the_score_is_not_correct(
        monkeypatch):
    """The planted fault: the absorbed query's rotary part is zeroed, so a
    decode step scores on the latent alone. The prefill is sound (its
    logits pass); the greedy tokens are not the reference's."""
    from paddle_tpu.core import registry

    absorb = registry.lookup("mla_absorb_query")
    sound = absorb.forward

    def no_rotary(ins, attrs):
        ins = dict(ins, QRope=[ins["QRope"][0] * 0.0])
        return sound(ins, attrs)

    monkeypatch.setattr(absorb, "forward", no_rotary)
    compared, notes = check(toy())
    assert notes and any("greedy token" in n for n in notes)
    for n in (6, 20, 40):
        assert compared[f"prefill_logit_err_p{n}"][0] < 1e-4
    assert compared["greedy_logit_gap"][0] > 5 * reference_kimi_k2.MARGIN


@pytest.mark.parametrize("only", ["latent", "kvb"])
def test_a_control_rounds_one_mechanism_alone(only):
    """The engine's outputs against the reference with one mechanism in 8
    bits: further off than against the reference as it is, and judged by
    the same `judge` the check uses."""
    config = toy()
    cfg, params, engine = started(config)
    try:
        rc = family.reference_config(cfg)
        ref = reference_kimi_k2.Reference(params, rc)
        low = reference_kimi_k2.Reference(params, rc, via="float8_e4m3fn",
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
        reference_kimi_k2.forward({}, np.zeros(4, np.int32), {},
                                  only="heads")
