"""What `families/qwen3_next.check_correct` can see, at toy sizes on the CPU.

The check prompts are prefilled and decoded with every other slot live and
each keeps its slot's matrix states as they stand after the decode. Three
planted faults come out as not correct: live rows fed each other's state
(which the prefill's logits cannot see: only what was carried through the
cache shows it), a prefill that keeps the conv tail of the padded bucket's
end, and value heads paired with the wrong key head. The controls: the
reference with one part of it in the nearest precision below the
configuration's."""

import copy
import json

import numpy as np
import pytest

from benchmark import reference_qwen3_next as rq
from benchmark.families import qwen3_next as family
from benchmark.readings_qwen3_next import (PLANTS, VIA, planted,
                                           swap_last_rows_state)

from .test_bench_qwen3_next import TOY_QWEN, qwen_root  # noqa: F401

TRAFFIC = {"max_context": 48, "prompt_tokens": {"max": 30}}
SEED = 2 ** 31 + 11
PROMPTS = (15, 17, 40)


def toy(**check):
    config = copy.deepcopy(TOY_QWEN)
    config["check"].update(check)
    return config


def started(config, seed=SEED):
    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config, TRAFFIC)
    return cfg, params, engine.start(warmup=False)


def check(config, plant=None):
    with planted(plant):
        cfg, params, engine = started(config)
        try:
            if plant == "state_slot":
                swap_last_rows_state(engine)
            compared, notes, _ = family.check_correct(
                None, engine, params, cfg, config["check"], SEED)
            return {n: (v, lim) for n, v, lim in compared}, notes
        finally:
            engine.close()


def test_the_check_decodes_with_every_other_slot_live():
    compared, notes = check(toy())
    assert notes == []
    assert compared["rows_not_live_beside_check"] == (0, 0)
    assert compared["greedy_logit_gap"] == (pytest.approx(0, abs=1e-3),
                                            rq.MARGIN)
    for n in PROMPTS:
        value, limit = compared[f"prefill_logit_err_p{n}"]
        assert value < 2e-4 and limit == rq.LOGIT_ERR
        value, limit = compared[f"state_err_p{n}"]
        assert value < 2e-4 and limit == rq.STATE_ERR
        value, limit = compared[f"state_err_first_p{n}"]
        assert value < 2e-4 and limit == rq.STATE_ERR_FIRST
        value, limit = compared[f"kv_err_p{n}"]
        assert value < 2e-5 and limit == rq.KV_ERR


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
    with pytest.raises(ValueError, match="largest prefill bucket"):
        family.engine_config(toy(), dict(TRAFFIC,
                                         prompt_tokens={"max": 100}))


def test_a_live_row_fed_another_slots_state_is_not_correct():
    """The last two live rows (check prompts) exchange their states before
    every step. The prefills are sound, so their logits pass: what shows it
    is what was carried through the cache."""
    compared, notes = check(toy(), plant="state_slot")
    assert notes and any("matrix state" in n for n in notes)
    for n in PROMPTS:
        assert compared[f"prefill_logit_err_p{n}"][0] < 2e-4
    worst = max(compared[f"state_err_p{n}"][0] for n in PROMPTS)
    assert worst > 10 * rq.STATE_ERR
    assert compared["greedy_logit_gap"][0] > rq.MARGIN


def test_a_conv_tail_taken_from_the_padded_buckets_end_is_not_correct():
    """A prompt that fills its bucket would not show it; none of the check
    prompts does."""
    compared, notes = check(toy(), plant="conv_tail")
    assert notes and any("matrix state" in n for n in notes)
    for n in PROMPTS:
        assert compared[f"prefill_logit_err_p{n}"][0] < 2e-4
        assert compared[f"state_err_first_p{n}"][0] > rq.STATE_ERR_FIRST
    # and the op is what it was once the fault is taken out again
    compared, notes = check(toy())
    assert notes == []


def test_value_heads_on_the_wrong_key_head_are_not_correct():
    """Value head j on key head j mod (key heads), in step and prefill
    alike: the engine agrees with itself and not with the reference, by
    the first layer's state and by the prefill's logits."""
    compared, notes = check(toy(), plant="head_pairing")
    assert notes
    for n in PROMPTS:
        assert compared[f"state_err_first_p{n}"][0] \
            > 10 * rq.STATE_ERR_FIRST
    assert max(compared[f"prefill_logit_err_p{n}"][0] for n in PROMPTS) \
        > rq.LOGIT_ERR
    compared, notes = check(toy())
    assert notes == []


@pytest.mark.parametrize("only", rq.CONTROLS)
def test_a_control_rounds_one_mechanism_alone(only):
    """The engine's outputs against the reference with one mechanism in the
    nearest precision below: further off than against the reference as it
    is, by the number that mechanism moves, and judged by the same `judge`
    the check uses."""
    config = toy()
    cfg, params, engine = started(config)
    try:
        rc = family.reference_config(cfg)
        ref = rq.Reference(params, rc)
        low = rq.Reference(params, rc, via=VIA[only], only=only)
        rng = np.random.RandomState(3)
        sents = family.check_prompts(cfg, config["check"], rng)
        outs, live = family.engine_outputs(engine, sents, config["check"],
                                           rng)
        sound = {n: v for n, v, _ in
                 family.judge(ref, sents, outs, live, config["check"])[0]}
        control = {n: v for n, v, _ in
                   family.judge(low, sents, outs, live, config["check"])[0]}
    finally:
        engine.close()
    moved = {"state": "state_err_first", "kv": "kv_err",
             "weights": "prefill_logit_err"}[only]
    for n in PROMPTS:
        assert sound[f"{moved}_p{n}"] < 2e-4
        assert control[f"{moved}_p{n}"] > 10 * sound[f"{moved}_p{n}"]
    if only == "kv":        # float8's rounding at every position
        assert all(0.02 < control[f"kv_err_p{n}"] < 0.04 for n in PROMPTS)


def test_the_reference_knows_its_controls():
    with pytest.raises(ValueError, match="only"):
        rq.forward({}, np.zeros(4, np.int32), {}, only="heads")
    assert set(VIA) == set(rq.CONTROLS)
    assert PLANTS == ("state_slot", "conv_tail", "head_pairing")


def test_the_readings_script_judges_the_reference_and_each_control(
        qwen_root, capsys):  # noqa: F811
    from benchmark import readings_qwen3_next

    readings_qwen3_next.main(["--config", "toy_qwen", "--traffic",
                              "qwen_closed", "--seed", "5"], root=qwen_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] \
        == ["as it is"] + list(rq.CONTROLS)
    assert lines[0]["correct"] is True
    assert not lines[1]["correct"]          # every weight matrix in 8 bits
    assert len(lines[0]["state_err_by_layer"]["15"]) == 3


@pytest.mark.parametrize("plant", PLANTS)
def test_the_readings_script_reads_a_planted_fault(qwen_root, capsys,  # noqa: F811
                                                   plant):
    from benchmark import readings_qwen3_next

    readings_qwen3_next.main(["--config", "toy_qwen", "--traffic",
                              "qwen_closed", "--seed", "5", "--plant",
                              plant], root=qwen_root)
    judged, = [json.loads(line) for line in
               capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert judged["planted"] == plant and not judged["correct"]
    assert judged["reference"] == "as it is"
    assert any("matrix state" in n for n in judged["notes"])
