"""What `families/falcon_h1.check_correct` can see, at toy sizes on the CPU.

The check prompts are prefilled and decoded with every other slot live and
each keeps its slot's recurrent state as it stands after the decode. Two
planted faults come out as not correct: live rows fed each other's state
(which the prefill's logits cannot see: only what was carried through the
cache shows it), and a prefill that keeps the conv tail of the padded
bucket's end. The controls: the reference with one part of it in the
nearest precision below the configuration's."""

import copy
import json

import numpy as np
import pytest

from benchmark import reference_falcon_h1 as rf
from benchmark.families import falcon_h1 as family
from benchmark.readings_falcon_h1 import (VIA, b_and_c_swapped,
                                          swap_last_rows_state,
                                          tail_from_the_buckets_end)

from .test_bench_falcon_h1 import TOY_FALCON, falcon_root  # noqa: F401

TRAFFIC = {"max_context": 48, "prompt_tokens": {"max": 30}}
SEED = 2 ** 31 + 11
PROMPTS = (15, 17, 40)


def toy(**check):
    config = copy.deepcopy(TOY_FALCON)
    config["check"].update(check)
    return config


def started(config, seed=SEED, faulty=None):
    """`faulty(cfg, params)` gives the weights the ENGINE runs on; the check
    is handed the sound ones."""
    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    engine = family.make_engine(
        cfg, params if faulty is None else faulty(cfg, params), config,
        TRAFFIC)
    return cfg, params, engine.start(warmup=False)


def check(config, plant=None, faulty=None):
    cfg, params, engine = started(config, faulty=faulty)
    try:
        if plant is not None:
            plant(engine)
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
                                            rf.MARGIN)
    for n in PROMPTS:
        value, limit = compared[f"prefill_logit_err_p{n}"]
        assert value < 2e-4 and limit == rf.LOGIT_ERR
        value, limit = compared[f"state_err_p{n}"]
        assert value < 2e-4 and limit == rf.STATE_ERR


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
    compared, notes = check(toy(), plant=swap_last_rows_state)
    assert notes and any("recurrent state" in n for n in notes)
    for n in PROMPTS:
        assert compared[f"prefill_logit_err_p{n}"][0] < 2e-4
    worst = max(compared[f"state_err_p{n}"][0] for n in PROMPTS)
    assert worst > 10 * rf.STATE_ERR
    assert compared["greedy_logit_gap"][0] > rf.MARGIN


def test_a_conv_tail_taken_from_the_padded_buckets_end_is_not_correct():
    """A prompt that fills its bucket would not show it; none of the check
    prompts does."""
    with tail_from_the_buckets_end():
        compared, notes = check(toy())
    assert notes and any("recurrent state" in n for n in notes)
    for n in PROMPTS:
        assert compared[f"prefill_logit_err_p{n}"][0] < 2e-4
        assert compared[f"state_err_p{n}"][0] > rf.STATE_ERR
    # and the op is what it was once the fault is taken out again
    compared, notes = check(toy())
    assert notes == []


def test_a_mup_table_with_b_and_c_exchanged_is_not_correct():
    """The reference lays the five `ssm_multipliers` over z, x, B, C, dt
    itself, from the configuration's widths: a program whose table puts
    C's multiplier on B's columns and B's on C's is not correct, by the
    prefill's logits and by the state."""
    assert TOY_FALCON["ssm_multipliers"][2] != TOY_FALCON["ssm_multipliers"][3]
    compared, notes = check(toy(), faulty=b_and_c_swapped)
    assert notes
    for n in PROMPTS:
        assert compared[f"state_err_first_p{n}"][0] > 10 * rf.STATE_ERR_FIRST
    assert max(compared[f"prefill_logit_err_p{n}"][0] for n in PROMPTS) \
        > rf.LOGIT_ERR


def test_the_reference_reads_no_mup_table_of_the_programs():
    config = toy()
    cfg = family.model_config(config)
    params = dict(family.make_params(cfg, SEED))
    rc = family.reference_config(cfg)
    assert rc["ssm_multipliers"] == tuple(config["ssm_multipliers"])
    tokens = np.arange(3, 20, dtype=np.int32)
    want, _ = rf.Reference(params, rc).rows(tokens, 32, 0, 17)
    del params["fh_mup_vector"]
    got, _ = rf.Reference(params, rc).rows(tokens, 32, 0, 17)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("only", rf.CONTROLS)
def test_a_control_rounds_one_mechanism_alone(only):
    """The engine's outputs against the reference with one mechanism in the
    nearest precision below: further off than against the reference as it
    is, by the number that mechanism moves, and judged by the same `judge`
    the check uses."""
    config = toy()
    cfg, params, engine = started(config)
    try:
        rc = family.reference_config(cfg)
        ref = rf.Reference(params, rc)
        low = rf.Reference(params, rc, via=VIA[only], only=only)
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
    moved = "state_err" if only == "state" else "prefill_logit_err"
    for n in PROMPTS:
        assert sound[f"{moved}_p{n}"] < 2e-4
        assert control[f"{moved}_p{n}"] > 10 * sound[f"{moved}_p{n}"]
    if only == "state":     # the prefill's logits never see a carried state
        for n in PROMPTS:
            assert control[f"prefill_logit_err_p{n}"] > 0


def test_the_reference_knows_its_controls():
    with pytest.raises(ValueError, match="only"):
        rf.forward({}, np.zeros(4, np.int32), {}, only="heads")
    assert set(VIA) == set(rf.CONTROLS)


def test_the_readings_script_judges_the_reference_and_each_control(
        falcon_root, capsys):  # noqa: F811
    from benchmark import readings_falcon_h1

    readings_falcon_h1.main(["--config", "toy_falcon", "--traffic",
                             "falcon_closed", "--seed", "5"],
                            root=falcon_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] \
        == ["as it is"] + list(rf.CONTROLS)
    assert lines[0]["correct"] is True
    assert not lines[1]["correct"]          # every weight matrix in 8 bits


@pytest.mark.parametrize("plant,seen", [("state_slot", "recurrent state"),
                                        ("conv_tail", "recurrent state"),
                                        ("mup_layout", "recurrent state")])
def test_the_readings_script_reads_a_planted_fault(falcon_root, capsys,  # noqa: F811
                                                   plant, seen):
    from benchmark import readings_falcon_h1

    readings_falcon_h1.main(["--config", "toy_falcon", "--traffic",
                             "falcon_closed", "--seed", "5", "--plant",
                             plant], root=falcon_root)
    judged, = [json.loads(line) for line in
               capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert judged["planted"] == plant and not judged["correct"]
    assert judged["reference"] == "as it is"
    assert any(seen in n for n in judged["notes"])
