"""What `families/xing4.check_correct` can see, at toy sizes on the CPU.

The check prompts, greedy and sampled, are prefilled and stepped with the
module drafting and every other slot live. Each planted fault comes out as
not correct, by the limit that is its own: an engine that leaves a rejected
draft's latent row in place (the step after attends it), a module fed the
hidden state one position off, a rejection that redraws from p, the maps
computed in bfloat16 where float32 is stated, and the lower-precision
control (the configuration states float32 here, so bfloat16)."""

import copy

import numpy as np
import pytest

from benchmark import reference_xing4 as rx
from benchmark.families import xing4 as family
from benchmark.readings_xing4 import leave_rejected_rows

from .test_bench_xing4 import TOY_XING4

TRAFFIC = {"max_context": 48}
SEED = 2 ** 31 + 11


def started(config, plant=None, seed=SEED):
    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config, TRAFFIC)
    if plant is not None:
        plant(engine)
    return cfg, params, engine.start(warmup=False)


def through_the_engine(plant=None):
    config = copy.deepcopy(TOY_XING4)
    cfg, params, engine = started(config, plant)
    try:
        rng = np.random.RandomState((SEED + 7919) % (2 ** 32))  # the check's
        prompts = family.check_prompts(cfg, config["check"], rng)
        outs, live = family.engine_outputs(engine, prompts, config["check"],
                                           rng)
    finally:
        engine.close()
    return config, params, family.reference_config(cfg), prompts, outs, live


@pytest.fixture(scope="module")
def outputs():
    """The sound engine's outputs for the toy check, judged several ways."""
    return through_the_engine()


def judged(outputs, redraw_from_p=False, **reference):
    config, params, rc, prompts, outs, live = outputs
    ref = rx.Reference(params, rc, **reference)
    compared, notes, detail = family.judge(ref, prompts, outs, live,
                                           config["check"], redraw_from_p)
    return {n: (v, lim) for n, v, lim in compared}, notes, detail


def test_the_check_steps_greedy_and_sampled_prompts_beside_live_slots(
        outputs):
    compared, notes, detail = judged(outputs)
    assert notes == []
    assert compared["rows_not_live_beside_check"] == (0, 0)
    lim = rx.limits("float32")      # the toy states float32
    assert lim["ROW_LOGIT_ERR"] < rx.ROW_LOGIT_ERR == rx.limits("bfloat16")[
        "ROW_LOGIT_ERR"]
    for name in ("step_logit_err", "draft_logit_err",
                 "prefill_logit_err_p10", "prefill_logit_err_p20",
                 "prefill_logit_err_p14"):
        value, limit = compared[name]
        assert value < 1e-4 and limit == lim["ROW_LOGIT_ERR"]
    for name in ("draft_logit_err_median",
                 "first_position_logit_err_median",
                 "second_position_logit_err_median"):
        value, limit = compared[name]
        assert 0 < value < 1e-4 and limit == lim["MEDIAN_LOGIT_ERR"]
    for n in (10, 20, 14):
        for what in ("unrouted", "held", "module"):
            value, limit = compared[f"latent_err_{what}_p{n}"]
            assert value < 1e-5 and limit == rx.FLOAT32["LATENT_ERR"]
    assert compared["rule_distance"] == (0.0, rx.RULE_DISTANCE)
    for name in ("uniforms_off", "positions_off"):
        assert compared[name] == (0, 0)
    assert compared["q_carry_err"][0] < 1e-6
    # the greedy prompt rejects, the warmest one accepts
    prompts = detail["prompts"]
    assert prompts["10"]["rejected_rows"] == 2
    assert prompts["14"]["accepted"] >= 3 > prompts["10"]["accepted"]
    assert prompts["20"]["steps"] - 1 > prompts["20"]["accepted"]


def test_a_rejected_drafts_row_left_in_place_is_not_correct():
    outs = through_the_engine(plant=leave_rejected_rows)
    compared, notes, _ = judged(outs)
    assert notes
    assert compared["positions_off"][0] > 0
    assert compared["step_logit_err"][0] > 10 * rx.FLOAT32["ROW_LOGIT_ERR"] \
        or compared["step_logit_err_undecided"][0] > 0.01
    assert any("another position" in n for n in notes)


def test_a_module_fed_the_hidden_state_one_position_off_is_not_correct(
        outputs):
    compared, notes, _ = judged(outputs, fault="hidden_off")
    assert notes and all("module" in n for n in notes)
    assert compared["draft_logit_err"][0] > 0.1
    assert compared["step_logit_err"][0] < 1e-4     # the model is sound


def test_a_rejection_that_redraws_from_p_is_not_correct(outputs):
    compared, notes, _ = judged(outputs, redraw_from_p=True)
    assert notes and all("acceptance rule" in n for n in notes)
    assert compared["rule_distance"][0] > 100 * rx.RULE_DISTANCE


@pytest.mark.parametrize("only", rx.CONTROLS)
def test_a_control_in_the_nearest_lower_precision_is_not_correct(outputs,
                                                                  only):
    """float32 is what the toy configuration states, so the nearest
    precision below is bfloat16: every weight matrix through it, or the
    maps computed in it."""
    sound, _, _ = judged(outputs)
    compared, notes, _ = judged(outputs, via="bfloat16", only=only)
    assert notes
    worst = max(v for n, (v, _) in compared.items()
                if n.startswith("latent_err"))
    assert worst > rx.FLOAT32["LATENT_ERR"] > 30 * max(
        v for n, (v, _) in sound.items() if n.startswith("latent_err"))


def test_the_reference_knows_its_controls_and_faults():
    with pytest.raises(ValueError, match="only="):
        rx.forward({}, np.zeros(4, np.int32), {}, only="latent")
    with pytest.raises(ValueError, match="fault="):
        rx.forward({}, np.zeros(4, np.int32), {}, fault="other")
