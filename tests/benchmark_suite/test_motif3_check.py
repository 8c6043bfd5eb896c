"""What `families/motif3.check_correct` can see, at toy sizes on the CPU.

The check prompts are prefilled and decoded with every other slot live, so
a fault that needs neighbours shows: a live row fed another slot's RING
table comes out as not correct (by the rows its ring then holds and by its
tokens). An engine judged against the reference without the noise head's
subtraction, or with one Sinkhorn iteration, comes out as not correct: the
check sees the differential heads and the doubly stochastic map. The
lower-precision control: the configuration states float32 here, so the
nearest precision below is bfloat16."""

import copy

import numpy as np
import pytest

from benchmark import reference_motif3 as rm
from benchmark.families import motif3 as family
from benchmark.readings_motif3 import share_slot_zeros_ring

from .test_bench_motif3 import TOY_MOTIF3

TRAFFIC = {"max_context": 48}
SEED = 2 ** 31 + 11


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


@pytest.fixture(scope="module")
def outputs():
    """The sound engine's outputs for the toy check, judged several ways."""
    config = copy.deepcopy(TOY_MOTIF3)
    cfg, params, engine = started(config)
    try:
        rc = family.reference_config(cfg)
        ref = rm.Reference(params, rc)
        rng = np.random.RandomState((SEED + 7919) % (2 ** 32))  # the check's
        sents = family.check_prompts(ref, cfg, config["check"], rng)
        outs, live = family.engine_outputs(engine, sents, config["check"],
                                           rng)
    finally:
        engine.close()
    return config, params, rc, ref, sents, outs, live


def judged(outputs, **reference):
    config, params, rc, ref, sents, outs, live = outputs
    if reference:
        ref = rm.Reference(params, rc, **reference)
    compared, notes, _ = family.judge(ref, sents, outs, live,
                                      config["check"])
    return {n: v for n, v, _ in compared}, notes


def test_the_check_decodes_with_every_other_slot_live():
    config = copy.deepcopy(TOY_MOTIF3)
    compared, notes = check(config)
    assert notes == []
    assert compared["rows_not_live_beside_check"] == (0, 0)
    # the toy states float32 and is held to float32's limits
    lim = rm.limits("float32")
    assert lim["LOGIT_ERR"] < rm.LOGIT_ERR == rm.limits("bfloat16")[
        "LOGIT_ERR"]
    assert compared["greedy_logit_gap"] == (pytest.approx(0, abs=1e-3),
                                            lim["MARGIN"])
    for n in (10, 20, 40):
        # float32 engine against the float32 reference: orders of sums
        value, limit = compared[f"prefill_logit_err_p{n}"]
        assert value < 1e-4 and limit == lim["LOGIT_ERR"]
        for what in ("ring", "pages"):
            value, limit = compared[f"latent_err_{what}_p{n}"]
            assert value < 1e-5 and limit == lim["LATENT_ERR"]


def test_a_live_row_fed_another_slots_ring_table_is_not_correct():
    _, notes = check(copy.deepcopy(TOY_MOTIF3), plant=share_slot_zeros_ring)
    assert notes
    assert any("ring" in n for n in notes)      # the rows its ring held
    assert not any("pages held" in n for n in notes)    # its pages are sound


def test_a_control_in_the_nearest_lower_precision_is_not_correct(outputs):
    """float32 is what the toy configuration states, so the nearest
    precision below is bfloat16: every weight matrix through it fails the
    prefill's logits, the latent rows through it fail LATENT_ERR, each by
    the limits a float32 configuration is held to, with room on both
    sides."""
    lim = rm.limits("float32")
    sound, notes = judged(outputs)
    assert notes == []
    weights, notes = judged(outputs, via="bfloat16", only="weights")
    assert any("prefill logits" in n for n in notes)
    worst = max(v for n, v in weights.items()
                if n.startswith("prefill_logit_err"))
    assert worst > 2 * lim["LOGIT_ERR"] > 20 * max(
        v for n, v in sound.items() if n.startswith("prefill_logit_err"))
    # the latent control acts on the rows themselves
    half, notes = judged(outputs, via="bfloat16", only="latent")
    assert any("latent rows" in n for n in notes)
    rows = [v for n, v in half.items() if n.startswith("latent_err")]
    assert min(rows) > 2 * lim["LATENT_ERR"]
    # and under the bfloat16 cell's own limit lies bfloat16's rounding, as
    # it must (the chip's rings and pages ARE bfloat16), over it float8's
    assert max(rows) < rm.LATENT_ERR / 3
    low, _ = judged(outputs, via="float8_e4m3fn", only="latent")
    assert min(v for n, v in low.items() if n.startswith("latent_err")) \
        > rm.LATENT_ERR


def test_dropping_the_noise_heads_subtraction_is_not_correct(outputs):
    got, notes = judged(outputs, noise=False)
    assert notes
    # far over the bfloat16 cell's limit too: the real cell would see it
    assert max(v for n, v in got.items()
               if n.startswith("prefill_logit_err")) > rm.LOGIT_ERR


def test_one_sinkhorn_iteration_is_not_correct(outputs):
    got, notes = judged(outputs, sinkhorn_iters=1)
    assert notes
    assert max(v for n, v in got.items()
               if n.startswith("prefill_logit_err")) > rm.LOGIT_ERR


def test_requests_beside_the_check_that_end_early_void_it():
    config = copy.deepcopy(TOY_MOTIF3)
    config["check"]["beside"]["new_tokens"] = 2
    compared, notes = check(config)
    assert compared["rows_not_live_beside_check"][0] > 0
    assert any("still decoding" in n for n in notes)


def test_the_reference_knows_its_controls():
    with pytest.raises(ValueError, match="only"):
        rm.forward({}, np.zeros(4, np.int32), {}, only="heads")
