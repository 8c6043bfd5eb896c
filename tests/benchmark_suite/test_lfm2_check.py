"""What `families/lfm2.check_correct` can see, at toy sizes on the CPU.

The check prompts (two of them shorter than the tail, all mid-bucket, half
sampled) are prefilled and decoded with every other slot live; each keeps
its prefill's logits, the logits of every step, its slot's tails and its
pages. The planted faults come out as not correct: a prefill that keeps the
tail of the padded bucket's end (in the engine), and, judged against the
reference built wrong, the gates swapped, the expert bias weighed into the
kept scores and the norms on q and k left out. The lower-precision control
rounds the router's scores and the convolution's sum alone; at bfloat16
weights, where the reference rounds what enters a product as the engine
does, the control and the weighed bias come out not correct by the chip's
own limits."""

import copy
import json

import numpy as np
import pytest

from benchmark import reference_lfm2 as rl
from benchmark.families import lfm2 as family
from benchmark.readings_lfm2 import JUDGES, PLANTS, tail_from_the_buckets_end

from .test_bench_lfm2 import TOY_LFM2, lfm2_root  # noqa: F401

TRAFFIC = {"max_context": 48, "prompt_tokens": {"max": 30}}
SEED = 2 ** 31 + 11
# at float32 the engine is the reference to rounding: a fault is what lies
# over this
NEAR = 5e-4


def toy(**check):
    config = copy.deepcopy(TOY_LFM2)
    config["check"].update(check)
    return config


def started(config, seed=SEED):
    cfg = family.model_config(config)
    params = family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config, TRAFFIC)
    return cfg, params, engine.start(warmup=False)


@pytest.fixture(scope="module")
def sound():
    """One sound engine's outputs for the check prompts, judged many ways."""
    config = toy()
    cfg, params, engine = started(config)
    try:
        rng = np.random.RandomState(3)
        prompts = family.check_prompts(cfg, config["check"], rng)
        outs, live = family.engine_outputs(engine, prompts,
                                           config["check"], rng)
    finally:
        engine.close()
    return cfg, params, config["check"], prompts, outs, live, {}


def judged(sound, **how):
    """One engine's outputs judged against a reference built `how`; a
    verdict is made once (a reference is three compiles)."""
    cfg, params, check, prompts, outs, live, made = sound
    key = tuple(sorted(how.items()))
    if key not in made:
        ref = rl.Reference(params, family.reference_config(cfg), **how)
        compared, notes, detail = family.judge(ref, prompts, outs, live,
                                               check)
        made[key] = {n: v for n, v, _ in compared}, notes, detail
    return made[key]


def test_the_check_decodes_with_every_other_slot_live():
    config = toy()
    cfg, params, engine = started(config)
    try:
        compared, notes, detail = family.check_correct(
            None, engine, params, cfg, config["check"], SEED)
    finally:
        engine.close()
    assert notes == []
    got = {n: (v, lim) for n, v, lim in compared}
    assert got["rows_not_live_beside_check"] == (0, 0)
    for name, limit in (("logit_err_median", rl.LOGIT_ERR),
                        ("prompt_logit_err_q25", rl.PROMPT_LOGIT_ERR),
                        ("prefill_logit_err_second", rl.PREFILL_LOGIT_ERR),
                        ("first_steps_logit_err_median",
                         rl.FIRST_STEPS_LOGIT_ERR),
                        ("tail_err_median", rl.TAIL_ERR),
                        ("tail_err_first", rl.TAIL_ERR_FIRST),
                        ("kv_err_max", rl.KV_ERR),
                        ("kv_last_err", rl.KV_LAST_ERR),
                        ("kv_turned_share", rl.KV_TURNED_SHARE)):
        value, lim = got[name]
        assert value < NEAR and lim == limit, name
    # a prompt's rows: the prefill's and seven steps'
    assert [len(p["rows"]) for p in detail["prompts"].values()] == [8] * 4
    assert detail["rows"] == 4 * 8
    # every position the four requests cached, less each one's last token
    assert detail["positions"] == sum(n + 7 for n in (1, 2, 15, 40))
    assert detail["row_logit_err_max"] < NEAR
    assert detail["rows_over_row_turned"] == 0


def test_requests_beside_the_check_that_end_early_void_it():
    config = toy()
    config["check"]["beside"]["new_tokens"] = 2
    cfg, params, engine = started(config)
    try:
        compared, notes, _ = family.check_correct(
            None, engine, params, cfg, config["check"], SEED)
    finally:
        engine.close()
    assert any("still decoding" in n for n in notes)


def test_a_check_that_does_not_fit_is_refused():
    config = toy()
    config["check"]["beside"]["requests"] = 5          # + 4 prompts > 8
    with pytest.raises(ValueError, match="slots"):
        family.engine_config(config, TRAFFIC)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        family.engine_config(toy(), dict(TRAFFIC,
                                         prompt_tokens={"max": 100}))
    with pytest.raises(ValueError, match="temperature"):
        family.engine_config(toy(temperatures=[0.0]), TRAFFIC)


def test_a_conv_tail_taken_from_the_padded_buckets_end_is_not_correct():
    """Planted in the ENGINE. The prefill's own logits are sound (the tail
    is what it leaves behind); the first two steps after it read the wrong
    tail, and by the decode's end the tail has moved on to real tokens."""
    from paddle_tpu.core import registry

    config = toy()
    sound_op = registry.get("gated_short_conv_prefill").forward
    with tail_from_the_buckets_end():
        assert registry.get("gated_short_conv_prefill").forward \
            is not sound_op
        cfg, params, engine = started(config)
        try:
            compared, notes, detail = family.check_correct(
                None, engine, params, cfg, config["check"], SEED)
        finally:
            engine.close()
    got = {n: v for n, v, _ in compared}
    assert any("first two steps" in n for n in notes)
    assert got["first_steps_logit_err_median"] > rl.FIRST_STEPS_LOGIT_ERR
    assert got["tail_err_first"] < NEAR
    assert all(p["rows"][0] < NEAR for p in detail["prompts"].values())
    # and the op is what it was once the fault is taken out again
    assert registry.get("gated_short_conv_prefill").forward is sound_op


def test_the_sound_engine_is_correct_by_every_limit(sound):
    got, notes, _ = judged(sound)
    assert notes == []
    assert max(v for n, v in got.items()
               if n != "rows_not_live_beside_check") < NEAR


@pytest.mark.parametrize("fault,seen_by", [
    ("gates_swapped", ("tail_err_first", "tail_err_median",
                       "logit_err_median", "prefill_logit_err_second")),
    ("bias_weighted", ("logit_err_median",)),
    ("no_qk_norm", ("kv_err_max",))])
def test_a_reference_built_wrong_judges_the_engine_not_correct(sound, fault,
                                                              seen_by):
    """What the check would read of an engine with that fault: the same
    distance, from the other side."""
    got, notes, _ = judged(sound, fault=fault)
    limits = {"tail_err_first": rl.TAIL_ERR_FIRST,
              "tail_err_median": rl.TAIL_ERR,
              "logit_err_median": rl.LOGIT_ERR,
              "prefill_logit_err_second": rl.PREFILL_LOGIT_ERR,
              "kv_err_max": rl.KV_ERR}
    # at float32 the sound engine reads under NEAR: a fault is what stands
    # far over it; the limits are the chip's, set over bfloat16's rounding,
    # and at this toy width and float32 a bias of std 0.03 moves a weight
    # less than that (`..._at_bfloat16` below holds it to the limits)
    for name in seen_by:
        assert got[name] > 10 * NEAR, (fault, name, got[name])
        if fault != "bias_weighted":
            assert got[name] > limits[name], (fault, name, got[name])
    assert notes or fault == "bias_weighted"
    if fault == "no_qk_norm":       # nothing of the tails' own arithmetic
        assert got["tail_err_first"] < NEAR
    if fault == "bias_weighted":    # the dense layer's tail is untouched
        assert got["tail_err_first"] < NEAR


def test_the_lower_precision_control_rounds_scores_and_sum_alone(sound):
    got, _, _ = judged(sound, via="bfloat16")
    base, _, _ = judged(sound)
    # the first layer's tail is z itself, before any rounded sum's use
    assert got["tail_err_first"] == pytest.approx(base["tail_err_first"])
    # scores rounded to bfloat16 turn routing choices: at some positions
    # the last attention layer's K and V are another expert's
    assert base["kv_turned_share"] == 0 < got["kv_turned_share"]


# the real configuration's pattern, mixers and routing at toy widths and in
# ITS number format: bfloat16 weights, pages and tails
BF16 = {"dtype": "bfloat16", "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": 16,
        "experts_held": [0, 16], "num_experts_per_tok": 4,
        "layer_types": TOY_LFM2["layer_types"][:4] * 2,
        "num_hidden_layers": 8, "layers_held": list(range(8)),
        "num_dense_layers": 2}


@pytest.fixture(scope="module")
def sound_at_bfloat16():
    config = copy.deepcopy(TOY_LFM2)
    config.update(BF16)
    config["check"]["new_tokens"] = 16
    cfg, params, engine = started(config, seed=5)
    try:
        rng = np.random.RandomState(3)
        prompts = family.check_prompts(cfg, config["check"], rng)
        outs, live = family.engine_outputs(engine, prompts,
                                           config["check"], rng)
    finally:
        engine.close()
    return cfg, params, config["check"], prompts, outs, live, {}


def test_the_reference_follows_the_number_format(sound_at_bfloat16):
    """Rounded where the engine rounds, the reference has the dense layers
    to the last bit and the sound engine correct by the chip's limits; the
    same reference on float32 copies of the weights, which rounds nothing,
    is bfloat16's whole rounding away."""
    import jax.numpy as jnp

    cfg, params, check, prompts, outs, live, _ = sound_at_bfloat16
    got, notes, _ = judged(sound_at_bfloat16)
    assert notes == []
    assert got["tail_err_first"] == 0.0 and got["kv_turned_share"] == 0.0
    wide = {k: jnp.asarray(v).astype(jnp.float32)
            for k, v in params.items()}
    ref = rl.Reference(wide, dict(family.reference_config(cfg),
                                  dtype="float32"))
    compared, _, _ = family.judge(ref, prompts, outs, live, check)
    plain = {n: v for n, v, _ in compared}
    assert plain["tail_err_first"] > 1e-3
    assert plain["logit_err_median"] > 10 * got["logit_err_median"]


@pytest.mark.parametrize("how,failed", [
    ({"via": "bfloat16"}, ("kv_last_err", "kv_turned_share")),
    ({"fault": "bias_weighted"}, ("kv_last_err", "kv_turned_share"))])
def test_the_control_and_the_weighed_bias_are_not_correct_at_bfloat16(
        sound_at_bfloat16, how, failed):
    """By the limits the chip's readings set, with room: each reads over
    1.2 times a limit that the sound engine reads under a tenth of."""
    got, notes, _ = judged(sound_at_bfloat16, **how)
    sound_got, _, _ = judged(sound_at_bfloat16)
    limits = {"kv_last_err": rl.KV_LAST_ERR,
              "kv_turned_share": rl.KV_TURNED_SHARE}
    assert notes
    for name in failed:
        assert got[name] > 1.2 * limits[name], (how, name, got[name])
        assert sound_got[name] < 0.1 * limits[name], (name, sound_got[name])


def test_the_reference_knows_its_faults():
    with pytest.raises(ValueError, match="fault"):
        rl.forward({}, np.zeros(4, np.int32), {"conv_L_cache": 3},
                   fault="heads")
    assert set(how.get("fault") for how in JUDGES.values()) - {None} \
        == set(rl.FAULTS)
    assert JUDGES["bf16"] == {"via": "bfloat16"}
    assert PLANTS == ("conv_tail",)


def test_the_readings_script_judges_the_reference_and_each_fault(
        lfm2_root, capsys):  # noqa: F811
    from benchmark import readings_lfm2

    readings_lfm2.main(["--config", "toy_lfm2", "--traffic", "lfm2_closed",
                        "--seed", "5"], root=lfm2_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] == ["as it is"] + list(JUDGES)
    assert lines[0]["correct"] is True
    by_name = {ln["reference"]: ln for ln in lines}
    assert not by_name["gates_swapped"]["correct"]
    assert not by_name["no_qk_norm"]["correct"]
    assert len(lines[0]["row_errs"]["15"]) == 8
    assert len(lines[0]["tail_err"]["15"]) == 3


def test_the_readings_script_reads_the_planted_fault(lfm2_root,  # noqa: F811
                                                     capsys):
    from benchmark import readings_lfm2

    readings_lfm2.main(["--config", "toy_lfm2", "--traffic", "lfm2_closed",
                        "--seed", "5", "--plant", "conv_tail"],
                       root=lfm2_root)
    judged_, = [json.loads(line) for line in
                capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert judged_["planted"] == "conv_tail" and not judged_["correct"]
    assert any("first two steps" in n for n in judged_["notes"])
