"""The `falcon_h1` family: paddle_tpu/models/falcon_h1.py behind
`DecodeEngine`, held against `benchmark/reference_falcon_h1.py` by logits
AND by the recurrent state itself, and counted by
`benchmark/flops_falcon_h1.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole and unchanged): `num_hidden_layers` (the layers held:
the published pattern has period 1, so the first that many), `vocab_size`
(rows of embedding and head held; traffic ids, logits and sampling are
over them), `max_context`, `dtype` (weights, K/V pages, the conv tail),
`ssm_state_dtype` (the recurrent state's: float32, a key of the
configuration and not a knob), `kv_pages` (context pages, one class), an
`engine` group for `DecodeConfig` and a `check` group: `prompt_tokens`
(one prompt or more in each prefill bucket, one just under and one just
over a multiple of `mamba_chunk_size`), `new_tokens` (greedy tokens
decoded through pages and state after each), `pad_min` (the least length a
sequence is padded to for the reference: the prompts share one compile),
and `beside` (the sampled requests that hold every other slot while the
check prompts are prefilled and decoded: how many, their prompt lengths in
turn, their `new_tokens`, which must outlast the check, and their
temperature). Every head of both kinds is held: a pipeline stage shares a
layer with no other chip. The published keys read are `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`intermediate_size`, `mamba_n_heads`, `mamba_d_head`, `mamba_d_ssm`,
`mamba_d_state`, `mamba_n_groups`, `mamba_d_conv`, `mamba_chunk_size`,
the multipliers (`embedding_multiplier`, `lm_head_multiplier`,
`attention_in_multiplier`, `attention_out_multiplier`, `key_multiplier`,
`ssm_in_multiplier`, `ssm_out_multiplier`, `ssm_multipliers`,
`mlp_multipliers`), `rms_norm_eps` and `rope_theta`; the switches
(`mamba_rms_norm`, `mamba_norm_before_gate`, `mamba_conv_bias`, the
`*_bias` keys, `tie_word_embeddings`, `rope_scaling`) are held to the
values the program implements, and any other refuses the configuration.

The check is the afmoe family's at the timed load, with this model's
reference and limits, and one thing more: prefill logits never see a state
that was carried through the cache, so each check request keeps its slot's
recurrent state as it stands after its decode (`keep_final_state`) and
that is held against the reference's state at that position.
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_falcon_h1, reference_falcon_h1
from benchmark.generators.requests import FIRST_TOKEN_ID

# the switches of the published config the program implements one value of
IMPLEMENTED = {"mamba_rms_norm": True, "mamba_norm_before_gate": False,
               "mamba_conv_bias": True, "mamba_proj_bias": False,
               "attention_bias": False, "mlp_bias": False,
               "projectors_bias": False, "tie_word_embeddings": False,
               "rope_scaling": None, "hidden_act": "silu"}


def model_config(config: dict):
    from paddle_tpu.models import falcon_h1

    for key, value in IMPLEMENTED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the falcon_h1 "
                             f"program implements {value!r}")
    if config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is not heads x head size")
    return falcon_h1.FalconH1Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        lm_head_multiplier=config["lm_head_multiplier"],
        attention_in_multiplier=config["attention_in_multiplier"],
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        ssm_multipliers=config["ssm_multipliers"],
        mlp_multipliers=config["mlp_multipliers"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_context"], dtype=config["dtype"],
        ssm_state_dtype=config["ssm_state_dtype"])


def reference_config(cfg) -> dict:
    """What reference_falcon_h1.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "n_layers", "num_heads", "num_kv_heads", "head_dim",
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "mamba_d_conv", "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
        "ssm_multipliers", "mlp_multipliers", "rms_norm_eps", "rope_theta")}


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call: drawn in float32 and rounded tensor by tensor (no
    float32 copy of the whole is held), each by the model's `seeded_value`
    (the scales of `init_scale`; A_log, dt_bias and D as the family's
    public initialisation draws them)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import falcon_h1

    specs = falcon_h1.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            k = jax.random.fold_in(key, j)
            out[name] = falcon_h1.seeded_value(
                cfg, name, specs[name],
                lambda s: jax.random.normal(k, s, jnp.float32),
                lambda s: jax.random.uniform(k, s, jnp.float32),
                xp=jnp).astype(specs[name][2])
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a mix or a check that do
    not fit. One class of pages: a slot needs ceil(max_context / page);
    the state class needs nothing said, it has a state for every slot."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"])
    slots_ = eng["max_slots"]
    per_slot = -(-config["max_context"] // eng["page_size"])
    if eng["kv_pages"] < slots_ * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {slots_} slots")
    check = config["check"]
    beside = check["beside"]
    longest = max(traffic["max_context"],
                  max(check["prompt_tokens"]) + check["new_tokens"],
                  max(beside["prompt_tokens"]) + beside["new_tokens"])
    if longest > config["max_context"]:
        raise ValueError(f"a context of {longest} tokens is over the "
                         f"configuration's max_context")
    if max(check["prompt_tokens"] + beside["prompt_tokens"]
           + [traffic["prompt_tokens"]["max"]]) \
            > max(eng["prefill_buckets"]):
        raise ValueError("a prompt is over the largest prefill bucket")
    if beside["requests"] + len(check["prompt_tokens"]) > slots_:
        raise ValueError(
            f"{beside['requests']} requests beside "
            f"{len(check['prompt_tokens'])} check prompts are more than the "
            f"{slots_} slots: the check prompts would wait for a slot")
    return eng


def make_engine(cfg, params, config: dict, traffic: dict):
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    return DecodeEngine(cfg, params,
                        DecodeConfig(**engine_config(config, traffic)))


def slots(config: dict) -> int:
    return config["engine"]["max_slots"]


def traffic_vocab(cfg, config: dict) -> int:
    """The ids the generator may draw: the held slice of the vocabulary."""
    return cfg.vocab_size


def pad_to(tokens: int, pad_min: int) -> int:
    return max(pad_min, -(-tokens // 512) * 512)


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD, as the afmoe
    family sends them: first `check["beside"]` fills the other slots with
    sampled requests of the traffic's kind and waits until each decodes;
    then the check prompts go in together, greedy, so that their prefills
    run between the others' steps (each writing its own slot's state while
    the neighbours' advance) and their tokens are chosen by steps of all
    the slots, each row on its own slot's state and its own page table.
    The others are still decoding when the last check prompt ends, or the
    check is void (`live`).
    -> ([(first_logits, chosen, state [layers, heads, head_dim, d_state])]
    a prompt, rows live beside the last)"""
    import numpy as np

    beside = check["beside"]
    lengths = beside["prompt_tokens"]
    others = [engine.submit(
        rng.randint(FIRST_TOKEN_ID, engine.model_cfg.vocab_size,
                    lengths[i % len(lengths)]),
        max_new_tokens=beside["new_tokens"], stop_at_eos=False,
        temperature=beside["temperature"], seed=int(rng.randint(2 ** 31)))
        for i in range(beside["requests"])]
    deadline = time.monotonic() + timeout
    while any(r.t_first is None and not r.done() for r in others):
        if time.monotonic() > deadline:
            raise TimeoutError("the requests beside the check never started")
        time.sleep(0.005)
    reqs = [engine.submit(sent, max_new_tokens=check["new_tokens"],
                          stop_at_eos=False, keep_first_logits=True,
                          keep_final_state=True)
            for sent in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    layers = engine.model_cfg.n_layers
    # the arrays hold a head as [d_state, head_dim]: the reference's order
    states = [np.swapaxes(np.stack([
        np.asarray(r.final_state[f"ssm_state_{i}"], np.float32)
        for i in range(layers)]), -1, -2) for r in reqs]
    return [(np.asarray(r.first_logits), c, s)
            for r, c, s in zip(reqs, chosen, states)], live


def judge_prompt(ref, sent, first_logits, chosen, state, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, or a lower-precision control of it): the prefill's logits
    row against the reference's at the prompt's last position
    (`logit_err`), each greedy token teacher-forced through the reference
    by the margin rule (`gap`, the worst), and every layer's recurrent
    state as the slot held it after the decode, against the reference's
    after the last token that was FED (the last chosen one never is):
    `state_err`, the worst layer's, and `state_err_first`, the first
    layer's, whose mixer reads the embedding itself: what is off there is
    the state's own arithmetic and nothing handed down from a layer below.
    -> dict(sent, logit_err, gap, state_err, state_err_first,
    state_err_by_layer, gaps)"""
    import numpy as np

    rf = reference_falcon_h1
    new = len(chosen)
    rows, states = ref.rows(np.concatenate([sent, chosen]),
                            pad_to(sent.size + new, pad_min),
                            sent.size - 1, new,
                            state_at=sent.size + new - 2)
    gaps = rf.greedy_gaps(rows, chosen)
    by_layer = rf.state_errors(state, states)
    return {"sent": int(sent.size),
            "logit_err": rf.logit_error(first_logits, rows[0]),
            "gap": float(gaps.max()),
            "state_err": max(by_layer), "state_err_first": by_layer[0],
            "state_err_by_layer": [round(e, 6) for e in by_layer],
            "gaps": [round(float(g), 5) for g in gaps]}


def judge(ref, sents, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_falcon_h1."""
    rf = reference_falcon_h1
    compared, notes, detail = [], [], {}
    gap = 0.0
    for n, sent, (first_logits, chosen, state) in zip(
            check["prompt_tokens"], sents, outs):
        got = detail[str(n)] = judge_prompt(ref, sent, first_logits, chosen,
                                            state, check["pad_min"])
        compared += [[f"prefill_logit_err_p{n}", got["logit_err"],
                      rf.LOGIT_ERR],
                     [f"state_err_p{n}", got["state_err"], rf.STATE_ERR],
                     [f"state_err_first_p{n}", got["state_err_first"],
                      rf.STATE_ERR_FIRST]]
        if got["logit_err"] > rf.LOGIT_ERR:
            notes.append(
                f"prefill logits of a {got['sent']}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {rf.LOGIT_ERR})")
        if got["state_err"] > rf.STATE_ERR:
            notes.append(
                f"a layer's recurrent state after a {got['sent']}-token "
                f"prompt and its decode is {got['state_err']:.4f} of its "
                f"norm off the reference's (limit {rf.STATE_ERR})")
        if got["state_err_first"] > rf.STATE_ERR_FIRST:
            notes.append(
                f"the first layer's recurrent state after a "
                f"{got['sent']}-token prompt and its decode is "
                f"{got['state_err_first']:.4f} of its norm off the "
                f"reference's (limit {rf.STATE_ERR_FIRST})")
        gap = max(gap, got["gap"])
    compared += [["greedy_logit_gap", gap, rf.MARGIN],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if gap > rf.MARGIN:
        notes.append(f"a greedy token lies {gap:.4f} under the reference's "
                     f"maximum logit (margin {rf.MARGIN})")
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {"prompts": detail}


def check_prompts(cfg, check: dict, rng):
    return [rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n).astype("int32")
            for n in check["prompt_tokens"]]


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """The check prompts through the engine it is handed, with every other
    slot live (`engine_outputs`), held against the reference by `judge`; on
    the chip neither the state update nor the paged attention may have
    taken its stock lowering.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_falcon_h1.Reference(params, reference_config(cfg))
    sents = check_prompts(cfg, check, rng)
    outs, live = engine_outputs(engine, sents, check, rng)
    compared, notes, detail = judge(ref, sents, outs, live, check)
    if jax.default_backend() == "tpu":
        for name, what in (
                ("ssm_state_update_fallbacks", "state updates"),
                ("paged_attn_fallbacks", "paged attention ops")):
            fell = int(telemetry.counter_get("pallas." + name))
            compared.append([name, fell, 0])
            if fell:
                notes.append(f"{fell} {what} took the stock lowering")
    # the reference goes NOW, inside set-up (families/kimi_k2.py)
    t0 = time.perf_counter()
    del ref
    gc.collect()
    detail["teardown_s"] = round(time.perf_counter() - t0, 3)
    return compared, notes, detail


def step_bytes(cfg, config: dict, live_context_tokens: float,
               telemetry: dict) -> float:
    """Least bytes a decode step moves, from the window's counters: the
    weights once, the keys attended and the states of the live rows, read
    and written."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_falcon_h1.step_bytes(
        config,
        kv_tokens=c.get("decode.kv_tokens_attended", 0) / steps,
        state_rows=c.get("decode.state_rows_updated", 0) / steps,
        rows=c.get("decode.tokens", 0) / steps)
