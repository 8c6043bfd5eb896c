"""The `qwen3_next` family: paddle_tpu/models/qwen3_next.py behind
`DecodeEngine`, held against `benchmark/reference_qwen3_next.py` by logits
AND by the DeltaNet layers' matrix states themselves, and counted by
`benchmark/flops_qwen3_next.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole and unchanged): `num_hidden_layers` (the layers held:
the first that many of the published pattern, whole periods of
`full_attention_interval`), `q_heads_held` / `kv_heads_held` (attention
heads held of `num_attention_heads` / `num_key_value_heads`),
`linear_key_heads_held` / `linear_value_heads_held` (DeltaNet heads held of
`linear_num_key_heads` / `linear_num_value_heads`: whole key heads with
their value heads), `experts_held` ([first, how many]; the router keeps
`num_experts`), `vocab_size` (rows of embedding and head held; traffic ids,
logits and sampling are over them), `max_context`, `dtype` (weights, K/V
pages, the conv tail), `linear_state_dtype` (the matrix state's: float32, a
key of the configuration and not a knob), `linear_chunk_size` (the
prefill's chunk), `kv_pages` (context pages of the attention layers, one
class), an `engine` group for `DecodeConfig` and a `check` group:
`prompt_tokens` (one prompt or more in each prefill bucket, one just under
and one just over a multiple of the chunk), `new_tokens` (greedy tokens
decoded through pages and state after each), `pad_min` (the least length a
sequence is padded to for the reference; above it lengths are padded to the
next power of two, so that the prompts share few compiles), and `beside`
(the sampled requests that hold every other slot while the check prompts
are prefilled and decoded: how many, their prompt lengths in turn, their
`new_tokens`, which must outlast the check, and their temperature). The
published keys read are `hidden_size`, `head_dim`, `partial_rotary_factor`,
`full_attention_interval`, `linear_key_head_dim`, `linear_value_head_dim`,
`linear_conv_kernel_dim`, `moe_intermediate_size`,
`shared_expert_intermediate_size`, `num_experts`, `num_experts_per_tok`,
`norm_topk_prob`, `rms_norm_eps` and `rope_theta`; the switches
(`decoder_sparse_step`, `mlp_only_layers`, `hidden_act`, `rope_scaling`,
`tie_word_embeddings`, `use_sliding_window`) are held to the values the
program implements, and any other refuses the configuration.

The check is the falcon_h1 family's at the timed load, with this model's
reference and limits: every other slot held by sampled requests, the check
prompts' prefill logits and greedy tokens against the reference's one full
forward, and each check request's DeltaNet states as its slot holds them
after its decode (`keep_final_state`) against the reference's at that
position, and the K and V its pages hold in the first attention layer
(`keep_final_pages`) against the reference's. There is no cut to a position of decided routing
(reference_qwen3_next's docstring says why).
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_qwen3_next, reference_qwen3_next
from benchmark.generators.requests import FIRST_TOKEN_ID

# the switches of the published config the program implements one value of
IMPLEMENTED = {"decoder_sparse_step": 1, "mlp_only_layers": [],
               "hidden_act": "silu", "rope_scaling": None,
               "tie_word_embeddings": False, "use_sliding_window": False}


def model_config(config: dict):
    from paddle_tpu.models import qwen3_next

    for key, value in IMPLEMENTED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the qwen3_next "
                             f"program implements {value!r}")
    if config["num_hidden_layers"] % config["full_attention_interval"]:
        raise ValueError("the layers held are whole periods of the pattern")
    return qwen3_next.Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        head_dim=config["head_dim"], num_heads=config["q_heads_held"],
        num_kv_heads=config["kv_heads_held"],
        partial_rotary_factor=config["partial_rotary_factor"],
        linear_key_heads=config["linear_key_heads_held"],
        linear_value_heads=config["linear_value_heads_held"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_chunk_size=config["linear_chunk_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["experts_held"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_context"], dtype=config["dtype"],
        linear_state_dtype=config["linear_state_dtype"])


def reference_config(cfg) -> dict:
    """What reference_qwen3_next.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "n_layers", "full_attention_interval", "head_dim", "num_heads",
        "num_kv_heads", "partial_rotary_factor", "linear_key_heads",
        "linear_value_heads", "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
        "experts_held", "norm_topk_prob", "rms_norm_eps", "rope_theta")}


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call: drawn in float32 and rounded tensor by tensor (no
    float32 copy of the whole is held), each by the model's `seeded_value`
    (matrices normal at fan_in^-0.5, the router's at ROUTER_GAIN times
    that; A_log and dt_bias on a grid over the linear-attention family's
    public ranges; the gains at the constants `param_specs` gives them)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import qwen3_next

    specs = qwen3_next.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            k = jax.random.fold_in(key, j)
            out[name] = qwen3_next.seeded_value(
                name, specs[name],
                lambda s: jax.random.normal(k, s, jnp.float32),
                xp=jnp).astype(specs[name][2])
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))

def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a mix or a check that do
    not fit. One class of pages: a slot needs ceil(max_context / page);
    the state class needs nothing said, it has a state for every slot
    (and the DeltaNet layers have no pages at all)."""
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
    """The reference's padded length: `pad_min`, or the next power of two
    (the prefill buckets' own ladder: few lengths, few compiles)."""
    n = pad_min
    while n < tokens:
        n *= 2
    return n


def state_layers(cfg):
    return [i for i in range(cfg.n_layers) if not cfg.is_attention(i)]


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD, as the
    falcon_h1 family sends them: first `check["beside"]` fills the other
    slots with sampled requests of the traffic's kind and waits until each
    decodes; then the check prompts go in together, greedy, so that their
    prefills run between the others' steps (each writing its own slot's
    states while the neighbours' advance) and their tokens are chosen by
    steps of all the slots, each row on its own slot's states and its own
    page table. The others are still decoding when the last check prompt
    ends, or the check is void (`live`).
    -> ([(first_logits, chosen, (states [state layers, value heads, dk,
    dv], the first attention layer's pages [tokens, 2, width]))] a prompt,
    rows live beside the last)"""
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
                          keep_final_state=True, keep_final_pages=True)
            for sent in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    cfg = engine.model_cfg
    # the arrays hold a head as [dk, dv]: the reference's order
    states = [np.stack([np.asarray(r.final_state[f"ssm_state_{i}"],
                                   np.float32) for i in state_layers(cfg)])
              for r in reqs]
    first = next(i for i in range(cfg.n_layers) if cfg.is_attention(i))
    # [pages, page, width] -> [tokens, 2, width]: K, then V, of the first
    # attention layer as the request's pages held them
    pages = [np.stack([np.asarray(r.final_pages[f"kv_{part}_{first}"],
                                  np.float32).reshape(
        -1, cfg.num_kv_heads * cfg.head_dim) for part in "kv"], axis=1)
        for r in reqs]
    return [(np.asarray(r.first_logits), c, (s, kv))
            for r, c, s, kv in zip(reqs, chosen, states, pages)], live


def judge_prompt(ref, sent, first_logits, chosen, kept, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, or a lower-precision control of it): the prefill's logits
    row against the reference's at the prompt's last position
    (`logit_err`), each greedy token teacher-forced through the reference
    by the margin rule (`gap`, the worst), and every DeltaNet layer's
    matrix state as the slot held it after the decode, against the
    reference's after the last token that was FED (the last chosen one
    never is): `state_err`, the worst layer's, and `state_err_first`, the
    first layer's, whose mixer reads the embedding itself: what is off
    there is the state's own arithmetic and nothing handed down from a
    layer below (no routed layer, no attention); and the K and V the
    request's pages hold in the FIRST attention layer against the
    reference's, the median over the positions that were fed (`kv_err`:
    logits and states read two attention layers of eight through six
    layers' noise, the pages themselves do not).
    -> dict(sent, logit_err, gap, state_err, state_err_first,
    state_err_by_layer, kv_err, gaps)"""
    import numpy as np

    rq = reference_qwen3_next
    new = len(chosen)
    state, pages = kept
    fed = sent.size + new - 1
    rows, states, kv = ref.rows(np.concatenate([sent, chosen]),
                                pad_to(sent.size + new, pad_min),
                                sent.size - 1, new, state_at=fed - 1)
    gaps = rq.greedy_gaps(rows, chosen)
    by_layer = rq.state_errors(state, states)
    return {"sent": int(sent.size),
            "logit_err": rq.logit_error(first_logits, rows[0]),
            "gap": float(gaps.max()),
            "state_err": max(by_layer), "state_err_first": by_layer[0],
            "state_err_by_layer": [round(e, 6) for e in by_layer],
            "kv_err": rq.kv_error(pages[:fed], kv[0][:fed]),
            "gaps": [round(float(g), 5) for g in gaps]}

def judge(ref, sents, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_qwen3_next."""
    rq = reference_qwen3_next
    compared, notes, detail = [], [], {}
    gap = 0.0
    for n, sent, (first_logits, chosen, kept) in zip(
            check["prompt_tokens"], sents, outs):
        got = detail[str(n)] = judge_prompt(ref, sent, first_logits, chosen,
                                            kept, check["pad_min"])
        compared += [[f"prefill_logit_err_p{n}", got["logit_err"],
                      rq.LOGIT_ERR],
                     [f"state_err_p{n}", got["state_err"], rq.STATE_ERR],
                     [f"state_err_first_p{n}", got["state_err_first"],
                      rq.STATE_ERR_FIRST],
                     [f"kv_err_p{n}", got["kv_err"], rq.KV_ERR]]
        if got["logit_err"] > rq.LOGIT_ERR:
            notes.append(
                f"prefill logits of a {got['sent']}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {rq.LOGIT_ERR})")
        if got["state_err"] > rq.STATE_ERR:
            notes.append(
                f"a layer's matrix state after a {got['sent']}-token "
                f"prompt and its decode is {got['state_err']:.4f} of its "
                f"norm off the reference's (limit {rq.STATE_ERR})")
        if got["state_err_first"] > rq.STATE_ERR_FIRST:
            notes.append(
                f"the first layer's matrix state after a "
                f"{got['sent']}-token prompt and its decode is "
                f"{got['state_err_first']:.4f} of its norm off the "
                f"reference's (limit {rq.STATE_ERR_FIRST})")
        if got["kv_err"] > rq.KV_ERR:
            notes.append(
                f"the first attention layer's pages of a {got['sent']}-token "
                f"prompt and its decode are {got['kv_err']:.4f} of a "
                f"position's K and V off the reference's at the median "
                f"position (limit {rq.KV_ERR})")
        gap = max(gap, got["gap"])
    compared += [["greedy_logit_gap", gap, rq.MARGIN],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if gap > rq.MARGIN:
        notes.append(f"a greedy token lies {gap:.4f} under the reference's "
                     f"maximum logit (margin {rq.MARGIN})")
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
    the chip neither the state update, the grouped expert product nor the
    paged attention may have taken its stock lowering.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_qwen3_next.Reference(params, reference_config(cfg))
    sents = check_prompts(cfg, check, rng)
    outs, live = engine_outputs(engine, sents, check, rng)
    compared, notes, detail = judge(ref, sents, outs, live, check)
    if jax.default_backend() == "tpu":
        for name, what in (
                ("gated_delta_state_update_fallbacks", "state updates"),
                ("grouped_swiglu_fallbacks", "grouped expert products"),
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
    non-expert weights once, each held expert that was hit, the keys
    attended and the states of the live rows, read and written."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_qwen3_next.step_bytes(
        config,
        experts_hit=c.get("decode.moe_experts_hit", 0) / steps,
        kv_tokens=c.get("decode.kv_tokens_attended", 0) / steps,
        state_rows=c.get("decode.state_rows_updated", 0) / steps,
        rows=c.get("decode.tokens", 0) / steps)
