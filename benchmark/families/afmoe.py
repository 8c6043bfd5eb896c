"""The `afmoe` family: paddle_tpu/models/afmoe.py behind `DecodeEngine`,
held against `benchmark/reference_afmoe.py` by logits, and counted by
`benchmark/flops_afmoe.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole): `layers_held` (indices into the published
`layer_types`: the leading `num_dense_layers` of them are dense),
`q_heads_held`, `kv_heads_held`, `experts_held` ([first, how many]; the
router keeps `num_experts`), `vocab_size` (rows of embedding and head
held; traffic ids, logits and sampling are over them), `max_context`,
`dtype`, `kv_pages` / `kv_ring_pages` (the two classes of pages), an
`engine` group for `DecodeConfig` and a `check` group: `prompt_tokens`
(one prompt of about each length; at least one past the window),
`new_tokens` (greedy tokens decoded through the cache after each, and how
far back from its end a prompt may be cut to a position whose routing is
decided: the reference then gives the same number of rows in both its
passes and compiles once a padded length), `pad_min` (the least length a
sequence is padded to for the reference: short prompts share one compile),
and `beside` (the sampled requests that hold every other slot while the
check prompts are prefilled and decoded: how many, their prompt lengths in
turn, their `new_tokens`, which must outlast the check, and their
temperature). The seeded post-norm gains are this file's (POST_NORM_GAIN).

What the seven-function contract left unsaid, found here: `model_config`
is the first thing a run calls, so on a program without this model it is
where the run ends (ImportError, exit 1, before any weight is made);
`check_correct` may use `engine` and never `url`; `make_params` must not
hold a float32 copy of the weights (one jitted call, cast inside it).
"""

from __future__ import annotations

from benchmark import flops_afmoe, reference_afmoe
from benchmark.generators.requests import FIRST_TOKEN_ID


def model_config(config: dict):
    from paddle_tpu.models import afmoe

    return afmoe.AfmoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        head_dim=config["head_dim"], num_heads=config["q_heads_held"],
        num_kv_heads=config["kv_heads_held"],
        layer_types=[config["layer_types"][i]
                     for i in config["layers_held"]],
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["experts_held"],
        route_scale=config["route_scale"], route_norm=config["route_norm"],
        sliding_window=config["sliding_window"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"], max_seq_len=config["max_context"],
        dtype=config["dtype"])


def reference_config(cfg) -> dict:
    """What reference_afmoe.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "hidden_size", "head_dim", "num_heads", "num_kv_heads",
        "layer_types", "num_dense_layers", "num_experts",
        "num_experts_per_tok", "experts_held", "route_scale", "route_norm",
        "sliding_window", "rms_norm_eps", "rope_theta")}


# What seeded weights give the gains of the two post-norms of a block. The
# family's sandwich norm is depth-scaled (the catalog row's words; the rule
# is not in config.json, so `assumed`): (2 x 60 published layers)^-0.5, a
# block is a small step off the residual stream. At 1 every layer adds a
# unit vector, attention over seeded weights is an average over the context
# and the same for every token of a sequence, and the router then sends a
# whole sequence to the same few experts (PERF.md, PR 28). A checkpoint
# brings its own gains, so this is the benchmark's and not the model's.
POST_NORM_GAIN = 0.0913
POST_NORMS = ("norm_post_attn", "norm_post_mlp")


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device
    in one jitted call: normal with std fan_in^-0.5 drawn in float32 and
    rounded tensor by tensor (no float32 copy of the whole is held); the
    post-norm gains at POST_NORM_GAIN, the other gains and the selection
    bias at the constants the model's `param_specs` gives them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import afmoe

    specs = afmoe.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind, dtype = specs[name]
            if kind == "normal":
                out[name] = (afmoe.fan_in(name, shape) ** -0.5
                             * jax.random.normal(jax.random.fold_in(key, j),
                                                 shape, jnp.float32)
                             ).astype(dtype)
            else:
                out[name] = jnp.full(
                    shape, POST_NORM_GAIN if name.endswith(POST_NORMS)
                    else kind, dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses pools, a mix or a check that do
    not fit. Two classes of pages: a slot needs ceil(max_context / page)
    context pages and a ring of window / page + 1."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"],
               kv_ring_pages=config["kv_ring_pages"])
    page, slots_ = eng["page_size"], eng["max_slots"]
    per_slot = -(-config["max_context"] // page)
    ring_slot = -(-config["sliding_window"] // page) + 1
    if eng["kv_pages"] < slots_ * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {slots_} slots")
    if eng["kv_ring_pages"] < slots_ * min(per_slot, ring_slot) + 1:
        raise ValueError(
            f"kv_ring_pages {eng['kv_ring_pages']} hold no ring of "
            f"{ring_slot} pages for each of {slots_} slots")
    check = config["check"]
    beside = check["beside"]
    longest = max(traffic["max_context"],
                  max(check["prompt_tokens"]) + check["new_tokens"],
                  max(beside["prompt_tokens"]) + beside["new_tokens"])
    if longest > config["max_context"]:
        raise ValueError(f"a context of {longest} tokens is over the "
                         f"configuration's max_context")
    if max(check["prompt_tokens"] + beside["prompt_tokens"]) \
            > max(eng["prefill_buckets"]):
        raise ValueError("a check prompt is over the largest prefill bucket")
    if beside["requests"] + len(check["prompt_tokens"]) > slots_:
        raise ValueError(
            f"{beside['requests']} requests beside {len(check['prompt_tokens'])}"
            f" check prompts are more than the {slots_} slots: the check "
            f"prompts would wait for a slot, not decode beside them")
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


def cut_prompt(ref, prompt, new_tokens: int, pad_min: int):
    """The prompt as it is sent: cut, within its last `new_tokens`
    positions, to end on the last whose routing the reference decides
    (reference_afmoe.ROUTE_EPS), so that the prefill's logits row is
    compared at a position where a top-k does not turn on rounding."""
    import numpy as np

    prompt = np.asarray(prompt, np.int32)
    tail = min(new_tokens, prompt.size)
    _, route_gap = ref.rows(prompt, pad_to(prompt.size, new_tokens, pad_min),
                            prompt.size - tail, new_tokens)
    keep = reference_afmoe.decided_prefix(route_gap[prompt.size - tail:])
    if not keep:
        raise ValueError(f"no position of the prompt's last {tail} routes "
                         f"by more than {reference_afmoe.ROUTE_EPS}")
    return prompt[:prompt.size - tail + keep]


def pad_to(tokens: int, new_tokens: int, pad_min: int) -> int:
    return max(pad_min, -(-(tokens + new_tokens) // 512) * 512)


def judge_prompt(ref, sent, first_logits, chosen, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, or a lower-precision control of it): the prefill's logits
    row against the reference's row at the prompt's last position
    (`logit_err`), and each greedy token teacher-forced through the
    reference by the margin rule: `gap` is the worst at the positions
    whose routing the reference decides, `undecided_gap` the worst at the
    others (`undecided` of them), which are held to a wider margin and
    not left out.
    -> dict(sent, logit_err, gap, undecided, undecided_gap, gaps)"""
    import numpy as np

    new = len(chosen)
    rows, route_gap = ref.rows(np.concatenate([sent, chosen]),
                               pad_to(sent.size, new, pad_min),
                               sent.size - 1, new)
    decided = route_gap[sent.size - 1:sent.size - 1 + new] \
        > reference_afmoe.ROUTE_EPS
    gaps = reference_afmoe.greedy_gaps(rows, chosen)
    return {"sent": int(sent.size),
            "logit_err": reference_afmoe.logit_error(first_logits, rows[0]),
            "gap": float(gaps[decided].max()) if decided.any() else 0.0,
            "undecided": int((~decided).sum()),
            "undecided_gap": float(gaps[~decided].max())
            if (~decided).any() else 0.0,
            "gaps": [round(float(g), 5) for g in gaps]}


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD: first
    `check["beside"]` fills the other slots with sampled requests of the
    traffic's kind (some past the window) and waits until each decodes;
    then the check prompts go in together, greedy, so that their prefills
    run between the others' steps and their tokens are chosen by a step
    of all the slots: the sort of a full batch's pairs by expert, `live`
    among full slots, each slot's own page and ring tables. The others
    are still decoding when the last check prompt ends, or the check is
    void (`live`).
    -> ([(first_logits, chosen)] a prompt, rows live beside the last)"""
    import time

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
                          stop_at_eos=False, keep_first_logits=True)
            for sent in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    return [(np.asarray(r.first_logits), c)
            for r, c in zip(reqs, chosen)], live


def judge(ref, sents, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits
    of reference_afmoe."""
    ra = reference_afmoe
    compared, notes, detail = [], [], {}
    gap = undecided_gap = 0.0
    undecided = positions = 0
    for n, sent, (first_logits, chosen) in zip(check["prompt_tokens"], sents,
                                               outs):
        got = detail[str(n)] = judge_prompt(ref, sent, first_logits, chosen,
                                            check["pad_min"])
        compared.append([f"prefill_logit_err_p{n}", got["logit_err"],
                         ra.LOGIT_ERR])
        if got["logit_err"] > ra.LOGIT_ERR:
            notes.append(
                f"prefill logits of a {got['sent']}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {ra.LOGIT_ERR})")
        gap = max(gap, got["gap"])
        undecided_gap = max(undecided_gap, got["undecided_gap"])
        undecided += got["undecided"]
        positions += len(chosen)
    compared += [["greedy_logit_gap", gap, ra.MARGIN],
                 ["greedy_logit_gap_undecided", undecided_gap,
                  ra.UNDECIDED_MARGIN],
                 ["undecided_positions", undecided,
                  int(ra.UNDECIDED_SHARE * positions)],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if gap > ra.MARGIN:
        notes.append(f"a greedy token lies {gap:.4f} under the reference's "
                     f"maximum logit (margin {ra.MARGIN})")
    if undecided_gap > ra.UNDECIDED_MARGIN:
        notes.append(
            f"a greedy token at a position of undecided routing lies "
            f"{undecided_gap:.4f} under the reference's maximum logit "
            f"(margin {ra.UNDECIDED_MARGIN})")
    if undecided > ra.UNDECIDED_SHARE * positions:
        notes.append(f"{undecided} of {positions} decoded positions route "
                     f"by less than {ra.ROUTE_EPS}: too few are held to "
                     f"the margin {ra.MARGIN}")
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {"prompts": detail}


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """The check prompts through the engine it is handed (logits are
    compared, and only the engine gives them out), with every other slot
    live (`engine_outputs`), held against the reference by `judge`.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_afmoe.Reference(params, reference_config(cfg))
    sents = [cut_prompt(ref, rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n),
                        check["new_tokens"], check["pad_min"])
             for n in check["prompt_tokens"]]
    outs, live = engine_outputs(engine, sents, check, rng)
    compared, notes, detail = judge(ref, sents, outs, live, check)
    if jax.default_backend() == "tpu":
        # on the chip the decode step runs the kernel, never the gather
        fell = int(telemetry.counter_get("pallas.paged_attn_fallbacks"))
        compared.append(["paged_attn_fallbacks", fell, 0])
        if fell:
            notes.append(f"{fell} attention ops took the stock gather")
    return compared, notes, detail


def step_bytes(cfg, config: dict, live_context_tokens: float,
               telemetry: dict) -> float:
    """Least bytes a decode step reads, from the window's counters: the
    routed experts that were HIT and the keys that were attended a step
    (a ring layer reads its window, not the context), not shapes alone."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_afmoe.step_bytes(
        config,
        experts_hit=c.get("decode.moe_experts_hit", 0) / steps,
        kv_tokens=c.get("decode.kv_tokens_attended", 0) / steps,
        rows=c.get("decode.tokens", 0) / steps)
