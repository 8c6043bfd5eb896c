"""The `motif3` family: paddle_tpu/models/motif3.py behind `DecodeEngine`,
held against `benchmark/reference_motif3.py` by logits and by the latent
rows its ring and its pages hold, and counted by
`benchmark/flops_motif3.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole under their own names): `layers_held` (PUBLISHED layer
indices: an index under `n_dense_first_layers` is a dense layer, one with
``(i + 1) % sliding_window_period == 0`` a full layer on latent pages, any
other a window layer on a latent ring), `experts_held` ([first, how many];
the router keeps `num_experts`), `vocab_size` (rows of embedding and head
held; traffic ids, logits and sampling are over them), `max_context`,
`dtype`, `kv_pages` (the full layers' latent pages), `kv_ring_pages` (the
window layers' latent rings: `sliding_window` / page + 1 pages a slot, and
the scratch page), `num_dense_layers` (the harness's key: the leading dense
layers held), an `engine` group for `DecodeConfig` and a `check` group as
the kimi_k2 family's (`prompt_tokens`, each prompt in a prefill bucket of
its own: five of the configuration's eight, its three halfway buckets among
them;
`new_tokens`; `pad_min`; `beside`). Published keys it reads:
`hidden_size`, `num_attention_heads`, `num_key_value_heads` (= groups =
noise heads), `head_dim` (nope = head_dim - qk_rope_head_dim),
`qk_rope_head_dim`, `v_head_dim`, `q_lora_rank`, `kv_lora_rank`,
`n_dense_first_layers`, `sliding_window`, `sliding_window_period`,
`intermediate_size`, `moe_intermediate_size`, `num_shared_experts`,
`num_experts`, `experts_top_k`, `route_scale`, `route_norm`,
`mhc_expansion_rate`, `mhc_sinkhorn_iters`, `hidden_clamp`,
`polynorm_output_scale`, `polynorm_bias_clamp`, `rms_norm_eps`,
`rope_theta`. Every head is held: the deployment's attention is
data-parallel.

The check is the kimi_k2 family's (the prompt cut to a position of decided
routing, the prefill's logits row, the greedy margin rule with the
undecided positions held to a wider margin), with every other slot live,
and beside it the LATENT ROWS the request's pages and its ring hold when it
retires (`keep_final_pages`), each against the reference's `[c, k_r]` of
that position and layer: the full layer's pages over every position fed,
each window layer's ring over the last `sliding_window` positions fed, all
of them past the window (a ring that wrapped wrongly, a table of another
slot or a row written at the wrong index shows there and nowhere else as
plainly).
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_motif3, reference_motif3
from benchmark.families.afmoe import pad_to  # noqa: F401
from benchmark.generators.requests import FIRST_TOKEN_ID


def model_config(config: dict):
    from paddle_tpu.models import motif3

    return motif3.Motif3Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["head_dim"] - config["qk_rope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        layer_ids=config["layers_held"],
        n_dense_first_layers=config["n_dense_first_layers"],
        sliding_window=config["sliding_window"],
        sliding_window_period=config["sliding_window_period"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["experts_top_k"],
        experts_held=config["experts_held"],
        route_scale=config["route_scale"], route_norm=config["route_norm"],
        n_streams=config["mhc_expansion_rate"],
        mhc_sinkhorn_iters=config["mhc_sinkhorn_iters"],
        hidden_clamp=config["hidden_clamp"],
        polynorm_output_scale=config["polynorm_output_scale"],
        polynorm_bias_clamp=config["polynorm_bias_clamp"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_seq_len=config["max_context"], dtype=config["dtype"])


def reference_config(cfg) -> dict:
    """What reference_motif3.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "num_kv_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "n_layers", "layer_ids", "n_dense_first_layers",
        "sliding_window", "sliding_window_period", "num_experts_per_tok",
        "experts_held", "route_scale", "route_norm", "n_streams",
        "mhc_sinkhorn_iters", "hidden_clamp", "polynorm_output_scale",
        "polynorm_bias_clamp", "rms_norm_eps", "rope_theta", "dtype")}


# The embedding has unit elements, as families/kimi_k2.py EMBED_STD says
# and why: the streams start as four copies of it and every sublayer adds
# at unit scale.
EMBED_STD = 1.0


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call: `param_specs`' kinds (``normal`` at the model's
    `init_std`, the embedding at EMBED_STD; a (mean, std) draw; a
    constant), drawn in float32 and rounded tensor by tensor."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import motif3

    specs = motif3.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind, dtype = specs[name]
            if kind == "normal" or isinstance(kind, tuple):
                mean, std = kind if isinstance(kind, tuple) else (
                    0.0, EMBED_STD if name == "m3_tok_emb"
                    else motif3.init_std(name, shape))
                out[name] = (mean + std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
                    ).astype(dtype)
            else:
                out[name] = jnp.full(shape, kind, dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a ring pool, a mix or a
    check that do not fit. A slot needs ceil(max_context / page) context
    pages and a ring of sliding_window / page + 1."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"],
               kv_ring_pages=config["kv_ring_pages"])
    slots_ = eng["max_slots"]
    per_slot = -(-config["max_context"] // eng["page_size"])
    if eng["kv_pages"] < slots_ * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {slots_} slots")
    ring = -(-config["sliding_window"] // eng["page_size"]) + 1
    if eng["kv_ring_pages"] < slots_ * ring + 1:
        raise ValueError(
            f"kv_ring_pages {eng['kv_ring_pages']} hold no ring of {ring} "
            f"pages for each of {slots_} slots")
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
    if min(check["prompt_tokens"]) <= config["sliding_window"]:
        raise ValueError("a check prompt is no longer than the window: its "
                         "ring would not be compared past it")
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


def cut_prompt(ref, prompt, new_tokens: int, pad_min: int):
    """The prompt as it is sent: cut, within its last `new_tokens`
    positions, to end on the last whose routing the reference decides
    (families/kimi_k2.py)."""
    import numpy as np

    rm = reference_motif3
    prompt = np.asarray(prompt, np.int32)
    tail = min(new_tokens, prompt.size)
    _, route_gap, _ = ref.rows(
        prompt, pad_to(prompt.size, new_tokens, pad_min),
        prompt.size - tail, new_tokens)
    keep = rm.decided_prefix(route_gap[prompt.size - tail:], rm.ROUTE_EPS)
    if not keep:
        raise ValueError(f"no position of the prompt's last {tail} routes "
                         f"by more than {rm.ROUTE_EPS}")
    return prompt[:prompt.size - tail + keep]


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD
    (families/afmoe.py `engine_outputs`: `check["beside"]` fills the other
    slots first, then the check prompts go in together, greedy), each
    keeping its prefill's logits row and, when it retires, its own pages
    and ring.
    -> ([(first_logits, chosen, {layer: rows})] a prompt, rows live beside
    the last); a layer's rows are float32 [tokens, row width]: a full
    layer's in position order, a window layer's in its ring's order."""
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
                          keep_final_pages=True) for sent in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    cfg = engine.model_cfg
    kept = [{i: np.asarray(r.final_pages[f"kv_c_{i}"], np.float32).reshape(
        -1, cfg.latent_row_width) for i in range(cfg.n_layers)}
        for r in reqs]
    return [(np.asarray(r.first_logits), c, k)
            for r, c, k in zip(reqs, chosen, kept)], live


def latent_errors(cfg_ref: dict, kept: dict, latents, fed: int):
    """(worst window layer's, worst full layer's) `latent_error` of the
    rows a request's ring and pages held after `fed` tokens were fed,
    against the reference's `latents` [layers, T, rank + rope]: a ring
    over the last `sliding_window` positions (position t at index t mod
    the ring's rows), pages over every position."""
    import numpy as np

    rm = reference_motif3
    ring = pages = 0.0
    for layer, rows in kept.items():
        window = rm.window_of(cfg_ref, layer)
        if window:
            at = np.arange(max(0, fed - window), fed)
            err = rm.latent_error(rows[at % rows.shape[0]],
                                  latents[layer][at])
            ring = max(ring, err)
        else:
            pages = max(pages, rm.latent_error(rows[:fed],
                                               latents[layer][:fed]))
    return ring, pages


def judge_prompt(ref, sent, first_logits, chosen, kept, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, or a control of it): the prefill's logits row
    (`logit_err`), each greedy token teacher-forced through the reference
    by the margin rule (`gap` at positions of decided routing,
    `undecided_gap` at the others), and the latent rows the request's
    rings (`latent_err_ring`) and pages (`latent_err_pages`) held when it
    retired, after its last FED token (the last chosen one never is).
    -> dict(sent, logit_err, gap, undecided, undecided_gap,
    latent_err_ring, latent_err_pages, gaps)"""
    import numpy as np

    rm = reference_motif3
    new = len(chosen)
    rows, route_gap, latents = ref.rows(
        np.concatenate([sent, chosen]), pad_to(sent.size, new, pad_min),
        sent.size - 1, new)
    decided = route_gap[sent.size - 1:sent.size - 1 + new] > rm.ROUTE_EPS
    gaps = rm.greedy_gaps(rows, chosen)
    ring, pages = latent_errors(ref.cfg, kept, latents, sent.size + new - 1)
    return {"sent": int(sent.size),
            "logit_err": rm.logit_error(first_logits, rows[0]),
            "gap": float(gaps[decided].max()) if decided.any() else 0.0,
            "undecided": int((~decided).sum()),
            "undecided_gap": float(gaps[~decided].max())
            if (~decided).any() else 0.0,
            "latent_err_ring": ring, "latent_err_pages": pages,
            "gaps": [round(float(g), 5) for g in gaps]}


def judge(ref, sents, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_motif3 for the configuration's dtype."""
    rm = reference_motif3
    lim = rm.limits(ref.cfg["dtype"])
    compared, notes, detail = [], [], {}
    gap = undecided_gap = 0.0
    undecided = positions = 0
    for n, sent, (first_logits, chosen, kept) in zip(
            check["prompt_tokens"], sents, outs):
        got = detail[str(n)] = judge_prompt(ref, sent, first_logits, chosen,
                                            kept, check["pad_min"])
        compared += [
            [f"prefill_logit_err_p{n}", got["logit_err"], lim["LOGIT_ERR"]],
            [f"latent_err_ring_p{n}", got["latent_err_ring"], lim["LATENT_ERR"]],
            [f"latent_err_pages_p{n}", got["latent_err_pages"],
             lim["LATENT_ERR"]]]
        if got["logit_err"] > lim["LOGIT_ERR"]:
            notes.append(
                f"prefill logits of a {got['sent']}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {lim['LOGIT_ERR']})")
        for what, key in (("ring", "latent_err_ring"),
                          ("pages", "latent_err_pages")):
            if got[key] > lim["LATENT_ERR"]:
                notes.append(
                    f"the latent rows a {got['sent']}-token prompt's {what} "
                    f"held are {got[key]:.4f} of a row off the reference's "
                    f"at the median row (limit {lim['LATENT_ERR']})")
        gap = max(gap, got["gap"])
        undecided_gap = max(undecided_gap, got["undecided_gap"])
        undecided += got["undecided"]
        positions += len(chosen)
    compared += [["greedy_logit_gap", gap, lim["MARGIN"]],
                 ["greedy_logit_gap_undecided", undecided_gap,
                  lim["UNDECIDED_MARGIN"]],
                 ["undecided_positions", undecided,
                  int(rm.UNDECIDED_SHARE * positions)],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if gap > lim["MARGIN"]:
        notes.append(f"a greedy token lies {gap:.4f} under the reference's "
                     f"maximum logit (margin {lim['MARGIN']})")
    if undecided_gap > lim["UNDECIDED_MARGIN"]:
        notes.append(
            f"a greedy token at a position of undecided routing lies "
            f"{undecided_gap:.4f} under the reference's maximum logit "
            f"(margin {lim['UNDECIDED_MARGIN']})")
    if undecided > rm.UNDECIDED_SHARE * positions:
        notes.append(f"{undecided} of {positions} decoded positions route "
                     f"by less than {rm.ROUTE_EPS}: too few are held to "
                     f"the margin {lim['MARGIN']}")
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {"prompts": detail}


# the kernels the cell's programs must have run on the chip: a fallback
# counter over 0 there is a finding
KERNEL_FALLBACKS = (
    ("paged_attn_fallbacks", "paged latent attention ops"),
    ("mla_prefill_fallbacks", "prefill attention ops"),
    ("mhc_fallbacks", "residual-path ops"),
    ("grouped_polyglu_fallbacks", "grouped expert products"))


def check_prompts(ref, cfg, check: dict, rng):
    return [cut_prompt(ref, rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n),
                       check["new_tokens"], check["pad_min"])
            for n in check["prompt_tokens"]]


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """The check prompts through the engine it is handed, with every other
    slot live (`engine_outputs`), held against the reference by `judge`; on
    the chip no kernel of KERNEL_FALLBACKS may have taken its stock
    lowering.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_motif3.Reference(params, reference_config(cfg))
    sents = check_prompts(ref, cfg, check, rng)
    outs, live = engine_outputs(engine, sents, check, rng)
    compared, notes, detail = judge(ref, sents, outs, live, check)
    if jax.default_backend() == "tpu":
        for name, what in KERNEL_FALLBACKS:
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
    """Least bytes a decode step reads, from the window's counters: the
    routed experts that were HIT and the latent rows that were attended,
    in rings and in pages."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_motif3.step_bytes(
        config,
        experts_hit=c.get("decode.moe_experts_hit", 0) / steps,
        latent_rows=c.get("decode.kv_tokens_attended", 0) / steps,
        rows=c.get("decode.tokens", 0) / steps)
