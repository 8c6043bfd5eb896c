"""The `kimi_k2` family: paddle_tpu/models/kimi_k2.py behind `DecodeEngine`,
held against `benchmark/reference_kimi_k2.py` by logits, and counted by
`benchmark/flops_kimi_k2.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole): `layers_held` (published layer indices: the leading
`first_k_dense_replace` of them are dense), `experts_held` ([first, how
many]; the router keeps `n_routed_experts`), `vocab_size` (rows of
embedding and head held; traffic ids, logits and sampling are over them),
`max_context`, `dtype`, `kv_pages` (latent pages, one class), an `engine`
group for `DecodeConfig` and a `check` group as the afmoe family's
(`prompt_tokens`, one prompt in each prefill bucket; `new_tokens`;
`pad_min`; `beside`). Every head is held: the deployment's attention is
data-parallel.

The check is the afmoe family's, with this model's reference and limits:
`engine_outputs` and `pad_to` are that file's own functions, imported.
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_kimi_k2, reference_kimi_k2
from benchmark.families.afmoe import engine_outputs, pad_to  # noqa: F401
from benchmark.generators.requests import FIRST_TOKEN_ID


def model_config(config: dict):
    from paddle_tpu.models import kimi_k2

    rope = config["rope_scaling"]
    return kimi_k2.KimiK2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_layers=len(config["layers_held"]),
        first_k_dense=sum(1 for i in config["layers_held"]
                          if i < config["first_k_dense_replace"]),
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        num_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["experts_held"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"], rope_factor=rope["factor"],
        rope_original_max=rope["original_max_position_embeddings"],
        rope_beta_fast=rope["beta_fast"], rope_beta_slow=rope["beta_slow"],
        rope_mscale_all_dim=rope["mscale_all_dim"],
        max_seq_len=config["max_context"], dtype=config["dtype"])


def reference_config(cfg) -> dict:
    """What reference_kimi_k2.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "n_layers", "first_k_dense", "num_experts_per_tok", "experts_held",
        "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
        "rope_theta", "rope_factor", "rope_original_max", "rope_beta_fast",
        "rope_beta_slow", "rope_mscale_all_dim")}


# The one scale of seeded weights that is not fan_in^-0.5 (the
# configuration file lists it under `assumed`): the embedding has unit
# elements. The block is pre-norm with no norm on what a sublayer adds, so
# with the embedding at fan_in^-0.5 too (rows of norm 1, 0.012 an element)
# a token's identity drowns in the first sublayers' unit-scale outputs: the
# trap PR 28 fell into, a whole sequence routed to the same experts. The
# matrices that write to the residual stream stay at fan_in^-0.5 like the
# rest: scaled down by depth (0.0905 = (2 x 61)^-0.5) the logits follow
# the last token's embedding alone, and a decode step that attends another
# request's pages still chooses the reference's tokens
# (tests/benchmark_suite/test_kimi_k2_check.py planted it and the check saw
# nothing). A checkpoint brings its own scales: this one is the benchmark's.
EMBED_STD = 1.0


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call: normal, drawn in float32 and rounded tensor by tensor
    (no float32 copy of the whole is held), the standard deviations by the
    model's `init_std`, the embedding's EMBED_STD; gains and the selection
    bias at the constants `param_specs` gives them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import kimi_k2

    specs = kimi_k2.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind, dtype = specs[name]
            if kind == "normal":
                std = EMBED_STD if name == "k2_tok_emb" \
                    else kimi_k2.init_std(name, shape)
                out[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
                    ).astype(dtype)
            else:
                out[name] = jnp.full(shape, kind, dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a mix or a check that do
    not fit. One class of pages: a slot needs ceil(max_context / page)."""
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
    if max(check["prompt_tokens"] + beside["prompt_tokens"]) \
            > max(eng["prefill_buckets"]):
        raise ValueError("a check prompt is over the largest prefill bucket")
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
    (ROUTE_EPS), so that the prefill's logits row is compared at a
    position where a top-k does not turn on rounding."""
    import numpy as np

    prompt = np.asarray(prompt, np.int32)
    tail = min(new_tokens, prompt.size)
    _, route_gap = ref.rows(prompt, pad_to(prompt.size, new_tokens, pad_min),
                            prompt.size - tail, new_tokens)
    keep = reference_kimi_k2.decided_prefix(
        route_gap[prompt.size - tail:], reference_kimi_k2.ROUTE_EPS)
    if not keep:
        raise ValueError(f"no position of the prompt's last {tail} routes "
                         f"by more than {reference_kimi_k2.ROUTE_EPS}")
    return prompt[:prompt.size - tail + keep]


def judge_prompt(ref, sent, first_logits, chosen, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, or a lower-precision control of it): the prefill's logits
    row against the reference's at the prompt's last position
    (`logit_err`), and each greedy token teacher-forced through the
    reference by the margin rule: `gap` the worst at positions whose
    routing the reference decides, `undecided_gap` the worst at the
    others (`undecided` of them), held to a wider margin, not left out.
    -> dict(sent, logit_err, gap, undecided, undecided_gap, gaps)"""
    import numpy as np

    rk = reference_kimi_k2
    new = len(chosen)
    rows, route_gap = ref.rows(np.concatenate([sent, chosen]),
                               pad_to(sent.size, new, pad_min),
                               sent.size - 1, new)
    decided = route_gap[sent.size - 1:sent.size - 1 + new] > rk.ROUTE_EPS
    gaps = rk.greedy_gaps(rows, chosen)
    return {"sent": int(sent.size),
            "logit_err": rk.logit_error(first_logits, rows[0]),
            "gap": float(gaps[decided].max()) if decided.any() else 0.0,
            "undecided": int((~decided).sum()),
            "undecided_gap": float(gaps[~decided].max())
            if (~decided).any() else 0.0,
            "gaps": [round(float(g), 5) for g in gaps]}


def judge(ref, sents, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_kimi_k2."""
    rk = reference_kimi_k2
    compared, notes, detail = [], [], {}
    gap = undecided_gap = 0.0
    undecided = positions = 0
    for n, sent, (first_logits, chosen) in zip(check["prompt_tokens"], sents,
                                               outs):
        got = detail[str(n)] = judge_prompt(ref, sent, first_logits, chosen,
                                            check["pad_min"])
        compared.append([f"prefill_logit_err_p{n}", got["logit_err"],
                         rk.LOGIT_ERR])
        if got["logit_err"] > rk.LOGIT_ERR:
            notes.append(
                f"prefill logits of a {got['sent']}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {rk.LOGIT_ERR})")
        gap = max(gap, got["gap"])
        undecided_gap = max(undecided_gap, got["undecided_gap"])
        undecided += got["undecided"]
        positions += len(chosen)
    compared += [["greedy_logit_gap", gap, rk.MARGIN],
                 ["greedy_logit_gap_undecided", undecided_gap,
                  rk.UNDECIDED_MARGIN],
                 ["undecided_positions", undecided,
                  int(rk.UNDECIDED_SHARE * positions)],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if gap > rk.MARGIN:
        notes.append(f"a greedy token lies {gap:.4f} under the reference's "
                     f"maximum logit (margin {rk.MARGIN})")
    if undecided_gap > rk.UNDECIDED_MARGIN:
        notes.append(
            f"a greedy token at a position of undecided routing lies "
            f"{undecided_gap:.4f} under the reference's maximum logit "
            f"(margin {rk.UNDECIDED_MARGIN})")
    if undecided > rk.UNDECIDED_SHARE * positions:
        notes.append(f"{undecided} of {positions} decoded positions route "
                     f"by less than {rk.ROUTE_EPS}: too few are held to "
                     f"the margin {rk.MARGIN}")
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {"prompts": detail}


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """The check prompts through the engine it is handed, with every other
    slot live (`engine_outputs`: each compared token comes from a step of
    all the slots, through each slot's own page table), held against the
    reference by `judge`; on the chip no latent op may have taken its
    stock lowering.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_kimi_k2.Reference(params, reference_config(cfg))
    sents = [cut_prompt(ref, rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n),
                        check["new_tokens"], check["pad_min"])
             for n in check["prompt_tokens"]]
    outs, live = engine_outputs(engine, sents, check, rng)
    compared, notes, detail = judge(ref, sents, outs, live, check)
    if jax.default_backend() == "tpu":
        # on the chip the decode step and the prefill run their kernels
        for name, what in (("paged_attn_fallbacks", "the stock gather"),
                           ("mla_prefill_fallbacks", "the stock products")):
            fell = int(telemetry.counter_get("pallas." + name))
            compared.append([name, fell, 0])
            if fell:
                notes.append(f"{fell} latent attention ops took {what}")
    # the reference goes NOW, inside set-up: its jitted pass closes over it
    # (a cycle), so it would otherwise go when the collector next runs a
    # full pass, its compiled programs with it, at a moment of the
    # collector's choosing inside the timed window
    t0 = time.perf_counter()
    del ref
    gc.collect()
    detail["teardown_s"] = round(time.perf_counter() - t0, 3)
    return compared, notes, detail


def step_bytes(cfg, config: dict, live_context_tokens: float,
               telemetry: dict) -> float:
    """Least bytes a decode step reads, from the window's counters: the
    routed experts that were HIT and the latent rows that were attended."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_kimi_k2.step_bytes(
        config,
        experts_hit=c.get("decode.moe_experts_hit", 0) / steps,
        latent_tokens=c.get("decode.kv_tokens_attended", 0) / steps,
        rows=c.get("decode.tokens", 0) / steps)
