"""The `xing4` family: paddle_tpu/models/xing4.py behind `DecodeEngine` with
its multi-token-prediction module DRAFTING, held against
`benchmark/reference_xing4.py` by the logits of both verified positions,
the module's logits, the latent rows its pages hold and the acceptance rule
replayed, and counted by `benchmark/flops_xing4.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole under their own names): `layers_held` (published layer
indices: those under `first_k_dense_replace` are dense), `experts_held`
([first, how many]; here all of `n_routed_experts`), `vocab_size` (here the
whole vocabulary), `max_context`, `dtype`, `kv_pages` (latent pages, one
class, six layers: the held ones and the module's), `num_dense_layers` (the
harness's key), an `engine` group for `DecodeConfig`, and a `check` group:
`prompt_tokens`, `temperatures` and `new_tokens` (one of each a check
prompt: greedy prompts reject nearly every draft, sampled ones accept most),
`rejected_rows` (steps a prompt whose REJECTED second position is held
against the reference fed the draft: one more forward each), `pad_min`,
`beside` (the sampled requests that keep the other slots live). Published
keys it reads: `hidden_size`, `num_attention_heads`, `q_lora_rank`,
`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`first_k_dense_replace`, `intermediate_size`, `moe_intermediate_size`,
`n_shared_experts`, `n_routed_experts`, `num_experts_per_tok`,
`routed_scaling_factor`, `norm_topk_prob`, `rms_norm_eps`, `rope_theta`,
`rope_scaling`, `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`. The temperature of the timed
traffic is the traffic file's (`assumed.temperature` says why it is what it
is).

The check (`judge`). A check request keeps its prefill's logits row, a
record of EVERY step (`keep_step_outputs`: the logits of both positions,
the module's logits, the draft and the q it was drawn from, the step's
uniforms and tokens) and its pages when it retires. Over the delivered
sequence the reference's one forward gives the logits of every position,
the module's logits, both routing gaps and the latent rows of all six
layers; then, a limit each (reference_xing4.py):

* The logits of BOTH positions of every step (an accepted second position
  against the sequence's own row, a rejected one, for `rejected_rows`
  steps a prompt, against the reference fed the draft). With all 64
  experts held nearly every position routes by less than ROUTE_EPS in one
  of five routed layers (96% on the chip), a bfloat16 engine keeps another
  expert at some of them, and such a row differs from the reference's
  like another row: so single rows are held only where the reference
  routes decidedly, and each prefill's row, and only to being the same row
  at all (`step_logit_err`, `prefill_logit_err_p<n>`: ROW_LOGIT_ERR; the
  undecided rows' largest is logged as `step_logit_err_undecided` and held
  by no limit), and every CLASS of rows is held by its median, which a
  flipped expert does not move and a fault does:
  `first_position_logit_err_median`, `second_position_logit_err_median`.
* `draft_logit_err`, `draft_logit_err_median`: the module's logits at the
  newest accepted position against the reference module's, likewise.
* `latent_err_held`, `latent_err_module`: the rows the request's pages held
  when it retired (after rejections: a greedy prompt rejects nearly every
  step, so nearly every row was once a rejected draft's and overwritten),
  the median row's error; `latent_err_unrouted`: the same of the layers no
  routed layer lies before, where no expert can flip and the engine's
  rounding alone is read.
* `rule_distance`: every step's tokens against the rule replayed from the
  fetched probabilities and the request's uniforms, the draft against q
  and the previous step's fourth uniform; `q_carry_err`: the q a step
  verified against the softmax of the module's logits the step before;
  `uniforms_off`: the uniforms a step was fed against the request's own
  stream replayed from its seed; `positions_off`: a step's position
  against the count of tokens delivered before it.
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_xing4, reference_xing4
from benchmark.families.afmoe import pad_to
from benchmark.generators.requests import FIRST_TOKEN_ID


def model_config(config: dict):
    from paddle_tpu.models import xing4

    rope = config["rope_scaling"]
    return xing4.Xing4Config(
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
        n_streams=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_res_clamp=(config["mhc_h_res_clamp_min"],
                      config["mhc_h_res_clamp_max"]),
        max_seq_len=config["max_context"], dtype=config["dtype"])


def reference_config(cfg) -> dict:
    """What reference_xing4.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "n_layers", "first_k_dense", "num_experts_per_tok", "experts_held",
        "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
        "rope_theta", "rope_factor", "rope_original_max", "rope_beta_fast",
        "rope_beta_slow", "rope_mscale_all_dim", "n_streams",
        "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp", "dtype")}


# The embedding has unit elements, as families/kimi_k2.py EMBED_STD says
# and why.
EMBED_STD = 1.0


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call (families/motif3.py `make_params`: ``normal`` at the
    model's `init_std`, the embedding at EMBED_STD; a (mean, std) draw; a
    constant)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import xing4

    specs = xing4.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind, dtype = specs[name]
            if kind == "normal" or isinstance(kind, tuple):
                mean, std = kind if isinstance(kind, tuple) else (
                    0.0, EMBED_STD if name == "x4_tok_emb"
                    else xing4.init_std(name, shape))
                out[name] = (mean + std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
                    ).astype(dtype)
            else:
                out[name] = jnp.full(shape, kind, dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a mix or a check that do
    not fit. A slot needs ceil(max_context / page) pages."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"])
    slots_ = eng["max_slots"]
    per_slot = -(-config["max_context"] // eng["page_size"])
    if eng["kv_pages"] < slots_ * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {slots_} slots")
    check = config["check"]
    beside = check["beside"]
    if not len(check["prompt_tokens"]) == len(check["temperatures"]) \
            == len(check["new_tokens"]):
        raise ValueError("a check prompt needs a length, a temperature and "
                         "a count of new tokens")
    longest = max(traffic["max_context"],
                  max(n + new for n, new in zip(check["prompt_tokens"],
                                                check["new_tokens"])),
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
    return cfg.vocab_size


def check_prompts(cfg, check: dict, rng):
    """[(prompt, temperature, new tokens, seed)] a check prompt."""
    return [(rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n).astype("int32"),
             float(t), int(new), int(rng.randint(2 ** 31)))
            for n, t, new in zip(check["prompt_tokens"],
                                 check["temperatures"], check["new_tokens"])]


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD
    (families/afmoe.py `engine_outputs`: `check["beside"]` fills the other
    slots first, sampled, then the check prompts go in together), each
    keeping its prefill's logits row, a record of every step and, when it
    retires, its pages.
    -> ([(first_logits, tokens, steps, {layer: rows})] a prompt, rows live
    beside the last)"""
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
    reqs = [engine.submit(sent, max_new_tokens=new, stop_at_eos=False,
                          temperature=t, seed=seed if t > 0 else None,
                          keep_first_logits=True, keep_final_pages=True,
                          keep_step_outputs=True)
            for sent, t, new, seed in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    cfg = engine.model_cfg
    kept = [{i: np.asarray(r.final_pages[f"kv_c_{i}"], np.float32).reshape(
        -1, cfg.latent_row_width) for i in range(cfg.n_layers + 1)}
        for r in reqs]
    return [(np.asarray(r.first_logits), c, r.step_outputs, k)
            for r, c, k in zip(reqs, chosen, kept)], live


def request_uniforms(seed: int, temperature: float, steps: int):
    """The request's own stream replayed: its first token's one draw (the
    host's), then four a step, float32 as the engine feeds them."""
    import numpy as np

    if temperature <= 0:
        return np.zeros((steps, 4), np.float32)
    rng = np.random.RandomState(seed)
    rng.random_sample()
    return rng.random_sample((steps, 4)).astype(np.float32)


def judge_prompt(ref, prompt, out, check: dict, redraw_from_p=False):
    """What the engine gave for one check prompt, held against `ref` (the
    reference, a control of it, or one with a planted fault): module
    docstring. -> dict of the numbers `judge` compares."""
    import numpy as np

    rx = reference_xing4
    sent, temperature, _new, seed = prompt
    first_logits, tokens, steps, kept = out
    seq = np.concatenate([sent, tokens])
    length, total = sent.size, seq.size
    pad = pad_to(total, 1, check["pad_min"])
    first = length - 1
    # one count of rows for every forward of the check: one compile
    n_rows = max(check["new_tokens"]) + 1
    if total + n_rows > pad:
        raise ValueError("the check's rows do not fit its padding")
    logits, draft_logits, gap, draft_gap, latents = ref.rows(
        seq, pad, first, n_rows)
    uniforms = request_uniforms(seed, temperature, len(steps))
    got = {"sent": int(length), "steps": len(steps),
           "logit_err": rx.logit_error(first_logits, logits[0]),
           "prefill_decided": bool(gap[first] > rx.ROUTE_EPS),
           "step_err": 0.0, "step_err_undecided": 0.0, "draft_err": 0.0,
           "draft_err_undecided": 0.0, "undecided": 0, "positions": 0,
           "first_errs": [], "second_errs": [], "draft_errs": [],
           "rule_distance": 0.0, "q_carry_err": 0.0, "uniforms_off": 0,
           "positions_off": 0, "accepted": 0, "rejected_rows": 0}

    def hold(key, err, decided, which):
        got["positions"] += 1
        got[which].append(round(err, 9))
        if decided:
            got[key] = max(got[key], err)
        else:
            got["undecided"] += 1
            got[key + "_undecided"] = max(got[key + "_undecided"], err)

    before = 1                              # tokens delivered so far
    last = None
    for k, s in enumerate(steps):
        pos = length + before - 1
        got["positions_off"] += int(s["position"] != pos)
        got["uniforms_off"] += int(np.any(s["uniforms"] != uniforms[k]))
        hold("step_err", rx.logit_error(s["logits"][0], logits[pos - first]),
             gap[pos] > rx.ROUTE_EPS, "first_errs")
        count = len(s["tokens"])
        got["accepted"] += count - 1
        if count == 2:
            hold("step_err",
                 rx.logit_error(s["logits"][1], logits[pos + 1 - first]),
                 gap[pos + 1] > rx.ROUTE_EPS, "second_errs")
        elif s["had_draft"] and got["rejected_rows"] < check["rejected_rows"]:
            # a rejected second position: the reference fed the draft
            got["rejected_rows"] += 1
            fed = np.concatenate([seq[:pos + 1], [s["draft"]]])
            row, _, fed_gap, _, _ = ref.rows(fed, pad, pos + 1, n_rows)
            hold("step_err", rx.logit_error(s["logits"][1], row[0]),
                 fed_gap[pos + 1] > rx.ROUTE_EPS, "second_errs")
        if s["delivered"] == count:
            # the module's row of the newest accepted position, whose next
            # token is the step's last
            at = pos + count - 1
            hold("draft_err",
                 rx.logit_error(s["draft_logits"], draft_logits[at - first]),
                 min(gap[at], draft_gap[at]) > rx.ROUTE_EPS, "draft_errs")
        # the rule, from what the step itself read
        q = s["q"] if s["had_draft"] else None
        if last is not None:
            want = rx.probabilities(last["draft_logits"], temperature)
            got["q_carry_err"] = max(got["q_carry_err"],
                                     float(np.abs(s["q"] - want).max()))
        if temperature > 0:
            dists = rx.rule_distances(
                rx.probabilities(s["logits"][0], temperature),
                rx.probabilities(s["logits"][1], temperature),
                q, s["draft"], s["uniforms"], s["tokens"], redraw_from_p)
            if last is not None:
                dists.append(rx.cdf_distance(s["q"], last["uniforms"][3],
                                             s["draft"]))
            got["rule_distance"] = max(got["rule_distance"], *dists)
        else:
            best = [int(np.argmax(s["logits"][j])) for j in range(count)]
            off = best != list(s["tokens"]) or (
                s["had_draft"] and (s["draft"] == int(np.argmax(
                    s["logits"][0]))) != (count == 2))
            if last is not None:
                off = off or s["draft"] != int(np.argmax(
                    last["draft_logits"]))
            got["rule_distance"] = max(got["rule_distance"], float(off))
        before += s["delivered"]
        last = s
    got["positions_off"] += int(before != tokens.size)
    # rows 0 .. total - 2 were written for tokens that were fed and kept
    n_layers = latents.shape[0] - 1
    fed = total - 1
    by_layer = [rx.latent_error(kept[i][:fed], latents[i][:fed])
                for i in range(n_layers + 1)]
    got["latent_err_by_layer"] = [round(e, 5) for e in by_layer]
    got["latent_err_held"] = max(by_layer[:n_layers])
    got["latent_err_module"] = by_layer[n_layers]
    # the rows no routed layer lies before: the leading dense layers' and
    # the first routed layer's (its attention reads what the dense layers
    # left): no expert can flip under them, so they are held far closer
    got["latent_err_unrouted"] = max(by_layer[:ref.cfg["first_k_dense"] + 1])
    return got


def judge(ref, prompts, outs, live: int, check: dict, redraw_from_p=False):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_xing4 for the configuration's dtype."""
    rx = reference_xing4
    lim = rx.limits(ref.cfg["dtype"])
    compared, notes, detail = [], [], {}
    worst = dict.fromkeys(
        ("step_err", "step_err_undecided", "draft_err",
         "draft_err_undecided", "rule_distance", "q_carry_err"), 0.0)
    totals = dict.fromkeys(("undecided", "positions", "uniforms_off",
                            "positions_off", "steps", "accepted"), 0)
    classes = {"first_errs": [], "second_errs": [], "draft_errs": []}
    for n, prompt, out in zip(check["prompt_tokens"], prompts, outs):
        got = detail[str(n)] = judge_prompt(ref, prompt, out, check,
                                            redraw_from_p)
        compared.append([f"prefill_logit_err_p{n}", got["logit_err"],
                         lim["ROW_LOGIT_ERR"]])
        if got["logit_err"] > lim["ROW_LOGIT_ERR"]:
            notes.append(
                f"prefill logits of a {n}-token prompt are "
                f"{got['logit_err']:.4f} of their RMS off the reference's "
                f"(limit {lim['ROW_LOGIT_ERR']})")
        for what, limit in (("unrouted", "LATENT_ERR_UNROUTED"),
                            ("held", "LATENT_ERR"),
                            ("module", "LATENT_ERR")):
            err = got[f"latent_err_{what}"]
            compared.append([f"latent_err_{what}_p{n}", err, lim[limit]])
            if err > lim[limit]:
                notes.append(
                    f"the latent rows a {n}-token prompt's pages held in "
                    f"the {what} layers are {err:.4f} of a row off the "
                    f"reference's at the median row (limit {lim[limit]})")
        for key in worst:
            worst[key] = max(worst[key], got[key])
        for key in totals:
            totals[key] += got[key]
        for key in ("first_errs", "second_errs", "draft_errs"):
            classes[key] += got[key]
    import numpy as np

    for key, errs in classes.items():
        worst[key[:-1] + "_median"] = float(np.median(errs)) if errs else 0.0
    held = (("step_logit_err", "step_err", "ROW_LOGIT_ERR",
             "a verified position's logits at decided routing"),
            ("draft_logit_err", "draft_err", "ROW_LOGIT_ERR",
             "the module's logits at decided routing"),
            ("first_position_logit_err_median", "first_err_median",
             "MEDIAN_LOGIT_ERR", "the first verified position's logits, the "
             "median over steps"),
            ("second_position_logit_err_median", "second_err_median",
             "MEDIAN_LOGIT_ERR", "the second verified position's logits, "
             "the median over steps"),
            ("draft_logit_err_median", "draft_err_median",
             "MEDIAN_LOGIT_ERR", "the module's logits, the median over "
             "steps"),
            ("rule_distance", "rule_distance", "RULE_DISTANCE",
             "a delivered token's distance from where the acceptance rule "
             "puts it"))
    for name, key, limit, what in held:
        compared.append([name, worst[key], lim[limit]])
        if worst[key] > lim[limit]:
            notes.append(f"{what}: {worst[key]:.5f} (limit {lim[limit]})")
    # q is carried from step to step in float32: the softmax again, in
    # float64, differs by float32's rounding of probabilities under 1
    compared += [["q_carry_err", worst["q_carry_err"], 1e-5],
                 ["uniforms_off", totals["uniforms_off"], 0],
                 ["positions_off", totals["positions_off"], 0],
                 ["undecided_positions", totals["undecided"],
                  totals["positions"]],
                 ["rows_not_live_beside_check",
                  check["beside"]["requests"] - live, 0]]
    if worst["q_carry_err"] > 1e-5:
        notes.append(f"the q a step verified against is "
                     f"{worst['q_carry_err']:.2e} off the module's softmax "
                     f"of the step before")
    for key, what in (("uniforms_off", "were fed other uniforms than the "
                       "request's stream gives"),
                      ("positions_off", "ran at another position than the "
                       "delivered tokens give")):
        if totals[key]:
            notes.append(f"{totals[key]} steps {what}")
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {
        "prompts": detail, "steps": totals["steps"],
        "accepted": totals["accepted"],
        # single rows where an expert may flip: read, held by no limit
        "step_logit_err_undecided": worst["step_err_undecided"],
        "draft_logit_err_undecided": worst["draft_err_undecided"]}


# the kernels the cell's programs must have run on the chip: a fallback
# counter over 0 there is a finding
KERNEL_FALLBACKS = (
    ("paged_attn_fallbacks", "paged latent attention ops"),
    ("mla_prefill_fallbacks", "prefill attention ops"),
    ("mhc_fallbacks", "residual-path ops"),
    ("grouped_swiglu_fallbacks", "grouped expert products"))


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
    ref = reference_xing4.Reference(params, reference_config(cfg))
    prompts = check_prompts(cfg, check, rng)
    outs, live = engine_outputs(engine, prompts, check, rng)
    compared, notes, detail = judge(ref, prompts, outs, live, check)
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
    """Least bytes a drafting step reads, from the window's counters: the
    routed experts that were HIT in the held layers and in the module's,
    and the latent rows a row attended, once for its two positions."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_xing4.step_bytes(
        config,
        experts_hit=(c.get("decode.moe_experts_hit", 0)
                     + c.get("decode.draft_moe_experts_hit", 0)) / steps,
        latent_rows=c.get("decode.kv_tokens_attended", 0) / steps,
        rows=c.get("decode.rows_stepped", 0) / steps)
