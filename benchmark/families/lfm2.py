"""The `lfm2` family: paddle_tpu/models/lfm2.py behind `DecodeEngine`, held
against `benchmark/reference_lfm2.py` by the LOGITS of its prefill and of
every step, by the conv tails its slots hold and by the pages of its first
and its last attention layer, and counted by `benchmark/flops_lfm2.py`.

Configuration keys this file reads (beside the published ones, which the
file carries whole and unchanged): `layers_held` (published layer indices:
each one's mixer is the published `layer_types` entry of that index, its
feed-forward dense where the index is under the published
`num_dense_layers`; the held layers must start at 0, because the stage
carries the embedding), `experts_held` ([first, how many]; here all of
`num_experts`), `max_context`, `dtype` (weights, K/V pages, conv tails),
`kv_pages` (context pages of the attention layers, one class), an `engine`
group for `DecodeConfig` and a `check` group: `prompt_tokens` (lengths
under and over the tail's two rows and mid-bucket), `temperatures` (one a
prompt: 0 is greedy, which with a head tied to a unit-scale embedding
repeats the prompt's last token; a high one spreads the tokens so that the
tails and pages of the decode hold something that varies), `new_tokens`
(tokens delivered a prompt: one from the prefill, the rest from steps),
`pad_min` (the least length a sequence is padded to for the reference;
above it the next power of two) and `beside` (the sampled requests that
hold every other slot while the check prompts are prefilled and decoded).
The published keys read are `hidden_size`, `layer_types`,
`num_dense_layers`, `intermediate_size`, `moe_intermediate_size`,
`num_experts`, `num_experts_per_tok`, `norm_topk_prob`,
`routed_scaling_factor`, `num_attention_heads`, `num_key_value_heads`,
`conv_L_cache`, `norm_eps`, `rope_parameters.rope_theta` and `vocab_size`;
the switches (`conv_bias`, `use_expert_bias`, `rope_parameters.rope_type`)
are held to the values the program implements, and any other refuses the
configuration. The vocabulary is whole: traffic ids, logits and sampling
are over all of it.

The check (`judge`), at the timed load (every other slot held by sampled
requests). A check request keeps its prefill's logits row, the logits row
of EVERY step (`keep_step_outputs`), its slot's tails as they stand when
it retires (`keep_final_state`) and its pages (`keep_final_pages`). Over
the delivered sequence the reference's one full forward, in the
configuration's number format (reference_lfm2.py), gives the logits of
every position, z of every convolution layer and K and V of every
attention layer; then, a limit each (reference_lfm2.py, beside the readings
it was set from and the fault that fails it):

* what NO ROUTED LAYER PRECEDES is held by its worst: `tail_err_first` (the
  first layer's tail, the worst prompt's: its mixer reads the embedding
  itself, so what is off there is the tail's own arithmetic, or another
  slot's tail) and `kv_err_max` (K and V of EVERY position every check
  request cached in the first attention layer: a page of another slot, a
  position written twice or not at all is one position far off).
* a row's error is its largest difference as a share of the reference
  row's root mean square. Routing decides discretely and a turned choice
  leaves a burst of rows a whole expert off (reference_lfm2.py), so no
  single row is held: `logit_err_median` (all rows), `prompt_logit_err_q25`
  (each prompt's OWN lower quartile, the worst prompt's: a fault in one
  prompt of six moves all of that prompt's rows), `prefill_logit_err_second`
  (the second nearest of the prefills' own rows) and
  `first_steps_logit_err_median` (each prompt's first two steps, the only
  ones that read the tail its prefill wrote).
* `tail_err_median` (over prompts and convolution layers).
* `kv_last_err`: K and V in the LAST attention layer, four routed layers
  after the first: the median position's (a turned choice moves a position
  far, and few of them; a fault of the routing WEIGHTS moves every one).
* `kv_turned_share`: of the positions the check requests cached, the share
  whose K and V in the last attention layer are off by more than KV_TURNED
  of their norm, which is more than rounding leaves: the positions at which
  a routing choice turned. Thousands of positions a run, so the share is
  steady where a single row is not; the lower-precision control (scores
  rounded to bfloat16 turn choices the engine does not) reads six times
  the engine's.
"""

from __future__ import annotations

import gc
import time

from benchmark import flops_lfm2, reference_lfm2
from benchmark.families import qwen3_next
from benchmark.families.qwen3_next import pad_to
from benchmark.generators.requests import FIRST_TOKEN_ID

# the switches of the published config the program implements one value of
IMPLEMENTED = {"conv_bias": False, "use_expert_bias": True}
# families/kimi_k2.py EMBED_STD says why the embedding has unit elements
EMBED_STD = 1.0


def model_config(config: dict):
    from paddle_tpu.models import lfm2

    for key, value in IMPLEMENTED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the lfm2 program "
                             f"implements {value!r}")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}: the lfm2 "
                         f"program implements 'default'")
    held = config["layers_held"]
    if held != list(range(len(held))) \
            or len(held) != config["num_hidden_layers"]:
        raise ValueError("the layers held are the first num_hidden_layers "
                         "of the published pattern (the stage carries the "
                         "embedding)")
    return lfm2.Lfm2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=[config["layer_types"][i] for i in held],
        num_dense_layers=flops_lfm2.dense_layers(config),
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["experts_held"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=config["norm_topk_prob"],
        head_dim=flops_lfm2.head_dim(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        conv_L_cache=config["conv_L_cache"],
        rms_norm_eps=config["norm_eps"],
        rope_theta=float(rope["rope_theta"]),
        max_seq_len=config["max_context"], dtype=config["dtype"])


def reference_config(cfg) -> dict:
    """What reference_lfm2.forward reads, from the program's config."""
    return {k: getattr(cfg, k) for k in (
        "layer_types", "num_dense_layers", "num_experts_per_tok",
        "experts_held", "routed_scaling_factor", "norm_topk_prob",
        "head_dim", "num_heads", "num_kv_heads", "conv_L_cache",
        "rms_norm_eps", "rope_theta", "dtype")}


def make_params(cfg, seed: int):
    """Seeded weights in the dtypes the model states, made on the device in
    one jitted call (families/xing4.py `make_params`: ``normal`` at the
    model's `init_std`, the embedding at EMBED_STD; a (mean, std) draw, the
    expert bias; a constant)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lfm2

    specs = lfm2.param_specs(cfg)
    names = sorted(specs)

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind, dtype = specs[name]
            if kind == "normal" or isinstance(kind, tuple):
                mean, std = kind if isinstance(kind, tuple) else (
                    0.0, EMBED_STD if name == "lf_tok_emb"
                    else lfm2.init_std(name, shape))
                out[name] = (mean + std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
                    ).astype(dtype)
            else:
                out[name] = jnp.full(shape, kind, dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a mix or a check that do
    not fit (families/qwen3_next.py `engine_config`: one class of pages, a
    slot needs ceil(max_context / page); the tails need nothing said, there
    is one for every slot)."""
    check = config["check"]
    if len(check["prompt_tokens"]) != len(check["temperatures"]):
        raise ValueError("a check prompt needs a length and a temperature")
    return qwen3_next.engine_config(config, traffic)


def make_engine(cfg, params, config: dict, traffic: dict):
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    return DecodeEngine(cfg, params,
                        DecodeConfig(**engine_config(config, traffic)))


def slots(config: dict) -> int:
    return config["engine"]["max_slots"]


def traffic_vocab(cfg, config: dict) -> int:
    return cfg.vocab_size


def conv_layers(cfg):
    return [i for i in range(cfg.n_layers) if not cfg.is_attention(i)]


def check_prompts(cfg, check: dict, rng):
    """[(prompt, temperature, seed)] a check prompt."""
    return [(rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n).astype("int32"),
             float(t), int(rng.randint(2 ** 31)))
            for n, t in zip(check["prompt_tokens"], check["temperatures"])]


def engine_outputs(engine, prompts, check: dict, rng, timeout: float = 900.0):
    """The check prompts through the engine AT THE TIMED LOAD
    (families/qwen3_next.py `engine_outputs`: `check["beside"]` fills the
    other slots first, sampled, and waits until each decodes; then the
    check prompts go in together, so that their prefills run between the
    others' steps, each writing its own slot's tails while the neighbours'
    move on, and their tokens are chosen by steps of all the slots).
    -> ([(first_logits, tokens, step logits [steps, vocab], tails [conv
    layers, K - 1, hidden], the attention layers' pages [attention layers,
    tokens, 2, width])] a prompt, rows live beside the last)"""
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
                          stop_at_eos=False, temperature=t,
                          seed=seed if t > 0 else None,
                          keep_first_logits=True, keep_final_state=True,
                          keep_final_pages=True, keep_step_outputs=True)
            for sent, t, seed in prompts]
    chosen = [r.result(timeout) for r in reqs]
    live = sum(1 for r in others if not r.done())
    for r in others:
        r.result(timeout)
    cfg = engine.model_cfg
    attending = [i for i in range(cfg.n_layers) if cfg.is_attention(i)]
    outs = []
    for r, c in zip(reqs, chosen):
        tails = np.stack([np.asarray(r.final_state[f"conv_tail_{i}"],
                                     np.float32) for i in conv_layers(cfg)])
        # [pages, page, width] -> [tokens, 2, width] a layer: K, then V
        pages = np.stack([np.stack([
            np.asarray(r.final_pages[f"kv_{part}_{i}"], np.float32).reshape(
                -1, cfg.num_kv_heads * cfg.head_dim) for part in "kv"],
            axis=1) for i in attending])
        steps = np.stack([s["logits"] for s in r.step_outputs]) \
            if r.step_outputs else np.zeros((0, cfg.vocab_size), np.float32)
        outs.append((np.asarray(r.first_logits), c, steps, tails, pages))
    return outs, live


def judge_prompt(ref, sent, out, pad_min: int):
    """What the engine gave for one prompt, held against `ref` (the
    reference, a lower-precision control of it, or it with a planted
    fault). -> dict(sent, rows: each row's logit error (the prefill's
    first), tail_err: a layer's, kv_first, kv_last: each cached position's
    error in the first and in the last attention layer)"""
    import numpy as np

    rl = reference_lfm2
    first_logits, chosen, steps, tails, pages = out
    new = len(chosen)
    if len(steps) != new - 1:
        raise ValueError(f"{len(steps)} step records for {new} tokens")
    fed = sent.size + new - 1
    rows, ref_tails, kv = ref.rows(
        np.concatenate([sent, chosen]), pad_to(sent.size + new, pad_min),
        sent.size - 1, new, tail_at=fed - 1)
    got = np.concatenate([first_logits[None], steps]) if new > 1 \
        else first_logits[None]
    return {"sent": int(sent.size),
            "rows": rl.logit_errors(got, rows),
            "tail_err": rl.tail_errors(tails, ref_tails),
            "kv_first": rl.kv_errors(pages[0][:fed], kv[0][:fed]),
            "kv_last": rl.kv_errors(pages[-1][:fed], kv[-1][:fed])}


def judge(ref, prompts, outs, live: int, check: dict):
    """-> ([name, value, limit] of each number compared, notes, detail):
    every check prompt by `judge_prompt` against `ref`, and the limits of
    reference_lfm2 (this module's docstring says what each is)."""
    import numpy as np

    rl = reference_lfm2
    detail = {}
    rows, quartiles, prefills, firsts, tails = [], [], [], [], []
    kv_first, kv_last = [], []
    tail_first = 0.0
    for n, (sent, _t, _seed), out in zip(check["prompt_tokens"], prompts,
                                         outs):
        got = judge_prompt(ref, sent, out, check["pad_min"])
        rows.append(got["rows"])
        quartiles.append(float(np.percentile(got["rows"], 25)))
        prefills.append(float(got["rows"][0]))
        firsts += got["rows"][1:3].tolist()
        tails += got["tail_err"]
        tail_first = max(tail_first, got["tail_err"][0])
        kv_first.append(got["kv_first"])
        kv_last.append(got["kv_last"])
        detail[str(n)] = {
            "sent": got["sent"], "tail_err": got["tail_err"],
            "rows": [round(float(e), 5) for e in got["rows"]],
            "kv_first_max": float(got["kv_first"].max()),
            "kv_last_median": float(np.median(got["kv_last"]))}
    rows = np.concatenate(rows)
    kv_first, kv_last = np.concatenate(kv_first), np.concatenate(kv_last)
    held = [
        ("logit_err_median", float(np.median(rows)), rl.LOGIT_ERR,
         "the median logits row is {v:.4f} of a row's root mean square off "
         "the reference's"),
        ("prompt_logit_err_q25", max(quartiles), rl.PROMPT_LOGIT_ERR,
         "the lower quartile of one check prompt's logits rows is {v:.4f} "
         "of a row's root mean square off the reference's"),
        ("prefill_logit_err_second", sorted(prefills)[min(1, len(prefills)
                                                        - 1)],
         rl.PREFILL_LOGIT_ERR,
         "the second nearest of the prefills' logits rows is {v:.4f} of its "
         "root mean square off the reference's"),
        ("first_steps_logit_err_median", float(np.median(firsts))
         if firsts else 0.0, rl.FIRST_STEPS_LOGIT_ERR,
         "the median logits row of the first two steps after a prefill is "
         "{v:.4f} of a row's root mean square off the reference's"),
        ("tail_err_median", float(np.median(tails)), rl.TAIL_ERR,
         "the median conv tail after a decode is {v:.4f} of its norm off "
         "the reference's z"),
        ("tail_err_first", tail_first, rl.TAIL_ERR_FIRST,
         "the first layer's conv tail after a decode is {v:.4f} of its "
         "norm off the reference's z"),
        ("kv_err_max", float(kv_first.max()), rl.KV_ERR,
         "a position's K and V in the first attention layer's pages are "
         "{v:.4f} of their norm off the reference's"),
        ("kv_last_err", float(np.median(kv_last)), rl.KV_LAST_ERR,
         "the last attention layer's pages are {v:.4f} of a position's K "
         "and V off the reference's at the median position"),
        ("kv_turned_share", float(np.mean(kv_last > rl.KV_TURNED)),
         rl.KV_TURNED_SHARE,
         "{v:.4f} of the cached positions' K and V in the last attention "
         "layer are off the reference's by more than rounding leaves")]
    compared = [[name, value, limit] for name, value, limit, _ in held]
    notes = [text.format(v=value) + f" (limit {limit})"
             for _, value, limit, text in held if value > limit]
    compared.append(["rows_not_live_beside_check",
                     check["beside"]["requests"] - live, 0])
    if live < check["beside"]["requests"]:
        notes.append(
            f"only {live} of the {check['beside']['requests']} requests "
            f"beside the check were still decoding when it ended")
    return compared, notes, {
        "prompts": detail, "rows": int(rows.size),
        "row_logit_err_max": float(rows.max()),
        "rows_over_row_turned": int(np.sum(rows > rl.ROW_TURNED)),
        "kv_last_err_quantiles": [round(float(v), 5) for v in np.percentile(
            kv_last, [10, 25, 50, 75, 90, 95, 99])],
        "positions": int(kv_last.size)}


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """The check prompts through the engine it is handed, with every other
    slot live (`engine_outputs`), held against the reference by `judge`; on
    the chip neither the paged attention nor the grouped expert product may
    have taken its stock lowering.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import jax
    import numpy as np

    from paddle_tpu.core import telemetry

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = reference_lfm2.Reference(params, reference_config(cfg))
    prompts = check_prompts(cfg, check, rng)
    outs, live = engine_outputs(engine, prompts, check, rng)
    compared, notes, detail = judge(ref, prompts, outs, live, check)
    if jax.default_backend() == "tpu":
        for name, what in (
                ("paged_attn_fallbacks", "paged attention ops"),
                ("grouped_swiglu_fallbacks", "grouped expert products")):
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
    non-expert weights once (the embedding as the head), each held expert
    that was hit, the keys attended and the tails of the live rows."""
    c = telemetry["counters"]
    steps = c.get("decode.steps") or 0
    if not steps:
        return 0.0
    return flops_lfm2.step_bytes(
        config,
        experts_hit=c.get("decode.moe_experts_hit", 0) / steps,
        kv_tokens=c.get("decode.kv_tokens_attended", 0) / steps,
        conv_rows=c.get("decode.conv_rows_updated", 0) / steps)
