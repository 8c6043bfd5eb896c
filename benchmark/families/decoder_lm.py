"""The `decoder_lm` family: paddle_tpu/models/decoder_lm.py behind
`DecodeEngine`, held against `benchmark/reference.py:check_greedy` and
counted by `benchmark/flops.py:decoder_*`.

Weights are made on the device from the seed in one jitted call and handed
to `DecodeEngine(cfg, params, DecodeConfig(**engine))`: chip_smoke.py's
`_serve_one` (run on the chip in PR 21) without the trip through the disk.
"""

from __future__ import annotations

from benchmark import flops, reference
from benchmark.common import post
from benchmark.generators.requests import FIRST_TOKEN_ID


def model_config(config: dict):
    from paddle_tpu.models import decoder_lm as dl

    return dl.DecoderLMConfig(
        vocab_size=config["vocab_size"], d_model=config["d_model"],
        n_head=config["attention_heads"], n_layers=config["num_layers"],
        d_inner=config["ffn_dim"],
        max_seq_len=config["max_position_embeddings"])


def param_specs(cfg):
    """name -> (shape, kind), as models/decoder_lm.decoder_lm_params lays
    them out (a CPU test compares the two)."""
    from paddle_tpu.models import decoder_lm as dl

    specs = {"lm_tok_emb": ((cfg.vocab_size, cfg.d_model), "normal")}
    for i in range(cfg.n_layers):
        for suffix, d_in, d_out in dl._dense_specs(cfg):
            specs[f"lm_l{i}_{suffix}_w"] = ((d_in, d_out), "normal")
            specs[f"lm_l{i}_{suffix}_b"] = ((d_out,), "zeros")
        for ln in ("ln1", "ln2"):
            specs[f"lm_l{i}_{ln}_scale"] = ((cfg.d_model,), "ones")
            specs[f"lm_l{i}_{ln}_bias"] = ((cfg.d_model,), "zeros")
    return specs


def make_params(cfg, seed: int):
    """Seeded float32 weights, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import decoder_lm as dl

    specs = param_specs(cfg)
    names = sorted(specs)
    std = cfg.d_model ** -0.5

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind = specs[name]
            if kind == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, float(kind == "ones"),
                                     jnp.float32)
        return out

    params = jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))
    params["lm_pos_enc"] = jnp.asarray(
        dl._sinusoid_table(cfg.max_seq_len, cfg.d_model))
    return params


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a traffic mix or a check
    that does not fit the configuration's `max_context`. One page class for
    every layer: a slot needs ceil(max_context / page) pages."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"])
    per_slot = -(-config["max_context"] // eng["page_size"])
    if eng["kv_pages"] < eng["max_slots"] * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {eng['max_slots']} slots")
    check = config["check"]
    longest = max(traffic["max_context"],
                  max(check["prompt_tokens"]) + check["new_tokens"])
    if longest > config["max_context"]:
        raise ValueError(f"a context of {longest} tokens is over the "
                         f"configuration's max_context")
    return eng


def make_engine(cfg, params, config: dict, traffic: dict):
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    return DecodeEngine(cfg, params,
                        DecodeConfig(**engine_config(config, traffic)))


def slots(config: dict) -> int:
    """Rows a full decode step advances: the engine's slots."""
    return config["engine"]["max_slots"]


def traffic_vocab(cfg, config: dict) -> int:
    """The ids the generator may draw: the whole vocabulary."""
    return cfg.vocab_size


def check_correct(url, engine, params, cfg, check: dict, seed: int):
    """Greedy requests over HTTP, teacher-forced through the reference.
    -> ([name, value, limit] of each number compared, notes, detail)."""
    import numpy as np

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    worst, notes, by_prompt = 0.0, [], {}
    for n in check["prompt_tokens"]:
        prompt = rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n)
        status, body = post(url + "/v1/generate", {
            "prompt_ids": prompt.tolist(), "stop_at_eos": False,
            "max_new_tokens": check["new_tokens"]})
        if status != 200 or body.get("num_tokens") != check["new_tokens"]:
            notes.append(f"check request answered {status}: {body}")
            continue
        ok, gap, by_prompt[n] = reference.check_greedy(
            params, cfg.n_layers, cfg.n_head, prompt, body["tokens"],
            pad_to=check["pad_to"])
        worst = max(worst, gap)
        if not ok:
            notes.append(f"engine's greedy token {gap:.4f} under the "
                         f"reference's maximum logit (margin "
                         f"{reference.MARGIN}) at prompt length {n}")
    return ([["greedy_logit_gap", worst, reference.MARGIN]], notes,
            {"gaps": by_prompt})


def step_bytes(cfg, config: dict, live_context_tokens: float,
               telemetry: dict) -> float:
    """Least bytes a decode step reads; a dense model's depend on shapes
    alone, so the window's counters are not looked at."""
    return flops.decoder_step_bytes(
        d_model=cfg.d_model, layers=cfg.n_layers, ffn=cfg.d_inner,
        vocab=cfg.vocab_size, live_context_tokens=live_context_tokens)
