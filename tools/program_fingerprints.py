"""sha256 of the jaxprs the served families' step and prefill programs and the
two trainers' step programs trace to at toy widths, and of the shared ops a
trainer reads, so that a PR that touches shared code can show that no
program changed. The committed table is `tests/program_fingerprints.json`
(`tests/test_program_fingerprints.py` holds every line of it); a PR that
changes a program on purpose rewrites the table and so says it in its diff.

    JAX_PLATFORMS=cpu python tools/program_fingerprints.py --check
    JAX_PLATFORMS=cpu python tools/program_fingerprints.py --write
"""

import argparse
import functools
import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = os.path.join(REPO, "tests", "program_fingerprints.json")
WRITE_COMMAND = "JAX_PLATFORMS=cpu python tools/program_fingerprints.py --write"

# family -> (config class, seeded parameters) of its module in models/
SERVED = {"decoder_lm": ("DecoderLMConfig", "decoder_lm_params"),
          "afmoe": ("AfmoeConfig", "afmoe_params"),
          "kimi_k2": ("KimiK2Config", "kimi_k2_params"),
          "falcon_h1": ("FalconH1Config", "falcon_h1_params"),
          "qwen3_next": ("Qwen3NextConfig", "qwen3_next_params"),
          "motif3": ("Motif3Config", "motif3_params"),
          "xing4": ("Xing4Config", "xing4_params"),
          "lfm2": ("Lfm2Config", "lfm2_params")}


def _digest(fn, *args):
    import jax

    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()
                          ).hexdigest()[:16]


def program_fingerprints(cfg, params):
    """[step, prefill] of a served model's programs over toy pools."""
    import jax.numpy as jnp

    from paddle_tpu.core.executor import run_block
    from paddle_tpu.serving.kv_cache import PagedKVCache

    model = cfg.served()
    layout = model.cache_layout()
    kv = PagedKVCache(layout, 8, 4 * 32 + 1,
                      4 * 5 + 1 if any(lc.ring for lc in layout) else None,
                      dtype=model.kv_dtype, slots=4)
    pools = kv.make_arrays()
    params = {k: jnp.asarray(v) for k, v in params.items()}
    out = []
    for build, size in ((model.build_step_program, 4),
                        (model.build_prefill_program, 32)):
        program, feeds, fetches = build(size, kv, "none")
        block = program.global_block()

        def run(params, pools, feed, block=block, fetches=fetches):
            env = dict(params)
            env.update(pools)
            env.update(feed)
            run_block(block, env)
            return [env[f] for f in fetches]

        feed = {v: jnp.zeros(tuple(block.vars[v].shape), block.vars[v].dtype)
                for v in feeds}
        out.append(_digest(run, params, pools, feed))
    return out


def train_fingerprint(main):
    """A trainer's whole step (forward, backward, optimizer) from what its
    block reads to everything it writes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.executor import _analyze_block, run_block

    block = main.global_block()
    reads, writes = _analyze_block(block)

    def run(env, step):
        env = dict(env)
        run_block(block, env, step=step)
        return [env[n] for n in writes]

    env = {n: jnp.zeros(tuple(block.vars[n].shape),
                        jax.dtypes.canonicalize_dtype(block.vars[n].dtype))
           for n in reads}
    return _digest(run, env, jnp.zeros((), jnp.int32))


def op_fingerprint(name, ins, attrs):
    from paddle_tpu.core import registry

    return _digest(lambda ins: registry.lookup(name).forward(
        {k: [v] for k, v in ins.items()}, attrs), ins)


@functools.lru_cache(maxsize=None)
def _served_pair(family):
    import importlib

    import paddle_tpu.ops  # noqa: F401

    module = importlib.import_module(f"paddle_tpu.models.{family}")
    config, make = SERVED[family]
    cfg = getattr(module, config)()
    return program_fingerprints(cfg, getattr(module, make)(cfg, 0))


def _served(family, which):
    return _served_pair(family)[("step", "prefill").index(which)]


def _train_bert():
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=2, intermediate_size=64,
                          max_position_embeddings=32)
    return train_fingerprint(bert.build_pretraining_program(
        cfg, seq_len=16, batch_size=2)[0])


def _train_mellum():
    from paddle_tpu.models import mellum

    return train_fingerprint(mellum.build_pretraining_program(
        mellum.MellumConfig(loss_chunk=16), 2, 32)[0])


def _shared_op(label):
    import jax.numpy as jnp

    import paddle_tpu.ops  # noqa: F401

    qk = {"Q": jnp.ones((2, 16, 128)), "K": jnp.ones((2, 16, 32)),
          "QScale": jnp.ones(32), "KScale": jnp.ones(32)}
    routed = {"X": jnp.ones((32, 64)), "RouterW": jnp.ones((64, 16)),
              "SelectBias": jnp.zeros(16), "W1": jnp.ones((4, 64, 32)),
              "W3": jnp.ones((4, 64, 32)), "W2": jnp.ones((4, 32, 64))}
    name, ins, attrs = {
        "rms_norm": (
            "rms_norm", {"X": jnp.ones((2, 16, 64)), "Scale": jnp.ones(64)},
            {"epsilon": 1e-5}),
        "qk_norm_rope.yarn": (
            "qk_norm_rope", qk,
            {"head_dim": 32, "rope": True, "theta": 5e5,
             "yarn_factor": 16.0, "yarn_original_max": 8192,
             "attention_factor": 1.2}),
        "routed_experts.softmax.trainable": (
            "routed_experts", routed,
            {"top_k": 4, "held_lo": 0, "score_func": "softmax",
             "trainable": True}),
        "routed_experts.sigmoid": (
            "routed_experts", routed,
            {"top_k": 4, "held_lo": 0, "route_scale": 2.4})}[label]
    return op_fingerprint(name, ins, attrs)


# line of the table -> its digest, computed when called
CASES = {}
for _family in SERVED:
    for _which in ("step", "prefill"):
        CASES[f"{_family}.{_which}"] = functools.partial(
            _served, _family, _which)
CASES["train.bert"] = _train_bert
CASES["train.mellum"] = _train_mellum
for _label in ("rms_norm", "qk_norm_rope.yarn",
               "routed_experts.softmax.trainable", "routed_experts.sigmoid"):
    CASES[f"op.{_label}"] = functools.partial(_shared_op, _label)


def read_table():
    with open(TABLE) as f:
        return json.load(f)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = parser.add_mutually_exclusive_group(required=True)
    mode.add_argument("--write", action="store_true",
                      help=f"compute every line and rewrite {TABLE}")
    mode.add_argument("--check", action="store_true",
                      help="compare every line with the committed table; "
                           "exit 1 on a difference")
    args = parser.parse_args(argv)
    sys.path.insert(0, REPO)
    now = {name: case() for name, case in CASES.items()}
    if args.write:
        with open(TABLE, "w") as f:
            json.dump(now, f, indent=0)
            f.write("\n")
        return 0
    table = read_table()
    moved = [n for n in sorted(set(now) | set(table))
             if now.get(n) != table.get(n)]
    for n in moved:
        print(f"{n}: table {table.get(n)} now {now.get(n)}")
    return 1 if moved else 0


if __name__ == "__main__":
    sys.exit(main())
