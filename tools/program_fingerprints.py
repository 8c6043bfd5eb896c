"""sha256 of the jaxprs the served families' step and prefill programs trace
to at their default (toy) configurations, and of the shared ops a trainer
reads, so that a PR that grows a shared op's attributes can show that the
other families' programs did not change: run it from the parent's checkout
and from the change's and compare the lines.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python tools/program_fingerprints.py
"""

import hashlib


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


def op_fingerprint(name, ins, attrs):
    from paddle_tpu.core import registry

    return _digest(lambda ins: registry.lookup(name).forward(
        {k: [v] for k, v in ins.items()}, attrs), ins)


def main():
    import jax.numpy as jnp

    import paddle_tpu.ops  # noqa: F401
    from paddle_tpu.models import afmoe, decoder_lm, falcon_h1, kimi_k2

    for module, config, make in (
            (afmoe, "AfmoeConfig", "afmoe_params"),
            (falcon_h1, "FalconH1Config", "falcon_h1_params"),
            (kimi_k2, "KimiK2Config", "kimi_k2_params"),
            (decoder_lm, "DecoderLMConfig", "decoder_lm_params")):
        cfg = getattr(module, config)()
        print(module.__name__.rpartition(".")[2],
              *program_fingerprints(cfg, getattr(module, make)(cfg, 0)))
    qk = {"Q": jnp.ones((2, 16, 128)), "K": jnp.ones((2, 16, 32)),
          "QScale": jnp.ones(32), "KScale": jnp.ones(32)}
    routed = {"X": jnp.ones((32, 64)), "RouterW": jnp.ones((64, 16)),
              "SelectBias": jnp.zeros(16), "W1": jnp.ones((4, 64, 32)),
              "W3": jnp.ones((4, 64, 32)), "W2": jnp.ones((4, 32, 64))}
    for label, name, ins, attrs in (
            ("rms_norm", "rms_norm",
             {"X": jnp.ones((2, 16, 64)), "Scale": jnp.ones(64)},
             {"epsilon": 1e-5}),
            ("qk_norm_rope.yarn", "qk_norm_rope", qk,
             {"head_dim": 32, "rope": True, "theta": 5e5,
              "yarn_factor": 16.0, "yarn_original_max": 8192,
              "attention_factor": 1.2}),
            ("routed_experts.softmax.trainable", "routed_experts", routed,
             {"top_k": 4, "held_lo": 0, "score_func": "softmax",
              "trainable": True}),
            ("routed_experts.sigmoid", "routed_experts", routed,
             {"top_k": 4, "held_lo": 0, "route_scale": 2.4})):
        print(label, op_fingerprint(name, ins, attrs))


if __name__ == "__main__":
    main()
