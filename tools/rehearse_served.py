"""AOT-compile a served configuration's decode step and prefill buckets for a
described v5e chip, whatever its family (benchmark/rehearse_compile.py does
the `decoder_lm` family and the trainers).

    JAX_PLATFORMS=cpu python tools/rehearse_served.py motif3_beta_dp_ep8 \
        [step prefill_16384 ...]

By hand, before the first chip call of a cell: what the chip's compiler
refuses (a kernel, a program that does not fit beside the weights and the
pools) it refuses here, at no chip time. The programs are the model's own
builders' (`cfg.served()`), fed shapes alone: parameters by the model's
`param_specs`, pools by the engine's `PagedKVCache`, the step ending in the
sampler. One JSON line a program: its `memory_analysis()` (arguments hold
the weights and the pools; `temp_gb` is what the program needs beside
them) and how many Mosaic kernels it carries. Nothing runs.
"""

import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    args = list(argv or sys.argv[1:])
    name, only = args[0], set(args[1:])
    sys.path.insert(0, REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu.ops  # noqa: F401
    import paddle_tpu.ops.pallas as pallas
    from benchmark.manifest import Manifest
    from paddle_tpu.core.executor import run_block
    from paddle_tpu.serving.kv_cache import PagedKVCache, state_array_names
    from paddle_tpu.serving.sampling import sample_tokens

    man = Manifest(REPO)
    config = man.config_doc(name)
    family = man.family(config["family"])
    cfg, eng = family.model_config(config), config["engine"]
    specs = importlib.import_module(
        f"paddle_tpu.models.{config['family']}").param_specs(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    pallas._requested_mode = lambda: "tpu"
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=chip)

    params = {n: shape(s, d) for n, (s, _, d) in specs.items()}
    model = cfg.served()
    kv = PagedKVCache(model.cache_layout(), eng["page_size"],
                      config["kv_pages"], config.get("kv_ring_pages"),
                      dtype=model.kv_dtype, slots=eng["max_slots"])
    pools = {}
    for pool in filter(None, (kv.context, kv.ring)):
        for i, dim, lat in zip(pool.layers, pool.kv_dims, pool.latent):
            for n in (f"kv_c_{i}",) if lat else (f"kv_k_{i}", f"kv_v_{i}"):
                pools[n] = shape((pool.num_pages, pool.page_size, dim),
                                 pool.dtype)
    for i in kv.state_layers:       # what a slot keeps beside its pages
        lc = kv.layout[i]
        *state, tail = state_array_names(i, lc.tail_only)
        for n in state:
            pools[n] = shape((kv.state_slots,) + tuple(lc.ssm_state),
                             lc.state_dtype)
        pools[tail] = shape((kv.state_slots,) + tuple(lc.conv_tail),
                            kv.context.dtype)
    held = sum(v.size * v.dtype.itemsize
               for v in list(params.values()) + list(pools.values()))
    print(json.dumps({"config": name, "weights_and_pools_gb":
                      round(held / 1e9, 3)}), flush=True)
    jobs = [("step", eng["max_slots"])] + [("prefill", b)
                                           for b in eng["prefill_buckets"]]
    for phase, bucket in jobs:
        label = phase if phase == "step" else f"prefill_{bucket}"
        if only and label not in only:
            continue
        build = model.build_step_program if phase == "step" \
            else model.build_prefill_program
        program, feeds, fetches = build(bucket, kv, eng["weight_quant"])
        block = program.global_block()
        feed = {f: shape(block.vars[f].shape, block.vars[f].dtype)
                for f in feeds}
        if phase == "step":
            feed["sampling"] = shape((bucket, 2), jnp.float32)
        if model.draft and phase == "step":
            fn, args = drafting_step(model, kv, eng, cfg, block, bucket,
                                     shape)
            report(label, jax.jit(fn, donate_argnums=(1, 4)),
                   (params, pools) + args)
            continue

        def fn(params, pools, feed, block=block, phase=phase):
            env = {**params, **pools,
                   **{k: v for k, v in feed.items() if k != "sampling"}}
            run_block(block, env)
            out = env["logits"]
            if phase == "step":       # [slots] int32 leave the device
                out = sample_tokens(out, feed["sampling"][:, 0],
                                    feed["sampling"][:, 1])
            return out, {n: env[n + "_out"] for n in sorted(pools)}

        report(label, jax.jit(fn, donate_argnums=(1,)),
               (params, pools, feed))
    return 0


def report(label, jitted, args):
    """Compile and print one program's line."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "compiled_for": "described v5e:2x2, one chip (not a run)",
        "program": label,
        "compile_s": round(time.perf_counter() - t0, 1),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "alias_gb": round(mem.alias_size_in_bytes / 1e9, 3),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
        "total_gb": round((mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes
                           + mem.temp_size_in_bytes) / 1e9, 3)}),
        flush=True)


def drafting_step(model, kv, eng, cfg, block, bucket, shape):
    """The engine's own step of a model with a draft module
    (serving/decode.py `draft_step`: the held layers on two positions a
    slot, the acceptance rule, the module, the next draft) and the shapes
    of its arguments behind the parameters and the pools."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.draft_tail import q_state_shape
    from paddle_tpu.serving.decode import draft_step
    from paddle_tpu.serving.served_model import DRAFT_SPARE_TOKENS

    slots = eng["max_slots"]
    mp = -(-(cfg.max_seq_len + DRAFT_SPARE_TOKENS) // eng["page_size"])
    feed = {"tokens": shape((bucket,), jnp.int32),
            "positions": shape((bucket,), jnp.int32),
            "page_table": shape((bucket, mp), jnp.int32),
            "sampling": shape((bucket, 5), jnp.float32),
            "carry": shape((bucket, 2), jnp.int32)}
    spec = {"pos": shape((slots,), jnp.int32),
            "draft": shape((slots,), jnp.int32),
            "q": shape(q_state_shape(slots, cfg.vocab_size), jnp.float32),
            "hidden": shape((slots, cfg.hidden_size), jnp.float32)}
    return (draft_step(model, kv, eng["weight_quant"], bucket, block),
            (feed, shape((slots,), jnp.int32), spec))


if __name__ == "__main__":
    sys.exit(main())
