"""AOT-compile a configuration's programs for a described v5e chip or 2x2 host.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_compile xglm_1p7b
    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_compile ernie_large \
        ring8_b40_s512 4

Run by hand before the first chip call of a cell: what the chip's compiler
refuses (a kernel, a program that does not fit) it refuses here, at no chip
time. For a serving configuration of the `decoder_lm` family it compiles, at
the real sizes, the decode step (ending in the sampler, fed `sampling`) and
every prefill bucket as `DecodeEngine._entry` builds them; for a
training configuration the Executor's step under a traffic file's batch, on
one described chip or on the configuration's mesh over four. It prints each
program's `memory_analysis()`. Nothing runs: a compile that passes is a
compile, never a run, and `memory_analysis()` counts one program, not what
else the process holds on the device.

The kernels' dispatchers ask `jax.default_backend()`, which is the CPU
here, so this script steers them onto their compiled route for the trace.
"""

from __future__ import annotations

import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def report(program: str, what: str, compiled, t0: float):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "compiled_for": f"described v5e:2x2, {what} (not a run)",
        "program": program,
        "compile_s": round(time.perf_counter() - t0, 1),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
        "all_gathers": text.count(" all-gather("),
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(mem.output_size_in_bytes / 1e9, 3),
        "alias_gb": round(mem.alias_size_in_bytes / 1e9, 3),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3)}), flush=True)


def rehearse_train(config: dict, traffic: dict, chips: int, topo) -> None:
    """The Executor's own step program (`Executor._compile`) lowered for
    described devices: state from a startup run on the CPU, as shapes."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as pt
    from paddle_tpu.parallel import axis_rules, create_mesh
    from paddle_tpu.parallel.mesh import set_mesh

    from .generators import train_ring
    from .runners import train

    cfg, main, startup, loss_v = train.build(config, traffic)
    axes = config["runner"].get("mesh_by_chips", {}).get(str(chips))
    mesh = create_mesh(dict(axes), devices=topo.devices[:chips]) \
        if axes else None
    try:
        replicas = int(axes.get("dp", 1)) if axes else 1
        feed = train_ring.make(dict(traffic, ring=1), 0, cfg.vocab_size,
                               cfg.type_vocab_size, replicas)[0]
        exe, scope = pt.Executor(), pt.Scope()
        exe.run(startup, scope=scope, use_compiled=False)
        block = main.global_block()
        feed_names = tuple(sorted(feed))
        batch_axis = axis_rules.batch_mesh_axis(mesh)
        dp = mesh.shape.get(batch_axis) if batch_axis else None
        dp_ok = {n: feed[n].shape[0] % dp == 0 for n in feed_names} \
            if dp else {}
        entry = exe._compile(main, block, feed_names, [loss_v.name], scope,
                             mesh, None, dp_ok)
        chip = None if mesh is not None \
            else SingleDeviceSharding(topo.devices[0])

        def shape(v):
            v = np.asarray(v)
            dtype = {"int64": "int32", "float64": "float32"}.get(
                v.dtype.name, v.dtype.name)
            return jax.ShapeDtypeStruct(v.shape, dtype, sharding=chip)

        state = {n: shape(scope.find_var(n)) for n in entry.state_names}
        ro = {n: shape(scope.find_var(n)) for n in entry.ro_names}
        feeds = {}
        for n in feed_names:      # feeds are cast to their declared dtypes
            declared = np.dtype(block.var(n).dtype).name
            feeds[n] = jax.ShapeDtypeStruct(
                feed[n].shape, {"int64": "int32"}.get(declared, declared),
                sharding=chip)
        t0 = time.perf_counter()
        compiled = entry.jitted.lower(state, ro, feeds,
                                      shape(np.int32(0))).compile()
        report(f"train step, batch {feed['src_ids'].shape[0]}",
               f"mesh {dict(axes)}" if axes else "one chip", compiled, t0)
    finally:
        set_mesh(None)


def main(argv=None) -> int:
    args = list(argv or sys.argv[1:]) or ["xglm_1p7b"]
    name = args[0]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.core.executor import run_block
    from paddle_tpu.models import decoder_lm as dl
    from paddle_tpu.serving.sampling import sample_tokens

    from .families import decoder_lm as family

    with open(os.path.join(CHECKOUT, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    pallas._requested_mode = lambda: "tpu"
    if config["kind"] == "train":
        with open(os.path.join(CHECKOUT, "benchmark", "traffic",
                               args[1] + ".json")) as f:
            traffic = json.load(f)
        rehearse_train(config, traffic, int(args[2]) if len(args) > 2 else 1,
                       topo)
        return 0
    cfg, eng = family.model_config(config), config["engine"]
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=chip)

    params = {n: shape(s) for n, (s, _) in family.param_specs(cfg).items()}
    params["lm_pos_enc"] = shape((cfg.max_seq_len, cfg.d_model))
    pool = (config["kv_pages"], eng["page_size"], cfg.d_model)
    pools = {f"kv_{kv}_{i}": shape(pool)
             for i in range(cfg.n_layers) for kv in "kv"}
    mp = -(-cfg.max_seq_len // eng["page_size"])
    jobs = [("step", eng["max_slots"])] + [("prefill", b)
                                           for b in eng["prefill_buckets"]]
    for phase, bucket in jobs:
        if phase == "step":
            program, _, _ = dl.build_step_program(
                cfg, bucket, config["kv_pages"], eng["page_size"],
                eng["weight_quant"])
            feed = {"tokens": shape((bucket,), jnp.int32),
                    "positions": shape((bucket,), jnp.int32),
                    "page_table": shape((bucket, mp), jnp.int32),
                    "sampling": shape((bucket, 2))}
        else:
            program, _, _ = dl.build_prefill_program(
                cfg, 1, bucket, config["kv_pages"], eng["page_size"],
                eng["weight_quant"])
            feed = {"tokens": shape((1, bucket), jnp.int32),
                    "lengths": shape((1,), jnp.int32),
                    "last_onehot": shape((1, bucket)),
                    "page_table": shape((1, mp), jnp.int32)}
        block = program.global_block()

        def fn(params, pools, feed, block=block, phase=phase):
            env = {**params, **pools, **feed}
            run_block(block, env)
            out = env["logits"]
            if phase == "step":       # [slots] int32 leave the device
                out = sample_tokens(out, feed["sampling"][:, 0],
                                    feed["sampling"][:, 1])
            return out, {n: env[n + "_out"] for n in sorted(pools)}

        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pools, feed).compile()
        report(f"{phase}_b{bucket}", "one chip", compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
