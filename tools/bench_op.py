"""Config-driven single-op latency benchmark.

Capability mirror of the reference's op benchmark driver
(/root/reference/paddle/fluid/operators/benchmark/op_tester.cc:1 +
op_tester_config.cc — a config file names an op, input shapes/dtypes and
attrs; the tester times repeated runs). TPU twist: ops are timed through
the registry's jitted lowering with the slope-timing method
(tools/perf.py) so each window's fixed cost cancels, and
each iteration is chained through a data dependency so no dispatch can
be elided.

Config format (JSON, one dict per case):
  {"op": "matmul", "inputs": {"X": [512, 1024], "Y": [1024, 1024]},
   "attrs": {}, "dtype": "bfloat16", "grad": true}

`chain` names the input slot the op's first output feeds back into
(defaults to the first input whose shape matches the output). `grad`
times fwd+bwd via jax.grad of sum(out) w.r.t. all float inputs.

Usage:
  python tools/bench_op.py                       # built-in suite
  python tools/bench_op.py --config cases.json   # user cases
  python tools/bench_op.py --op matmul --shapes "X=512x1024,Y=1024x1024"
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tools.perf import sync, time_chain, time_chain_device

# The recorded suite: the hot ops of the BASELINE ladder at bench
# geometry (ERNIE-large / BERT-base / ResNet-50 shapes).
BUILTIN_SUITE = [
    {"op": "matmul", "inputs": {"X": [4096, 1024], "Y": [1024, 1024]},
     "dtype": "bfloat16"},
    {"op": "matmul", "inputs": {"X": [4096, 1024], "Y": [1024, 4096]},
     "dtype": "bfloat16"},
    {"op": "layer_norm", "inputs": {"X": [16384, 1024],
                                    "Scale": [1024], "Bias": [1024]},
     "attrs": {"begin_norm_axis": 1}, "dtype": "bfloat16"},
    # fused_layer_norm (Pallas) is not in the recorded suite: the op is
    # opt-in (models emit plain layer_norm)
    {"op": "softmax", "inputs": {"X": [512, 16, 512]}, "dtype": "bfloat16"},
    {"op": "flash_attention",
     "inputs": {"Q": [32, 16, 512, 64], "K": [32, 16, 512, 64],
                "V": [32, 16, 512, 64]},
     "dtype": "bfloat16", "grad": True},
    {"op": "batch_norm",
     "inputs": {"X": [256, 64, 56, 56], "Scale": [64], "Bias": [64],
                "Mean": [64], "Variance": [64]},
     "dtype": "float32", "chain": "X"},
    {"op": "conv2d", "inputs": {"Input": [256, 64, 56, 56],
                                "Filter": [64, 64, 3, 3]},
     "attrs": {"strides": [1, 1], "paddings": [1, 1]},
     "dtype": "bfloat16", "chain": "Input"},
    {"op": "dropout", "inputs": {"X": [16384, 1024]},
     "attrs": {"dropout_prob": 0.1,
               "dropout_implementation": "upscale_in_train"},
     "dtype": "bfloat16"},
]


def _materialise(case):
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    dtype = case.get("dtype", "float32")
    ins = {}
    for slot, shape in case["inputs"].items():
        a = rng.randn(*shape).astype(np.float32)
        if slot in ("Mean",):
            a = np.zeros(shape, np.float32)
        if slot in ("Variance",):
            a = np.ones(shape, np.float32)
        # stats/scale stay f32 even for bf16 cases (framework convention)
        use_bf16 = dtype == "bfloat16" and slot not in (
            "Scale", "Bias", "Mean", "Variance")
        ins[slot] = jnp.asarray(a, jnp.bfloat16 if use_bf16 else jnp.float32)
    return ins


def _first_out(outs):
    for v in outs.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for x in vals:
            if x is not None:
                return x
    raise ValueError("op produced no outputs")


def bench_case(case):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import registry

    opdef = registry.lookup(case["op"])
    attrs = dict(case.get("attrs", {}))
    ins = _materialise(case)
    chain_slot = case.get("chain")
    if chain_slot is None:
        probe = _first_out(opdef.forward(
            {k: [v] for k, v in ins.items()}, attrs))
        for slot, v in ins.items():
            if tuple(v.shape) == tuple(probe.shape):
                chain_slot = slot
                break
    if chain_slot is None:
        # no shape-compatible input: chain through the first input via a
        # zero-scaled reduction of the output (keeps the data dependence)
        chain_slot = next(iter(ins))

    others = {k: v for k, v in ins.items() if k != chain_slot}

    if case.get("grad"):
        float_slots = sorted(k for k, v in ins.items()
                             if jnp.issubdtype(v.dtype, jnp.floating))

        def loss(vals):
            io = dict(zip(float_slots, vals))
            io.update({k: v for k, v in ins.items() if k not in io})
            out = _first_out(opdef.forward(
                {k: [v] for k, v in io.items()}, attrs))
            return jnp.sum(out.astype(jnp.float32))

        gfn = jax.jit(jax.grad(loss))

        def step(x):
            vals = [x if k == chain_slot else ins[k] for k in float_slots]
            g = gfn(vals)
            return (x + g[float_slots.index(chain_slot)] * 1e-6).astype(
                x.dtype)
    else:
        @jax.jit
        def fwd(x):
            io = dict(others)
            io[chain_slot] = x
            return _first_out(opdef.forward(
                {k: [v] for k, v in io.items()}, attrs))

        def step(x):
            out = fwd(x)
            if out.shape == x.shape:
                return out.astype(x.dtype)
            # 1e-20 (not 0): a *0 chain constant-folds under jit and
            # the op would be DCE'd out of the timing loop entirely
            return (x + jnp.sum(out.astype(jnp.float32)) * 1e-20).astype(
                x.dtype)

    ms = time_chain_device(step, ins[chain_slot])
    return {"op": case["op"],
            "inputs": case["inputs"],
            "dtype": case.get("dtype", "float32"),
            "grad": bool(case.get("grad")),
            "ms": round(ms, 4)}


def parse_shapes(spec):
    ins = {}
    for part in spec.split(","):
        slot, dims = part.split("=")
        ins[slot] = [int(d) for d in dims.split("x")]
    return ins


BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "op_bench_baseline.json")


def _case_key(case):
    shapes = ";".join(f"{s}={'x'.join(map(str, d))}"
                      for s, d in sorted(case["inputs"].items()))
    attrs = json.dumps(case.get("attrs") or {}, sort_keys=True)
    return (f"{case['op']}|{shapes}|{case.get('dtype', 'float32')}"
            f"|{attrs}|{case.get('chain', '')}"
            + ("|grad" if case.get("grad") else ""))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="JSON file with a list of cases")
    ap.add_argument("--op", help="single op name")
    ap.add_argument("--shapes", help='e.g. "X=512x1024,Y=1024x1024"')
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--attrs", default="{}", help="JSON attrs dict")
    ap.add_argument("--grad", action="store_true")
    ap.add_argument("--record", action="store_true",
                    help="write results as the regression baseline "
                         f"({BASELINE_PATH})")
    ap.add_argument("--check", action="store_true",
                    help="FAIL (exit 1) if any recorded op regresses "
                         ">10%% vs the baseline (VERDICT r4 #10)")
    ap.add_argument("--tolerance", type=float, default=0.10)
    args = ap.parse_args()
    if args.op:
        cases = [{"op": args.op, "inputs": parse_shapes(args.shapes),
                  "attrs": json.loads(args.attrs), "dtype": args.dtype,
                  "grad": args.grad}]
    elif args.config:
        with open(args.config) as f:
            cases = json.load(f)
    else:
        cases = BUILTIN_SUITE
    results = {}
    for case in cases:
        try:
            r = bench_case(case)
            results[_case_key(case)] = r["ms"]
            print(json.dumps(r), flush=True)
        except Exception as e:
            print(json.dumps({"op": case.get("op"),
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
    if args.record:
        # slope timing can yield nonsense for
        # sub-noise cases (a NEGATIVE dropout baseline was once
        # recorded): never BASELINE a non-positive duration — it
        # poisons every future --check ratio for that row. The row still
        # appears in --check runs (informational), so a missing-key
        # hard-fail never triggers for noise.
        dropped = {k: v for k, v in results.items() if v <= 0}
        for k in dropped:
            print(json.dumps({"case": k, "ms": dropped[k],
                              "skipped": "non-positive timing (noise "
                              "floor) — not recorded"}), flush=True)
        merged = {k: v for k, v in results.items() if v > 0}
        if (args.op or args.config) and os.path.exists(BASELINE_PATH):
            # a filtered run must MERGE — overwriting would wipe the
            # rest of the recorded suite and the gate would go vacuous
            with open(BASELINE_PATH) as f:
                prev = json.load(f)
            prev.update(merged)
            merged = prev
        with open(BASELINE_PATH, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        print(json.dumps({"recorded": len(results),
                          "total": len(merged), "path": BASELINE_PATH}))
    if args.check:
        if not os.path.exists(BASELINE_PATH):
            print(json.dumps({"check": "NO BASELINE — run --record "
                                       "first"}))
            sys.exit(2)
        with open(BASELINE_PATH) as f:
            base = json.load(f)
        bad, info = [], []
        if not (args.op or args.config):
            # full-suite check: a CURRENT-suite case that failed to run
            # must FAIL, not silently drop out of the gate. Keys only in
            # the baseline (older suite versions, filtered --record
            # additions) are ignored — they'd fail forever otherwise.
            expected = {_case_key(c) for c in cases}
            for k in base:
                if k in expected and k not in results:
                    bad.append({"case": k, "baseline_ms": base[k],
                                "now_ms": None,
                                "regression": "MISSING (errored)"})
        for k, ms in results.items():
            ref = base.get(k)
            if not ref:
                continue
            row = {"case": k, "baseline_ms": ref, "now_ms": ms,
                   "regression": round(ms / ref - 1.0, 3)}
            if ref < 1.0:
                # sub-ms kernels varied >2x run-over-run when the
                # baseline was recorded (dropout 1.23 -> 0.05 ms back to
                # back) — informational only, never a gate failure
                info.append(row)
            elif ms > ref * (1.0 + args.tolerance):
                bad.append(row)
        print(json.dumps({"check": "FAIL" if bad else "PASS",
                          "regressions": bad,
                          "informational_sub_ms": info}))
        if bad:
            sys.exit(1)


if __name__ == "__main__":
    main()
