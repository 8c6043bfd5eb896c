"""Dump + audit the optimized HLO of the north-star ERNIE step.

Builds the bench-identical program, compiles the whole-block step the
same way the executor does, and reports every dot/convolution in the
optimized module with shape, dtype, and FLOPs — split into forward vs
backward (HLO ops carry no roles, so the split is by operand-shape
heuristics printed per dot for manual attribution) — plus totals by
dtype so fp32 dots (half-rate on the MXU) stand out.

Usage: python tools/audit_hlo.py [--batch 34] [--out /tmp/ernie_hlo.txt]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compiled_step(batch):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import bert

    cfg = bert.ernie_large()
    cfg.dtype = "bfloat16"
    cfg.use_flash_attention = True
    main, startup, _feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=512, optimizer_name="adamw",
        max_predictions_per_seq=80)
    loss_v = fetches["loss"]
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    feed = {k: jnp.asarray(v) for k, v in bert.synthetic_pretraining_batch(
        cfg, batch, 512, seed=0, max_predictions_per_seq=80).items()}
    exe.run(main, feed=feed, fetch_list=[loss_v], scope=scope)
    (entry,) = exe._cache.values()
    state = {n: scope.find_var(n) for n in entry.state_names}
    ro = {n: scope.find_var(n) for n in entry.ro_names}
    step = scope.find_var("@STEP_COUNTER@")
    lowered = entry.jitted.lower(state, ro, feed, step)
    return lowered.compile()


DOT_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\][^=]*"
    r"(dot|convolution)\(")


def shape_of(tok):
    m = re.match(r"(\w+)\[([\d,]*)\]", tok)
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def audit(txt):
    """Parse dots/convs out of optimized HLO text (they appear inside
    fusion computations as plain instructions)."""
    rows = []
    for line in txt.splitlines():
        m = DOT_RE.match(line)
        if not m:
            continue
        name, odt, oshape, kind = m.groups()
        odims = tuple(int(d) for d in oshape.split(",") if d)
        # operand types: grab the first two type[shape] tokens in the args
        args = line.split("(", 1)[1]
        opnds = re.findall(r"(\w+\[[\d,]*\])", args)[:2]
        ishapes = [shape_of(t) for t in opnds]
        dnums = re.search(r"contracting_dims=\{([\d,]*)\}", line)
        # FLOPs: 2 * prod(out) * contraction size (from lhs)
        flops = 0
        try:
            lhs_dt, lhs = ishapes[0]
            cd = [int(d) for d in dnums.group(1).split(",")] if dnums else []
            k = 1
            for d in cd:
                k *= lhs[d]
            out_n = 1
            for d in odims:
                out_n *= d
            flops = 2 * out_n * k
        except Exception:
            pass
        ins = [f"{dt}{list(sh)}" for dt, sh in ishapes]
        while len(ins) < 2:
            ins.append("?")
        rows.append({
            "name": name, "kind": kind, "out": f"{odt}{list(odims)}",
            "in": ins, "gflops": round(flops / 1e9, 2),
        })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=34)
    ap.add_argument("--out", default="/tmp/ernie_hlo.txt")
    args = ap.parse_args()

    compiled = compiled_step(args.batch)
    txt = compiled.as_text()
    with open(args.out, "w") as f:
        f.write(txt)
    print(f"wrote {len(txt)} bytes to {args.out}", file=sys.stderr)

    try:
        # one place knows XLA's cost_analysis() shape (list-vs-dict, the
        # 'bytes accessed' key): core/costmodel.py — CLI output keeps the
        # raw XLA key names
        from paddle_tpu.core.costmodel import normalize_cost_analysis

        ca = normalize_cost_analysis(compiled.cost_analysis())
        print(json.dumps({xla_key: ca[k] for xla_key, k in
                          (("flops", "flops"),
                           ("bytes accessed", "bytes_accessed"),
                           ("transcendentals", "transcendentals"))
                          if k in ca}), file=sys.stderr)
    except Exception as e:
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    rows = audit(txt)
    total = sum(r["gflops"] for r in rows)
    by_dtype = collections.Counter()
    for r in rows:
        by_dtype[r["out"].split("[")[0]] += r["gflops"]
    # group identical shapes
    groups = collections.Counter()
    gf = collections.defaultdict(float)
    for r in rows:
        key = (r["kind"], r["out"], tuple(r["in"]))
        groups[key] += 1
        gf[key] += r["gflops"]
    print(f"\n{len(rows)} dots/convs, {total:.0f} GFLOP total")
    print("by output dtype (GFLOP):",
          {k: round(v, 1) for k, v in by_dtype.items()})
    print(f"\n{'n':>3} {'GFLOP':>8}  shape")
    for key, n in sorted(groups.items(), key=lambda kv: -gf[kv[0]]):
        kind, out, ins = key
        print(f"{n:>3} {gf[key]:>8.1f}  {kind} {ins[0]} x {ins[1]} -> {out}")


if __name__ == "__main__":
    main()
