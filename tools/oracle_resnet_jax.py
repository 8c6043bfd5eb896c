"""Plain-JAX ResNet-50 oracle — framework-free train step on the same chip.

Purpose (VERDICT round-3 #1): decide whether the framework's 12.7% MFU
ResNet-50 row is the chip's bandwidth floor or framework overhead. This
file deliberately uses NOTHING from paddle_tpu — raw jax.lax convs, a
hand-rolled momentum update, one jitted donated train step — so its
number is what "a pure-JAX expert implementation" gets on this chip.

Variants (composable flags):
  --stem s2d     space-to-depth stem: input [B,224,224,3]->[B,112,112,12],
                 the 7x7/s2 conv becomes an 8x8/s2-equivalent 4x4/s1 conv
                 on the transformed input (MLPerf TPU ResNet trick).
  --remat        jax.checkpoint each residual block (trade recompute for
                 activation HBM writes).
  --fp32         disable bf16 compute (AMP off).
  --no-bn-stats  skip running-stat updates (isolate their cost).

Methodology identical to tools/bench_models.py: device-resident feed,
donated state, fetch-free windows closed by one loss fetch.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

STAGES = [3, 4, 6, 3]
FILTERS = [64, 128, 256, 512]
MOMENTUM = 0.9
BN_MOMENTUM = 0.9
EPS = 1e-5


# ---------------------------------------------------------------- params

def _conv_w(key, kh, kw, cin, cout):
    fan = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * np.sqrt(
        2.0 / fan)


def init_params(key, s2d=False):
    """Returns (params, bn_state). params: dict name->fp32 array."""
    params, bn = {}, {}
    keys = iter(jax.random.split(key, 256))

    def add_bn(name, c):
        params[name + "/scale"] = jnp.ones((c,), jnp.float32)
        params[name + "/bias"] = jnp.zeros((c,), jnp.float32)
        bn[name + "/mean"] = jnp.zeros((c,), jnp.float32)
        bn[name + "/var"] = jnp.ones((c,), jnp.float32)

    if s2d:
        params["conv1/w"] = _conv_w(next(keys), 4, 4, 12, 64)
    else:
        params["conv1/w"] = _conv_w(next(keys), 7, 7, 3, 64)
    add_bn("conv1", 64)
    cin = 64
    for s, (n, c) in enumerate(zip(STAGES, FILTERS)):
        for i in range(n):
            pre = f"res{s}_{i}"
            cout = c * 4
            if i == 0:
                params[pre + "/sc/w"] = _conv_w(next(keys), 1, 1, cin, cout)
                add_bn(pre + "/sc", cout)
            params[pre + "/c1/w"] = _conv_w(next(keys), 1, 1, cin, c)
            add_bn(pre + "/c1", c)
            params[pre + "/c2/w"] = _conv_w(next(keys), 3, 3, c, c)
            add_bn(pre + "/c2", c)
            params[pre + "/c3/w"] = _conv_w(next(keys), 1, 1, c, cout)
            add_bn(pre + "/c3", cout)
            cin = cout
    params["fc/w"] = jax.random.normal(
        next(keys), (2048, 1000), jnp.float32) * 0.01
    params["fc/b"] = jnp.zeros((1000,), jnp.float32)
    return params, bn


# ---------------------------------------------------------------- forward

def conv(x, w, stride=1, dtype=jnp.bfloat16):
    kh = w.shape[0]
    pad = (kh - 1) // 2
    return lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, params, bn, name, train=True, relu=False, residual=None,
               track=True):
    """BN in fp32 stats, bf16 output. Returns (y, new_running_stats) —
    stats are threaded functionally so jax.checkpoint can wrap blocks
    without closure-mutation tracer leaks."""
    xf = x.astype(jnp.float32)
    stats = {}
    if train:
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean)
        if track:
            stats[name + "/mean"] = (
                BN_MOMENTUM * bn[name + "/mean"] + (1 - BN_MOMENTUM) * mean)
            stats[name + "/var"] = (
                BN_MOMENTUM * bn[name + "/var"] + (1 - BN_MOMENTUM) * var)
    else:
        mean, var = bn[name + "/mean"], bn[name + "/var"]
    scale = params[name + "/scale"] * lax.rsqrt(var + EPS)
    shift = params[name + "/bias"] - mean * scale
    y = xf * scale + shift
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), stats


def block(x, params, bn, pre, stride, dtype, track):
    stats = {}
    if pre + "/sc/w" in params:
        sc = conv(x, params[pre + "/sc/w"], stride, dtype)
        sc, s = batch_norm(sc, params, bn, pre + "/sc", track=track)
        stats.update(s)
    else:
        sc = x
    y = conv(x, params[pre + "/c1/w"], 1, dtype)
    y, s = batch_norm(y, params, bn, pre + "/c1", relu=True, track=track)
    stats.update(s)
    y = conv(y, params[pre + "/c2/w"], stride, dtype)
    y, s = batch_norm(y, params, bn, pre + "/c2", relu=True, track=track)
    stats.update(s)
    y = conv(y, params[pre + "/c3/w"], 1, dtype)
    y, s = batch_norm(y, params, bn, pre + "/c3", relu=True, residual=sc,
                      track=track)
    stats.update(s)
    return y, stats


def space_to_depth(img):
    b, h, w, c = img.shape
    x = img.reshape(b, h // 2, 2, w // 2, 2, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)


def forward(params, bn, img, label, *, s2d, remat, dtype, track_stats=True):
    all_stats = {}
    if s2d:
        # stride is absorbed by the 2x2 space-to-depth: 4x4/s1 conv on
        # [112,112,12] with block pad (2,1) == 7x7/s2/pad3 on [224,224,3]
        # exactly (kernel zero-padded to 8x8 at the top-left)
        x = lax.conv_general_dilated(
            space_to_depth(img).astype(dtype),
            params["conv1/w"].astype(dtype), (1, 1), [(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    else:
        x = conv(img, params["conv1/w"], 2, dtype)
    x, s = batch_norm(x, params, bn, "conv1", relu=True, track=track_stats)
    all_stats.update(s)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])

    def run_block(x, pre, stride):
        f = functools.partial(block, params=params, bn=bn, pre=pre,
                              stride=stride, dtype=dtype, track=track_stats)
        if remat:
            return jax.checkpoint(f)(x)
        return f(x)

    for s, n in enumerate(STAGES):
        for i in range(n):
            stride = 2 if i == 0 and s > 0 else 1
            x, st = run_block(x, f"res{s}_{i}", stride)
            all_stats.update(st)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    logits = x @ params["fc/w"] + params["fc/b"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=1))
    return loss, all_stats


def make_step(*, s2d, remat, dtype, lr=0.1, track_stats=True):
    def step(state, img, label):
        params, mom, bn = state

        def loss_fn(p):
            return forward(p, bn, img, label, s2d=s2d, remat=remat,
                           dtype=dtype, track_stats=track_stats)

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        new_mom = jax.tree_util.tree_map(
            lambda v, g: MOMENTUM * v + g.astype(jnp.float32), mom, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, v: p - lr * v, params, new_mom)
        new_bn = dict(bn)
        if track_stats and stats:
            new_bn.update(stats)
        return (new_params, new_mom, new_bn), loss

    return jax.jit(step, donate_argnums=(0,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--stem", default="conv7", choices=["conv7", "s2d"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--no-bn-stats", action="store_true")
    args = ap.parse_args()

    s2d = args.stem == "s2d"
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    key = jax.random.PRNGKey(0)
    params, bn = init_params(key, s2d=s2d)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = (params, mom, bn)

    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.randn(args.batch, 224, 224, 3).astype(np.float32))
    label = jnp.asarray(rng.randint(0, 1000, (args.batch,)).astype(np.int32))

    step = make_step(s2d=s2d, remat=args.remat, dtype=dtype,
                     track_stats=not args.no_bn_stats)
    t0 = time.perf_counter()
    state, loss = step(state, img, label)
    print(f"first step (compile): {time.perf_counter() - t0:.1f}s "
          f"loss={float(np.asarray(loss)):.4f}", flush=True)
    state, loss = step(state, img, label)
    _ = float(np.asarray(loss))  # sync

    best = float("inf")
    for _ in range(args.windows):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, loss = step(state, img, label)
        lv = float(np.asarray(loss))  # host fetch = the only real sync
        dt = (time.perf_counter() - t0) / args.steps
        best = min(best, dt)
    flops = 3 * 3.8e9 * args.batch
    mfu = flops / best / 197e12
    print(json.dumps({
        "variant": {"stem": args.stem, "remat": args.remat,
                    "fp32": args.fp32, "bn_stats": not args.no_bn_stats},
        "ms_per_step": round(best * 1e3, 2),
        "imgs_per_sec": round(args.batch / best, 1),
        "mfu": round(mfu, 4), "loss": round(lv, 4)}))


if __name__ == "__main__":
    main()
