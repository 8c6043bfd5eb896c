"""Quantized inference END-TO-END (VERDICT r5 #7): train a small
classifier, PTQ-calibrate, convert to the int8 engine
(contrib/slim.convert_to_int8_program) and RUN it through
AnalysisPredictor — top-1 parity against the fp predictor."""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.contrib import slim
from paddle_tpu.inference.predictor import AnalysisConfig, AnalysisPredictor


def _build_and_train(scope, steps=60):
    from paddle_tpu.core import ir, unique_name

    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    unique_name.switch()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.static_data("x", [-1, 16], "float32")
        y = layers.static_data("y", [-1, 1], "int64")
        h = layers.fc(x, 32, act="relu")
        logits = layers.fc(h, 4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        pt.optimizer.AdamOptimizer(0.01).minimize(loss)
    # pure-inference program (no loss ops): rebuild x->logits with the
    # SAME parameter names (fresh unique_name context, same call order)
    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    unique_name.switch()
    infer, _istart = pt.Program(), pt.Program()
    with pt.program_guard(infer, _istart):
        xi = layers.static_data("x", [-1, 16], "float32")
        hi = layers.fc(xi, 32, act="relu")
        ilogits = layers.fc(hi, 4)
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    rng = np.random.RandomState(0)
    centers = rng.randn(4, 16).astype(np.float32) * 2.0
    def batch(n, seed):
        r = np.random.RandomState(seed)
        lab = r.randint(0, 4, (n, 1))
        return {"x": (centers[lab[:, 0]]
                      + r.randn(n, 16).astype(np.float32) * 0.5),
                "y": lab.astype(np.int64)}
    for i in range(steps):
        exe.run(main, feed=batch(64, i), fetch_list=[loss], scope=scope)
    return infer, ilogits, batch


def test_int8_predictor_top1_parity(scope):
    infer, logits, batch = _build_and_train(scope)
    feeds = ["x"]
    fetches = [logits.name]

    # fp32 reference predictor
    fp_pred = AnalysisPredictor(AnalysisConfig(), program=infer,
                                feed_names=feeds, fetch_names=fetches,
                                scope=scope)
    test = batch(256, 999)
    fp_logits, = fp_pred.run({"x": test["x"]})
    fp_top1 = np.argmax(fp_logits, axis=1)
    fp_acc = float(np.mean(fp_top1 == test["y"][:, 0]))
    assert fp_acc > 0.9, f"fp model underfit: {fp_acc}"

    # PTQ calibration for activation scales
    exe = pt.Executor()
    ptq = slim.PostTrainingQuantization(
        exe, infer.clone(for_test=True), feeds, scope,
        [batch(64, 7), batch(64, 8)])
    ptq.quantize()
    assert ptq.calibrated_scales

    # convert a CLEAN copy of the inference program to the int8 engine
    import copy

    int8_scope = pt.Scope()
    int8_scope._vars = {k: np.copy(v) for k, v in scope.items()}
    int8_prog = slim.convert_to_int8_program(
        infer.clone(for_test=True), int8_scope, ptq.calibrated_scales)
    types = [op.type for op in int8_prog.global_block().ops]
    assert "int8_matmul" in types, types
    for name, val in int8_scope.items():
        if name.endswith("@int8_scale"):
            base = name[:-len("@int8_scale")]
            assert np.asarray(int8_scope.find_var(base)).dtype == np.int8

    q_pred = AnalysisPredictor(AnalysisConfig(), program=int8_prog,
                               feed_names=feeds, fetch_names=fetches,
                               scope=int8_scope)
    q_logits, = q_pred.run({"x": test["x"]})
    q_top1 = np.argmax(q_logits, axis=1)
    agree = float(np.mean(q_top1 == fp_top1))
    assert agree >= 0.97, f"int8 top-1 agreement {agree}"
    q_acc = float(np.mean(q_top1 == test["y"][:, 0]))
    assert q_acc > 0.85, q_acc


def test_weight_only_path(scope):
    """Without activation scales every matmul-family op takes the
    weight-only ``int8_matmul`` route (NO act_scale attr — the lowering
    the Pallas int8 MXU GEMM kernel sits behind; before this the
    weight-only convert emitted dequantize_weight + stock matmul and
    the kernel never fired) and still matches closely."""
    infer, logits, batch = _build_and_train(scope, steps=30)
    fp = AnalysisPredictor(AnalysisConfig(), program=infer,
                           feed_names=["x"], fetch_names=[logits.name],
                           scope=scope)
    test = batch(128, 555)
    fp_logits, = fp.run({"x": test["x"]})

    int8_scope = pt.Scope()
    int8_scope._vars = {k: np.copy(v) for k, v in scope.items()}
    prog = slim.convert_to_int8_program(infer.clone(for_test=True),
                                        int8_scope, act_scales=None)
    mm_ops = [op for op in prog.global_block().ops
              if op.type == "int8_matmul"]
    assert len(mm_ops) == 2, \
        [op.type for op in prog.global_block().ops]
    assert all(not op.attrs.get("act_scale") for op in mm_ops)
    q = AnalysisPredictor(AnalysisConfig(), program=prog,
                          feed_names=["x"], fetch_names=[logits.name],
                          scope=int8_scope)
    q_logits, = q.run({"x": test["x"]})
    agree = np.mean(np.argmax(q_logits, 1) == np.argmax(fp_logits, 1))
    assert agree >= 0.98, agree

    # regression: numeric parity with the OLD weight-only lowering
    # (dequantize_weight + stock matmul — dequant-then-dot instead of
    # the kernel's dot-then-scale; same math, different rounding order,
    # pinned within float tolerance)
    def old_lowering(x):
        h = x
        for i, op in enumerate(mm_ops):
            w8 = np.asarray(int8_scope.find_var(op.inputs["Y"][0]))
            sc = np.asarray(int8_scope.find_var(op.inputs["YScale"][0]))
            b = np.asarray(int8_scope.find_var(f"fc_{i}.b_0"))
            h = h @ (w8.astype(np.float32) * sc[None, :]) + b
            if i == 0:
                h = np.maximum(h, 0.0)
        return h

    want = old_lowering(test["x"].astype(np.float32))
    np.testing.assert_allclose(np.asarray(q_logits), want,
                               rtol=2e-4, atol=2e-4)


def test_weight_tied_param_stays_fp(scope):
    """A parameter read by BOTH a quantizable matmul and a non-quantized
    consumer (weight tying, e.g. an embedding doubling as the output
    projection) must NOT be overwritten with int8 in the scope."""
    from paddle_tpu.core import ir, unique_name

    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    unique_name.switch()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.static_data("ids", [-1, 3], "int64")
        emb = layers.embedding(ids, [50, 8],
                               param_attr=pt.ParamAttr(name="tied_w"))
        pooled = layers.reduce_mean(emb, dim=[1])          # [B, 8]
        w = main.global_block().var("tied_w")              # [50, 8]
        logits = layers.matmul(pooled, w, transpose_y=True)
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    feed = {"ids": np.random.RandomState(0).randint(0, 50, (4, 3))
            .astype(np.int64)}
    ref, = exe.run(main, feed=feed, fetch_list=[logits], scope=scope)

    prog = slim.convert_to_int8_program(main, scope, act_scales=None)
    assert np.asarray(scope.find_var("tied_w")).dtype == np.float32
    got, = exe.run(prog, feed=feed, fetch_list=[logits], scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5)


def test_weight_only_conv_reads_through_dequantize_weight(scope):
    """A quantizable op outside the matmul family (conv2d) keeps its
    stock lowering and reads its int8 weight through `dequantize_weight`:
    the converted program runs and answers what the fp program answers
    with the same weights rounded to int8."""
    from paddle_tpu.core import ir, unique_name

    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    unique_name.switch()
    infer, startup = pt.Program(), pt.Program()
    with pt.program_guard(infer, startup):
        x = layers.static_data("x", [-1, 3, 8, 8], "float32")
        h = layers.conv2d(x, num_filters=4, filter_size=3, act="relu")
        logits = layers.reduce_mean(h, dim=[2, 3])
    pt.Executor().run(startup, scope=scope, use_compiled=False)
    xs = np.random.RandomState(3).randn(6, 3, 8, 8).astype(np.float32)
    fp, = AnalysisPredictor(AnalysisConfig(), program=infer,
                            feed_names=["x"], fetch_names=[logits.name],
                            scope=scope).run({"x": xs})

    int8_scope = pt.Scope()
    int8_scope._vars = {k: np.copy(v) for k, v in scope.items()}
    prog = slim.convert_to_int8_program(infer.clone(for_test=True),
                                        int8_scope, act_scales=None)
    types = [op.type for op in prog.global_block().ops]
    assert "dequantize_weight" in types and "conv2d" in types, types
    for op in prog.global_block().ops:
        if op.type == "dequantize_weight":
            w8 = np.asarray(int8_scope.find_var(op.inputs["X"][0]))
            assert w8.dtype == np.int8
    q, = AnalysisPredictor(AnalysisConfig(), program=prog,
                           feed_names=["x"], fetch_names=[logits.name],
                           scope=int8_scope).run({"x": xs})
    # int8 weights carry 1/254 of a channel's range: outputs of order 1
    # move in the second decimal, never in the first
    assert np.abs(q - fp).max() < 0.05 * max(1.0, np.abs(fp).max())
    assert np.abs(q - fp).max() > 0.0
