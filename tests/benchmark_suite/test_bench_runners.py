"""Each runner end to end at toy width on the CPU, through the test-only
entry that skips the device refusal; the command itself refuses a CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import reference, run
from benchmark.families import decoder_lm as family

from . import toy

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


def check_line(out, expected_metrics):
    assert LINE_KEYS <= set(out)
    json.dumps(out)                      # the last line is plain JSON
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert expected_metrics <= set(out["metrics"])
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])


@pytest.mark.parametrize("cell, metrics", [
    ("toy_train", {"train_tokens_per_s", "setup_s"}),
    ("toy_closed", {"serve_tokens_per_s", "tpot_p90_ms", "setup_s"}),
    ("toy_open", {"serve_tokens_per_s", "tpot_p90_ms", "setup_s"}),
])
def test_an_end_to_end_run_prints_the_contracts_line(root, cell, metrics,
                                                     capsys):
    out = run.run_cell(root, cell, seed=2 ** 31 + 11, seconds=1.5,
                       trace=False, require_platform=None)
    check_line(out, metrics)
    assert set(out["metrics"]) == metrics
    assert "breakdown" not in out
    # stderr ends with each number the check compared, beside its limit
    last = capsys.readouterr().err.strip().splitlines()[-3:]
    assert all(line.startswith("compared ") and "(limit " in line
               for line in last), last


@pytest.mark.parametrize("cell, metrics", [
    ("toy_train", {"step_ms_p50.train", "host_dispatch_ms_p50.train",
                   "first_step_s.train", "device_idle_share.train",
                   "compile_cache_misses"}),
    ("toy_open", {"decode_step_ms_p50", "prefill_ms_p50", "tpot_p50_ms",
                  "batch_occupancy_avg", "completed_requests_per_s",
                  "device_idle_share.serve"}),
])
def test_a_traced_run_reports_layer_metrics_and_a_breakdown(root, cell,
                                                            metrics):
    out = run.run_cell(root, cell, seed=3, seconds=2.0, trace=True,
                       require_platform=None)
    check_line(out, metrics)
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    for part in ("device_ops", "idle_gaps"):
        rows = out["breakdown"][part]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


@pytest.mark.parametrize("planted", [False, True])
def test_a_four_chip_cell_trains_and_is_checked_on_the_configurations_mesh(
        tmp_path, monkeypatch, capsys, planted):
    """The path a dp2 x mp2 cell takes, on four of the suite's virtual CPU
    devices: the mesh from `mesh_by_chips`, the batch times dp, and the
    reference check through the partitioned step, not beside it. Planted:
    every step on the mesh sees the first replica's rows twice, as a
    gradient averaged over one replica would; the check's gradients then
    leave the reference's and the run is not correct."""
    import jax

    import paddle_tpu as pt

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    root = toy.keep_cells(
        toy.make_root(str(tmp_path), chips={"toy_train": 4},
                      mesh={"4": {"dp": 2, "mp": 2}}),
        {"toy_train": f"mesh_train_{int(planted)}"})
    seen = []
    run_step = pt.Executor.run

    def spy(self, program=None, feed=None, fetch_list=None, **kw):
        if kw.get("mesh") is not None:
            seen.append([str(getattr(v, "name", v)) for v in fetch_list])
            if planted:
                half = len(feed["src_ids"]) // 2
                feed = {k: np.concatenate([v[:half], v[:half]])
                        for k, v in feed.items()}
        return run_step(self, program, feed=feed, fetch_list=fetch_list, **kw)

    monkeypatch.setattr(pt.Executor, "run", spy)
    out = run.run_cell(root, f"mesh_train_{int(planted)}", seed=5,
                       seconds=1.0, trace=False, require_platform=None)
    assert out["device"]["count"] == 4
    # the first step on the mesh is the check's: it fetches the gradients
    assert seen[0][1:] == [n + "@GRAD" for n in
                           toy.TRAIN_CONFIG["check"]["grads"]]
    err = capsys.readouterr().err
    assert "compared grad_rel_err.layer_0_attn_q_w = " in err
    if planted:
        assert out["correct"] is False
        assert "incorrect: gradient of " in err
    else:
        check_line(out, {"train_tokens_per_s", "setup_s"})
    from paddle_tpu.parallel.mesh import get_mesh

    assert get_mesh() is None            # the runner leaves no global mesh


def test_a_compile_inside_the_window_makes_the_run_incorrect(root,
                                                             monkeypatch,
                                                             capsys):
    from benchmark.common import CompileWatch

    monkeypatch.setattr(CompileWatch, "since_mark", lambda self: 1)
    out = run.run_cell(root, "toy_train", seed=1, seconds=0.5, trace=False,
                       require_platform=None)
    assert out["correct"] is False
    err = capsys.readouterr().err.strip().splitlines()
    assert "compared compiles_in_window = 1 (limit 0)" in err
    assert err[-1].startswith("incorrect: compiled inside the window")


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "ernie_large_s512_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=toy.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == run.NO_DEVICE_EXIT
    assert '"correct"' not in proc.stdout
    assert "no result" in proc.stderr


@pytest.mark.parametrize("given", [None, "/somewhere/the/machine/keeps"])
def test_the_compile_cache_keeps_its_place_and_loses_its_size_cap(
        monkeypatch, given):
    """A machine's directory is taken as given, else one inside the
    checkout; a machine's size cap is lifted (one cell's programs can be
    larger than it, and then no run ever finds one in the cache)."""
    if given:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_MAX_SIZE", "201326592")
    for name in ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                 "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "TPU_LOG_DIR"):
        monkeypatch.setenv(name, os.environ.get(name, "unset"))
    run._prepare_environment()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == (
        given or os.path.join(toy.REPO, ".jax_cache"))
    assert os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] == "-1"


def toy_lm():
    return family.model_config(toy.SERVE_CONFIG)


def test_device_made_parameters_match_decoder_lm_params():
    from paddle_tpu.models import decoder_lm as dl

    cfg = toy_lm()
    ours = family.make_params(cfg, 2 ** 31 + 11)
    theirs = dl.decoder_lm_params(cfg, 0)
    assert set(ours) == set(theirs)
    for name, v in theirs.items():
        assert ours[name].shape == v.shape, name
        assert ours[name].dtype == v.dtype, name
    assert np.array_equal(np.asarray(ours["lm_pos_enc"]), theirs["lm_pos_enc"])
    for name in ("lm_l0_q_b", "lm_l1_ln2_bias"):
        assert not np.asarray(ours[name]).any()
    assert np.asarray(ours["lm_l0_ln1_scale"]).min() == 1.0
    w = np.asarray(ours["lm_l1_fc1_w"])
    assert abs(w.std() - cfg.d_model ** -0.5) < 0.02
    again = family.make_params(cfg, 2 ** 31 + 11)
    assert np.array_equal(w, np.asarray(again["lm_l1_fc1_w"]))


def test_the_reference_agrees_with_the_engines_greedy_tokens():
    cfg = toy_lm()
    params = family.make_params(cfg, 5)
    engine = family.make_engine(cfg, params, toy.SERVE_CONFIG,
                                toy.TRAFFIC["toy_closed"])
    engine.start(warmup=False)
    try:
        rng = np.random.RandomState(0)
        for n in (4, 11, 23):
            prompt = rng.randint(3, cfg.vocab_size, n)
            chosen = engine.generate(prompt, max_new_tokens=6,
                                     stop_at_eos=False, timeout=120)
            ok, gap, gaps = reference.check_greedy(
                params, cfg.n_layers, cfg.n_head, prompt, chosen, pad_to=32)
            assert ok and gap <= 1e-3 and len(gaps) == 6, (n, gap)
            wrong = (np.asarray(chosen) + 1) % cfg.vocab_size
            assert not reference.check_greedy(
                params, cfg.n_layers, cfg.n_head, prompt, wrong,
                pad_to=32)[0]
    finally:
        engine.close(drain=False, timeout=30)


def test_the_references_rows_are_rows_of_its_full_logits():
    cfg = toy_lm()
    params = family.make_params(cfg, 9)
    tokens = np.random.RandomState(1).randint(3, cfg.vocab_size, 16)
    full = np.asarray(reference.decoder_logits(
        params, tokens, cfg.n_layers, cfg.n_head))
    part = np.asarray(reference.decoder_logits(
        params, tokens, cfg.n_layers, cfg.n_head, 5, 4))
    assert part.shape == (4, cfg.vocab_size)
    assert np.allclose(part, full[5:9], atol=1e-5)


@pytest.fixture(scope="module")
def toy_step():
    """One Executor step of the toy BERT with dropout off: the parameters
    before it, its batch, its loss and two of its gradients."""
    import paddle_tpu as pt
    from benchmark.generators import train_ring
    from benchmark.runners import train

    traffic = toy.TRAFFIC["toy_ring"]
    cfg, main, startup, loss_v = train.build(
        toy.TRAIN_CONFIG, traffic, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
        initializer_range=0.15)   # at width 32, 0.02 makes a layer nearly
    #                               the identity and blind to a missing one
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    batch = train_ring.batch(cfg.vocab_size, cfg.type_vocab_size, 3,
                             traffic["seq_len"], 2 ** 31 + 11,
                             traffic["max_predictions_per_seq"])
    batch["input_mask"][1, 11:] = 0.0        # padding, which the ring has not
    params = {p.name: np.array(scope.find_var(p.name))
              for p in main.all_parameters()}
    names = ["layer_0_attn_q_w", "layer_1_ffn2_b", "word_embedding"]
    loss, *grads = exe.run(main, feed=batch, scope=scope,
                           fetch_list=[loss_v] + [n + "@GRAD" for n in names])
    return (cfg, params, batch, float(np.asarray(loss).reshape(-1)[0]),
            {n: np.asarray(g) for n, g in zip(names, grads)})


def test_the_bert_reference_agrees_with_the_executors_step(toy_step):
    cfg, params, batch, loss, grads = toy_step
    notes, facts = reference.check_train_step(
        params, batch, cfg.num_hidden_layers, cfg.num_attention_heads, loss,
        grads)
    assert notes == []
    assert abs(facts["loss"] - facts["reference_loss"]) < 1e-5
    assert max(facts["grad_rel_err"].values()) < 1e-3


@pytest.mark.parametrize("what, said", [
    ("loss", "step loss"),
    ("scaled", "gradient of layer_0_attn_q_w"),
    ("shallower", "gradient of layer_0_attn_q_w"),
])
def test_the_bert_reference_refuses_a_step_that_is_off(toy_step, what, said):
    cfg, params, batch, loss, grads = toy_step
    layers = cfg.num_hidden_layers
    if what == "loss":
        loss *= 1.01
    elif what == "scaled":           # a gradient 10% too large
        grads = dict(grads, layer_0_attn_q_w=1.1 * grads["layer_0_attn_q_w"])
    else:                            # the reference leaves a layer out, as
        layers -= 1                  # a program that skipped one would
        grads = {"layer_0_attn_q_w": grads["layer_0_attn_q_w"]}
    notes, _ = reference.check_train_step(
        params, batch, layers, cfg.num_attention_heads, loss, grads)
    assert said in " | ".join(notes)


@pytest.mark.parametrize("change, said", [
    (dict(kv_pages=64), "hold no"),
    (dict(max_context=32), "over the configuration's max_context"),
])
def test_a_pool_or_a_mix_that_does_not_fit_the_context_is_refused(change,
                                                                  said):
    with pytest.raises(ValueError, match=said):
        family.engine_config(dict(toy.SERVE_CONFIG, **change),
                             toy.TRAFFIC["toy_closed"])


def test_memory_in_the_window_is_read_apart_from_the_peak():
    from types import SimpleNamespace

    from benchmark.manifest import Manifest

    read = Manifest(toy.REPO).reader("window_hbm_gb.serve")
    ctx = SimpleNamespace(kind="serve", window_hbm_bytes=10_593_048_064,
                          peak_hbm_bytes=13_813_715_968)
    assert read(ctx) == pytest.approx(10.593048064)
    assert read(SimpleNamespace(kind="serve", window_hbm_bytes=None)) is None
    assert read(SimpleNamespace(kind="train", window_hbm_bytes=1)) is None
