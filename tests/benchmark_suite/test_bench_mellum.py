"""The Mellum training cell: its entries, its configuration's cut and
arithmetic, the `train_lm` runner on a toy cell added by files alone, its
readers by hand, and the check's lower-precision control."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_mellum as f
from benchmark import reference_mellum, run
from benchmark.manifest import Manifest
from benchmark.runners import result, train_lm

from . import toy

REAL_CELL = "mellum2_12b_tp4ep4_s8192_train"
REAL_CONFIG = "mellum2_12b_tp4ep4"
CELL = "mellum_toy_train"
JOINED = ("train_tokens_per_s", "first_step_s.train", "step_ms_p50.train",
          "host_dispatch_ms_p50.train", "train_step_roofline",
          "device_idle_share.train", "peak_hbm_gb.train")
NEW_METRICS = {"flash_window_fwd_roofline": "kernels and step program",
               "flash_window_bwd_roofline": "kernels and step program",
               "routed_experts_train_roofline": "routed experts",
               "moe_rows_per_held_expert.train": "routed experts"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
with open(os.path.join(toy.REPO, "benchmark", "configs",
                       REAL_CONFIG + ".json")) as _f:
    MELLUM = json.load(_f)

TOY_MELLUM = {
    "name": "toy_mellum", "kind": "train_lm",
    "source": "none: a test preset", "reduced": [],
    "assumed": {"all": "a test preset"}, "departures": ["a test preset"],
    "hidden_size": 32, "head_dim": 8, "moe_intermediate_size": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "sliding_window": 8, "rms_norm_eps": 1e-6, "vocab_size": 61,
    "layer_types": ["sliding_attention", "full_attention"] * 2,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "layers_held": [0, 1], "q_heads_held": 4, "kv_heads_held": 2,
    "experts_held": [0, 4],
    "runner": {"dtype": "float32", "optimizer": "adamw", "lr": 1e-3,
               "loss_chunk": 8},
    "check": {"grads": ["ml_tok_emb", "ml_l0_router_w", "ml_l1_ex_w2",
                        "ml_l1_k_w"],
              "expert_rows": {"ml_l1_ex_w2": 3}}}
TOY_TRAFFIC = {"generator": "lm_ring", "batch_per_replica": 2, "seq_len": 16,
               "ring": 3, "warmup_steps": 4, "loss_every": 2}


@pytest.fixture(scope="module")
def mellum_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy `train_lm`
    cell that reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("mellum_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_mellum.json"), "w") as fh:
        json.dump(TOY_MELLUM, fh)
    with open(os.path.join(data, "traffic", "toy_lm_ring.json"), "w") as fh:
        json.dump(TOY_TRAFFIC, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "toy_mellum", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_mellum.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_mellum", "traffic": "toy_lm_ring",
        "chips": 1, "why": "toy"})
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if m["name"] in JOINED or m["name"] in NEW_METRICS:
                m["workloads"] = sorted(set(m["workloads"]) | {CELL})
    with open(path, "w") as fh:
        json.dump(doc, fh)
    assert Manifest(root).problems() == []
    return root


def holds(man):
    cell = man.cell(REAL_CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "lm_ring8_b2_s8192", REAL_CONFIG)
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "compile_cache_misses"} <= reported
    assert not {"serve_tokens_per_s", "tpot_p90_ms"} & reported
    assert all(m["moves"] in ("train_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    # the new metrics came with this cell, wherever they stand now
    for name, layer in NEW_METRICS.items():
        entry = toy.entry(man, "per_layer", name)
        assert REAL_CELL in entry["workloads"]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["layer"] == layer
        assert (entry["unit"] == "%") == name.endswith("_roofline")
    assert man.config_doc(REAL_CONFIG)["kind"] == "train_lm"


def test_the_real_manifest_is_sound_with_the_mellum_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)
    entry = toy.entry(man, "configs", REAL_CONFIG)
    assert entry["reduced"] == MELLUM["reduced"] == [
        "num_hidden_layers", "q_heads_held", "kv_heads_held", "experts_held",
        "vocab_size"]
    assert entry["source"] == MELLUM["source"]


def test_the_traffic_is_the_mix_the_issue_states():
    assert Manifest(toy.REPO).traffic_doc("lm_ring8_b2_s8192") == {
        "generator": "lm_ring", "batch_per_replica": 2, "seq_len": 8192,
        "ring": 8, "warmup_steps": 12, "loss_every": 10}


def test_the_configuration_carries_every_published_number():
    """The catalog row's `config`, key by key; only what `reduced` names
    differs, and no width."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "use_sliding_window": True}
    for key, value in published.items():
        assert MELLUM[key] == value, key
    assert MELLUM["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 7
    assert MELLUM["mlp_layer_types"] == ["sparse"] * 28
    assert MELLUM["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert (MELLUM["num_hidden_layers"], MELLUM["vocab_size"]) == (4, 24576)
    assert MELLUM["published"] == {
        "num_hidden_layers": 28, "vocab_size": 98304,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "num_experts": 64, "max_position_embeddings": 131072}
    assert (MELLUM["layers_held"], MELLUM["q_heads_held"],
            MELLUM["kv_heads_held"], MELLUM["experts_held"]) \
        == ([0, 1, 2, 3], 8, 1, [0, 16])
    for key in ("deployment", "reduced_note", "assumed", "departures"):
        assert MELLUM[key], key
    # the share is a quarter of every divided count: rank 0 of 4
    assert MELLUM["q_heads_held"] * 4 == MELLUM["num_attention_heads"]
    assert MELLUM["kv_heads_held"] * 4 == MELLUM["num_key_value_heads"]
    assert MELLUM["experts_held"][1] * 4 == MELLUM["num_experts"]
    assert MELLUM["vocab_size"] * 4 == MELLUM["published"]["vocab_size"]


def test_the_cut_is_the_arithmetic_the_configuration_states():
    cfg = train_lm.model_config(MELLUM)
    from paddle_tpu.models import mellum

    specs = mellum.param_specs(cfg)
    held = sum(int(np.prod(shape)) for shape, _k, _d in specs.values())
    assert held == f.parameters(MELLUM) == 531_453_184
    assert "531,453,184" in MELLUM["reduced_note"]
    layer = sum(int(np.prod(s)) for n, (s, _k, _d) in specs.items()
                if n.startswith("ml_l0_"))
    assert layer == 104_551_168
    assert 16 * 3 * 2304 * 896 == 99_090_432      # a layer's held experts
    # 12 bytes a parameter: bfloat16 value and gradient, two float32 moments
    assert 6.37e9 < 12 * held < 6.38e9
    assert cfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert cfg.yarn == {"factor": 16.0, "original_max": 8192,
                        "beta_fast": 32.0, "beta_slow": 1.0,
                        "attention_factor": 1.2772588722239782}
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.experts_held,
            cfg.num_experts, cfg.vocab_size) == (8, 1, (0, 16), 64, 24576)


def test_the_flops_by_hand():
    """A token, forward, held share: projections and router 10.9 M a layer,
    two held pairs 24.8 M, scores and values 3.9 M a sliding layer and
    16.8 M the full one at 8,192, the head 113.2 M: 284.6 M."""
    assert f.projection_flops_per_token(MELLUM) == 2 * 2304 * (
        128 * (2 * 8 + 2 * 1) + 64) == 10_911_744
    assert f.expected_held_pairs_per_token(MELLUM) == 2.0
    assert f.expert_pair_flops(MELLUM) == 2 * 3 * 2304 * 896 == 12_386_304
    assert f.window_pairs(8192, 0) == 8192 * 8193 // 2
    assert f.window_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    assert f.window_pairs(16, 64) == f.window_pairs(16, 0) == 136
    # brute force: pairs with t - w < s <= t
    assert f.window_pairs(40, 7) == sum(
        1 for t in range(40) for s in range(40) if t - 7 < s <= t)
    sliding = f.attention_flops_per_sequence(MELLUM, 8192, True, 2)
    full = f.attention_flops_per_sequence(MELLUM, 8192, False, 2)
    assert sliding == 2 * 2 * 8 * 128 * f.window_pairs(8192, 1024)
    assert 3.9e6 < sliding / 8192 < 4.0e6 and 16.7e6 < full / 8192 < 16.8e6
    assert f.head_flops_per_token(MELLUM) == 2 * 2304 * 24576
    forward = f.forward_flops_per_token(MELLUM, 8192)
    assert forward == pytest.approx(
        4 * (10_911_744 + 2 * 12_386_304) + (3 * sliding + full) / 8192
        + 113_246_208)
    assert 284e6 < forward < 285e6
    assert f.train_flops_per_token(MELLUM, 8192) == 3 * forward
    # what the window's counter read takes the expectation's place
    assert f.forward_flops_per_token(MELLUM, 8192, 1.5) == pytest.approx(
        forward - 4 * 0.5 * 12_386_304)
    assert f.window_attention_step_flops(MELLUM, 2, 8192, 2) \
        == 2 * (3 * sliding + full)
    assert f.routed_train_step_flops(MELLUM, 131072) \
        == 9 * 131072 * 2 * 2304 * 896


def test_the_generator_shifts_its_ids_by_one():
    from benchmark.generators import lm_ring

    ring = lm_ring.make(TOY_TRAFFIC, 2 ** 31 + 7, 61)
    assert len(ring) == 3
    for i, b in enumerate(ring):
        assert b["tokens"].shape == b["labels"].shape == (2, 16)
        assert b["tokens"].dtype == np.int64
        assert (b["tokens"][:, 1:] == b["labels"][:, :-1]).all()
        assert 0 <= b["tokens"].min() and b["labels"].max() < 61
        again = lm_ring.batch(61, 2, 16, 2 ** 31 + 7 + i)
        assert (again["tokens"] == b["tokens"]).all()
    assert (ring[0]["tokens"] != ring[1]["tokens"]).any()


def test_the_mellum_cell_runs_to_correct_on_the_cpu(mellum_root):
    out = run.run_cell(mellum_root, CELL, seed=2 ** 31 + 5, seconds=1.0,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())


def test_the_mellum_cells_counters_reach_its_readers(mellum_root,
                                                     monkeypatch):
    facts = {}
    run_lm = train_lm.run

    def spy(job):
        facts["ctx"] = run_lm(job)
        return facts["ctx"]

    monkeypatch.setattr(train_lm, "run", spy)
    out = run.run_cell(mellum_root, CELL, seed=7, seconds=1.0, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"step_ms_p50.train", "host_dispatch_ms_p50.train",
            "first_step_s.train", "device_idle_share.train",
            "moe_rows_per_held_expert.train"} <= set(m)
    # 32 tokens x top-2 of 8 experts, 4 held: ~8 rows an expert and layer
    assert 5 <= m["moe_rows_per_held_expert.train"] <= 12
    # a CPU trace holds no kernel and no program line
    assert not {"flash_window_fwd_roofline", "flash_window_bwd_roofline",
                "routed_experts_train_roofline",
                "train_step_roofline"} & set(m)
    ctx = facts["ctx"]
    counters = ctx.telemetry["counters"]
    steps = counters["moe.train.steps"]
    assert steps == ctx.steps          # the window's own, no warm-up step
    assert counters["moe.train.pairs"] == steps * 2 * 32 * 2
    assert 0 < counters["moe.train.pairs_held"] < counters["moe.train.pairs"]
    assert counters["pallas.flash_window_fallbacks"] > 0
    assert ctx.telemetry["hists"]["moe.train.max_group_rows"]["count"] > 0
    names = [c[0] for c in ctx.compared]
    leaves = ["ml_tok_emb", "ml_l0_router_w", "ml_l1_ex_w2[3]", "ml_l1_k_w"]
    assert names == ["loss_rel_err"] + [
        "grad_rel_err." + n for n in leaves] + ["routing_agreement"] + [
        "update_rel_err." + n for n in leaves] + ["compiles_in_window"]
    by = {c[0]: c[1] for c in ctx.compared}
    assert by["loss_rel_err"] < 1e-5 and by["routing_agreement"] == 1.0
    assert all(v < 1e-4 for n, v in by.items() if n.startswith("grad_"))
    assert all(v < 1e-3 for n, v in by.items() if n.startswith("update_"))
    # the check's first call of the timed step compiled it: the warm-up
    # and the window compile nothing more
    assert ctx.first_step_s > 0 and by["compiles_in_window"] == 0


def traced(op_seconds, counters, kind="train", programs=None, config=MELLUM):
    return result(
        kind=kind, peaks=PEAKS, config=config,
        traffic={"batch_per_replica": 2, "seq_len": 8192},
        telemetry={"counters": counters, "hists": {}},
        trace={"op_seconds": op_seconds,
               "programs": programs if programs is not None else {
                   "jit_step_fn(3)": {"runs": 10.0, "seconds": 2.5}}})


COUNTERS = {"moe.train.steps": 300,
            "moe.train.pairs_held": 300 * 4 * 32_768}


def test_flash_window_rooflines_by_hand():
    """Two sequences: 3 sliding layers and a full one, 2 products forward
    (0.468 TFLOP: 2.4 ms by the peak), 4 backward."""
    man = Manifest(toy.REPO)
    fwd = man.reader("flash_window_fwd_roofline")
    bwd = man.reader("flash_window_bwd_roofline")
    ops = {"flash_fwd_window": 0.05, "flash_bwd_window_dkv": 0.08,
           "flash_bwd_window_dq": 0.07, "fusion": 1.0}
    least = 2 * (3 * 32_214_351_872 + 137_455_730_688) / 197e12
    assert fwd(traced(ops, COUNTERS)) == pytest.approx(
        100 * least / 0.005)
    assert 45 < fwd(traced(ops, COUNTERS)) < 50
    assert bwd(traced(ops, COUNTERS)) == pytest.approx(
        100 * 2 * least / 0.015)
    # nothing to read: a kernel missing, an untraced run, a served model,
    # another trainer's configuration
    assert fwd(traced({"fusion": 1.0}, COUNTERS)) is None
    assert bwd(traced({"flash_bwd_window_dq": 0.07}, COUNTERS)) is None
    assert fwd(result(kind="train", peaks=PEAKS, config=MELLUM)) is None
    assert fwd(traced(ops, COUNTERS, kind="serve")) is None
    assert fwd(traced(ops, COUNTERS, config={"hidden_size": 1024})) is None


def test_routed_experts_train_roofline_by_hand():
    """131,072 held pairs a step (4 layers x 32,768) x nine products of
    2 x 2304 x 896: 4.87 TFLOP, 24.7 ms by the peak."""
    read = Manifest(toy.REPO).reader("routed_experts_train_roofline")
    ops = {"grouped_swiglu": 0.10, "ragged-dot-none": 0.35, "fusion": 1.0}
    least = 9 * 131_072 * 2 * 2304 * 896 / 197e12
    assert read(traced(ops, COUNTERS)) == pytest.approx(
        100 * least / 0.045)
    assert 50 < read(traced(ops, COUNTERS)) < 60
    assert read(traced({"fusion": 1.0}, COUNTERS)) is None
    assert read(traced(ops, {})) is None                 # the parent
    assert read(traced(ops, COUNTERS, programs={})) is None
    assert read(traced(ops, COUNTERS, kind="serve")) is None


def test_moe_rows_per_held_expert_by_hand():
    read = Manifest(toy.REPO).reader("moe_rows_per_held_expert.train")
    assert read(traced({}, COUNTERS)) == 2048.0
    assert read(traced({}, {"moe.train.steps": 0})) is None
    assert read(traced({}, {})) is None
    assert read(traced({}, COUNTERS, kind="serve")) is None
    assert read(traced({}, COUNTERS, config={"hidden_size": 8})) is None


@pytest.fixture(scope="module")
def toy_step():
    """The toy cell's first step as the runner's check takes it, and the
    reference's."""
    import paddle_tpu as pt

    built = train_lm.build(TOY_MELLUM, TOY_TRAFFIC, 5) + (pt.Executor(),
                                                          pt.Scope())
    step = train_lm.first_step(TOY_MELLUM, TOY_TRAFFIC, 5, built)
    assert built[5].local_var_names() == []     # left empty for the reference
    reference = reference_mellum.loss_and_grads(
        step["params"], step["batch"]["tokens"], step["batch"]["labels"],
        train_lm.reference_model(built[0]))
    return built, step, reference


def _judged(step, reference, **planted):
    step = dict(step, **planted)
    notes, compared = train_lm.judge(
        TOY_MELLUM, step["params"], reference, step["loss"], step["grads"],
        step["chosen"], step["after"])
    return notes, {n: v for n, v, _lim in compared}


def _other_step(monkeypatch, config=TOY_MELLUM, batch_of=None):
    """The toy cell's first step again, from the same seed, with another
    configuration or with the program fed another batch."""
    import paddle_tpu as pt
    from benchmark.generators import lm_ring

    if batch_of is not None:
        true_batch = lm_ring.batch
        monkeypatch.setattr(lm_ring, "batch", lambda *a: batch_of(
            true_batch(*a)))
    built = train_lm.build(config, TOY_TRAFFIC, 5) + (pt.Executor(),
                                                      pt.Scope())
    return train_lm.first_step(config, TOY_TRAFFIC, 5, built)


def test_the_sound_step_holds_every_limit(toy_step):
    _built, step, reference = toy_step
    notes, by = _judged(step, reference)
    assert notes == []
    assert step["loss"] == step["loss_with_grads"]
    updates = {n: v for n, v in by.items() if n.startswith("update_")}
    assert len(updates) == 4 and all(v < 1e-3 for v in updates.values())
    # a leaf the reference's step leaves where it was (the real cell's
    # embedding: no bfloat16 element moves at lr 1e-5) must be left there
    still = np.zeros((3, 2))
    assert reference_mellum._rel_err(still, still) == 0.0
    assert reference_mellum._rel_err(still + 1e-9, still) == float("inf")


@pytest.mark.parametrize("fault", ["unchanged_state", "skipped_leaf",
                                   "twice_the_rate", "half_the_batch"])
def test_a_planted_fault_in_the_step_is_not_correct(toy_step, monkeypatch,
                                                    fault):
    """What the loss of a seeded model cannot see (it is ln V whatever the
    step does) and the first step's gradients alone cannot either: an
    optimizer that does nothing, skips a leaf or steps at another rate,
    and a step fed half its batch."""
    _built, step, reference = toy_step
    leaves = list(step["after"])
    if fault == "unchanged_state":
        notes, by = _judged(step, reference, after={
            n: step["params"][n] for n in leaves})
        assert all(by["update_rel_err." + n] == 1.0 for n in (
            "ml_tok_emb", "ml_l0_router_w", "ml_l1_k_w"))
        assert len(notes) == 4
    elif fault == "skipped_leaf":
        notes, by = _judged(step, reference, after=dict(
            step["after"], ml_l1_k_w=step["params"]["ml_l1_k_w"]))
        assert by["update_rel_err.ml_l1_k_w"] == 1.0 and len(notes) == 1
    elif fault == "twice_the_rate":
        other = _other_step(monkeypatch, dict(TOY_MELLUM, runner=dict(
            TOY_MELLUM["runner"], lr=2e-3)))
        notes, by = _judged(step, reference, after=other["after"])
        assert all(0.9 < by["update_rel_err." + n] < 1.1
                   for n in ("ml_l0_router_w", "ml_l1_k_w"))
        assert len(notes) == 4
    else:
        def first_row_twice(batch):
            return {k: np.stack([v[0], v[0]]) for k, v in batch.items()}

        other = _other_step(monkeypatch, batch_of=first_row_twice)
        notes, by = _judged(step, reference, loss=other["loss"],
                            grads=other["grads"], chosen=other["chosen"],
                            after=other["after"])
        assert by["loss_rel_err"] < 0.1         # the loss hardly sees it
        assert all(by["grad_rel_err." + n] > 0.3 for n in (
            "ml_l0_router_w", "ml_l1_k_w"))
        assert any(n.startswith("the optimizer's change") for n in notes)
        assert by["routing_agreement"] < 0.75   # row 1 routed as row 0


def test_one_step_reads_the_decay_and_cannot_hold_it(toy_step, monkeypatch):
    """The decoupled decay is lr * 0.01 * p beside a first step of lr: a
    hundredth of it on a weight of 1. Ten times the stated decay is read
    in the embedding's change (std 1) and stays under UPDATE_TOL: what
    the check does not hold, said here."""
    _built, step, reference = toy_step
    other = _other_step(monkeypatch, dict(TOY_MELLUM, runner=dict(
        TOY_MELLUM["runner"], weight_decay=0.1)))
    notes, by = _judged(step, reference, after=other["after"])
    assert notes == []
    assert 0.01 < by["update_rel_err.ml_tok_emb"] < 0.2


def test_the_lower_precision_control_fails_the_check():
    """The reference's own step with every matrix through float8 is no
    correct step: at least one limit fails, and the same comparison of
    the float32 step with itself holds them all."""
    import jax

    from paddle_tpu.models import mellum

    cfg = train_lm.model_config(TOY_MELLUM)
    model = train_lm.reference_model(cfg)
    rng = np.random.RandomState(3)
    params = {}
    for name, (shape, kind, _dt) in mellum.param_specs(cfg).items():
        params[name] = (np.ones(shape) if kind == "one" else rng.normal(
            0, shape[-2] ** -0.5 if isinstance(kind, str) else kind, shape)
        ).astype(np.float32)
    batch = mellum.synthetic_batch(cfg, 2, 16, seed=11)
    names = TOY_MELLUM["check"]["grads"]
    notes, compared = reference_mellum.control(
        params, batch["tokens"], batch["labels"], model, names,
        optimizer=train_lm.optimizer_of(TOY_MELLUM))
    assert notes, compared
    assert sum(n.startswith("update_rel_err.") for n, _v, _l in compared) \
        == len(names)
    over = [n for n, v, lim in compared
            if (v < lim if n == "routing_agreement" else v > lim)]
    assert over and any(n.startswith("grad_rel_err.") for n in over)
    loss, grads, chosen = reference_mellum.loss_and_grads(
        params, batch["tokens"], batch["labels"], model)
    stepped = reference_mellum.adamw_first_step(
        params, {n: grads[n] for n in names},
        **train_lm.optimizer_of(TOY_MELLUM))
    notes, compared = reference_mellum.compare(
        loss, {n: grads[n] for n in names}, chosen, (loss, grads, chosen),
        reference_mellum.changes(params, stepped, stepped))
    assert notes == [] and jax.default_backend() == "cpu"
    # a first step of Adam moves every element by the rate, but for the
    # decay and where the gradient is as small as epsilon
    moved = np.abs(stepped["ml_l1_k_w"] - params["ml_l1_k_w"])
    assert 0.9e-3 < np.median(moved) < 1.1e-3
