"""The `kimi_k2` family at toy sizes through `benchmark.run`'s own path on
the CPU: its cell runs to `correct: true`, its counters reach its readers
and its byte count, the real manifest with its configuration is sound, and
the new readers by hand on a fixture trace."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_kimi_k2, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "Kimi-K2.7-Code"
REAL_CELL = "kimi_k2_dp_ep32_serve_closed_c96"
CELL = "kimi_closed"
TOY_KIMI = {
    "name": "toy_kimi", "kind": "serve", "family": "kimi_k2",
    "source": "none: a test preset",
    "vocab_size": 128, "hidden_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8,
    "layers_held": [0, 1, 2, 3, 4], "first_k_dense_replace": 1,
    "num_dense_layers": 1,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 32, "num_experts_per_tok": 8, "n_shared_experts": 1,
    "experts_held": [0, 8], "routed_scaling_factor": 2.827,
    "norm_topk_prob": True, "rms_norm_eps": 1e-5, "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "max_context": 64, "dtype": "float32", "kv_pages": 8 * 16 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [6, 20, 40], "new_tokens": 8, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}}
JOINED = ("moe_experts_hit_per_layer", "moe_held_pair_share",
          "prefill_time_share.serve")
NEW_METRICS = ("mla_attention_busy_share.serve",
               "mla_prefill_attention_roofline")
# read off a decode step of the traced sub-window, which at the cell's 4 s
# ramp holds prefills alone: `paged_mla_attention_roofline` has its reader
# and no entry, `routed_decode_step_roofline` does not list the cell
STEP_METRICS = ("paged_mla_attention_roofline",
                "routed_decode_step_roofline")


@pytest.fixture(scope="module")
def kimi_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy kimi_k2 cell
    that reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("kimi_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_kimi.json"), "w") as f:
        json.dump(TOY_KIMI, f)
    with open(os.path.join(data, "traffic", "kimi_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_kimi", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_kimi.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_kimi", "traffic": "kimi_closed",
        "chips": 1, "why": "toy"})
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if "toy_closed" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
    assert Manifest(root).problems() == []
    return root


def holds(man):
    cell = man.cell(REAL_CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "closed_c96_code")
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "serve_tokens_per_s",
        "batch_occupancy_avg", "completed_requests_per_s",
        "window_hbm_gb.serve", "step_ahead_share.serve"} <= reported
    assert not set(STEP_METRICS) & reported
    assert os.path.isfile(man.reader_path("paged_mla_attention_roofline"))
    # the other two models' kernels count their own bytes
    assert not {"paged_attention_roofline", "paged_gqa_attention_roofline",
                "rows_past_window_share.serve"} & reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    # the new metrics came with this cell
    for name in NEW_METRICS:
        assert REAL_CELL in toy.entry(man, "per_layer", name)["workloads"]
    step_ahead_lists_the_serving_cells(man)


def step_ahead_lists_the_serving_cells(man):
    """`step_ahead_share.serve` (PR 31) over the loops at saturation that
    PR 33 knew, and whichever came since; the entry's fields and its
    cells' kind are test_bench_step_ahead's to hold."""
    entry = toy.entry(man, "per_layer", "step_ahead_share.serve")
    assert {"xglm_1p7b_serve_closed_c16",
            "trinity_large_tp8ep8_serve_closed_c96",
            REAL_CELL} <= set(entry["workloads"])


def test_the_real_manifest_is_sound_with_the_kimi_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)


def test_step_ahead_share_lists_every_serving_cell_at_saturation():
    step_ahead_lists_the_serving_cells(Manifest(toy.REPO))


def test_the_family_file_keeps_the_contract():
    family = Manifest(toy.REPO).family("kimi_k2")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = Manifest(toy.REPO).config_doc("kimi_k2_dp_ep32")
    cfg = family.model_config(doc)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.num_heads) == (5, 1, 64)
    assert (cfg.latent_dim, cfg.latent_row_width) == (576, 640)
    assert cfg.experts_held == (0, 12) and cfg.num_experts == 384
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.41589 ** 2,
                                              rel=1e-4)
    assert family.slots(doc) == 64
    assert family.traffic_vocab(cfg, doc) == 20480
    traffic = Manifest(toy.REPO).traffic_doc("closed_c96_code")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 64 * 128 + 1 and not eng["prefix_cache"]
    # one check prompt in each prefill bucket
    buckets = eng["prefill_buckets"]
    assert buckets == [512, 1024, 2048, 4096, 6144]
    lands = [next(b for b in buckets if b >= n)
             for n in doc["check"]["prompt_tokens"]]
    assert lands == buckets
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=8192), traffic)


def test_the_traffic_is_the_mix_the_issue_states():
    from benchmark.generators import requests

    traffic = Manifest(toy.REPO).traffic_doc("closed_c96_code")
    assert traffic["arrival"] == {"kind": "closed", "clients": 96}
    assert traffic["ramp_s"] == 4.0 and traffic["temperature"] == 0.8
    prompt, new = requests.lengths(traffic, traffic["distinct_lengths"])
    assert len(prompt) == 256
    assert prompt.min() >= 512 and prompt.max() <= 6144
    assert new.min() >= 64 and new.max() <= 2048
    assert (prompt + new).max() <= 8192
    assert 2800 < np.median(prompt) < 3300 and 280 < np.median(new) < 360


def test_the_configuration_carries_every_published_number():
    """The catalog row's `config`, key by key: a number that differs is
    listed under `reduced`, and no width is."""
    rows = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if not os.path.isfile(rows):
        pytest.skip("no catalog beside this checkout")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == PUBLISHED_ROW)
    man = Manifest(toy.REPO)
    doc = man.config_doc("kimi_k2_dp_ep32")
    assert doc["source"] == row["source_url"] \
        == man.configs["kimi_k2_dp_ep32"]["source"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs <= set(doc["reduced"])
    assert doc["reduced"] == man.configs["kimi_k2_dp_ep32"]["reduced"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "n_routed_experts", "num_attention_heads")
    assert not set(widths) & set(doc["reduced"])
    assert doc["rope_scaling"] == row["config"]["rope_scaling"]
    for key in ("published", "deployment", "assumed", "departures"):
        assert doc[key]
    assert "expert-parallel over 32" in doc["deployment"]
    assert any("1/32 of the pairs" in d for d in doc["departures"])
    assert any("vision tower" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc("kimi_k2_dp_ep32")
    f = flops_kimi_k2
    assert f.attention_weight_params(doc) == (
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
        + 64 * 128 * 7168) == 101_122_048
    assert f.expert_params(doc) == 3 * 7168 * 2048 == 44_040_192
    assert f.moe_layers(doc) == 4
    assert f.params_held(doc) == (
        5 * 101_122_048 + 3 * 7168 * 18432
        + 4 * (13 * 44_040_192 + 7168 * 384) + 2 * 20480 * 7168) \
        == 3_496_673_280
    assert f.latent_bytes_per_token_layer(doc) == 1152
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    # a step that hits 9 experts a layer at 64 rows of 3600 latent rows
    least = f.step_bytes(doc, experts_hit=36, latent_tokens=64 * 5 * 3600,
                         rows=64)
    assert least == pytest.approx(
        f.non_expert_weight_bytes(doc) + 36 * 88_080_384 + 64 * 7168 * 2
        + 64 * 5 * 3600 * 1152)
    assert 2.46e9 < f.non_expert_weight_bytes(doc) < 2.48e9
    assert 6.9e9 < least < 7.1e9
    # the kernel: each row once, every head's two products over it
    assert f.paged_mla_bytes(doc, 1000) == 1000 * 1152
    assert f.paged_mla_flops(doc, 1000) == 1000 * 2 * 64 * (576 + 512)
    intensity = f.paged_mla_flops(doc, 1) / f.paged_mla_bytes(doc, 1)
    assert intensity == pytest.approx(120.9, abs=0.1)     # ridge ~240


def test_the_kimi_cell_runs_to_correct_on_the_cpu(kimi_root):
    out = run.run_cell(kimi_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_kimi_cells_counters_reach_its_readers(kimi_root):
    out = run.run_cell(kimi_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(JOINED) <= set(m)
    assert 0 < m["moe_experts_hit_per_layer"] <= 8        # of 8 held
    assert 5 < m["moe_held_pair_share"] < 60              # 8 of 32 held
    assert 0 < m["prefill_time_share.serve"] < 100
    # no window layers: nothing is past a window
    assert "rows_past_window_share.serve" not in m
    # a CPU trace holds no kernel: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(m)


def test_the_readings_script_judges_the_reference_and_each_control(
        kimi_root, capsys):
    from benchmark import readings_kimi_k2, reference_kimi_k2

    readings_kimi_k2.main(["--config", "toy_kimi", "--traffic",
                           "kimi_closed", "--seed", "5"], root=kimi_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    by_sequence = lines.pop()
    assert [ln["reference"] for ln in lines] \
        == ["as it is"] + list(reference_kimi_k2.CONTROLS)
    assert lines[0]["correct"] is True
    assert not lines[-1]["correct"]         # every weight matrix in 8 bits
    worst = [max(v for n, v, _ in ln["compared"]
                 if n.startswith("prefill_logit_err")) for ln in lines]
    assert all(w > 20 * worst[0] for w in worst[1:])
    # the routed pairs on the held quarter, sequence by sequence and MoE
    # layer by layer: no sequence goes all one way
    shares = by_sequence["held_pair_share_by_sequence_and_layer"]
    assert by_sequence["even"] == 25.0
    assert len(shares) == 3 and all(len(s) == 4 for s in shares)
    assert all(5 < v < 60 for s in shares[1:] for v in s)


def readings(root, capsys, *more):
    from benchmark import readings_kimi_k2

    readings_kimi_k2.main(["--config", "toy_kimi", "--traffic",
                           "kimi_closed", "--seed", "5", *more], root=root)
    return [json.loads(line) for line in
            capsys.readouterr().out.splitlines() if line.startswith("{")]


def test_the_readings_script_reads_the_engines_routing_beside_the_references(
        kimi_root, capsys):
    """A line a check prompt: where the engine's prefill program and the
    reference keep other experts, and the prefill's logits against the
    reference on the engine's own routing. Where none differ the two
    errors are one number."""
    lines = [ln for ln in readings(kimi_root, capsys, "--routing")
             if "differ" in ln]
    assert [ln["prompt"] for ln in lines] == [6, 20, 40]
    for ln in lines:
        assert ln["positions_x_layers"] == 4 * ln["sent"]
        assert 0 <= ln["on_held"] <= ln["differ"] \
            <= ln["positions_x_layers"]
        assert len(ln["on_held_at"]) == min(ln["on_held"], 12)
        if not ln["differ"]:
            assert ln["logit_err_on_the_engines_routing"] \
                == pytest.approx(ln["logit_err"], abs=1e-6)
        assert ln["logit_err_on_the_engines_routing"] \
            <= ln["logit_err"] + 1e-6


def test_the_readings_script_reads_the_planted_page_table_fault(
        kimi_root, capsys):
    """The engine's outputs with live rows fed the first row's page table,
    against the reference as it is: one line, not correct by a greedy
    margin, no control beside it."""
    judged, = readings(kimi_root, capsys, "--plant", "page_table")
    assert judged["planted"] == "page_table" and not judged["correct"]
    assert judged["reference"] == "as it is"
    assert any("greedy token" in n for n in judged["notes"])


# -- the new readers, by hand --------------------------------------------------

PEAKS = flops.peaks("TPU v5 lite")
KIMI = Manifest(toy.REPO).config_doc("kimi_k2_dp_ep32")


def traced(op_seconds, counters, config=KIMI, kind="serve", busy_s=2.4,
           prefills=12):
    return result(
        kind=kind, peaks=PEAKS, config=config,
        telemetry={"counters": counters},
        trace={"window_s": 3.0, "busy_s": busy_s, "op_seconds": op_seconds,
               "counters": {"decode.steps": 150, "decode.prefills": prefills},
               "programs": {
                   "jit_decode_step_b64(1)": {"runs": 150.0, "seconds": 1.5},
                   "jit_prefill_p4096(2)": {"runs": 12.0, "seconds": 0.9}}})


def test_paged_mla_attention_roofline_by_hand():
    """64 rows x 5 layers x 3,600 latent rows a step = 1.33 GB and 160
    GFLOP; the kernel 0.3 s over the 150 steps of a traced window."""
    read = Manifest(toy.REPO).reader("paged_mla_attention_roofline")
    rows = 64 * 5 * 3600
    counters = {"decode.steps": 1000,
                "decode.kv_tokens_attended": 1000 * rows}
    ctx = traced({"paged_mla_attention": 0.3, "fusion": 1.0}, counters)
    by_bytes = rows * 1152 / 819e9
    by_flops = rows * 2 * 64 * 1088 / 197e12
    assert by_bytes > by_flops            # the bytes bound it
    assert read(ctx) == pytest.approx(100 * by_bytes / (0.3 / 150))
    assert 75 < read(ctx) < 85
    # a chip whose ridge lay under the kernel's 121 FLOP a byte would be
    # bound by the operations: the larger of the two is read
    slow = dict(PEAKS, bf16_flops_per_s=50e12)
    ctx.peaks = slow
    assert read(ctx) == pytest.approx(
        100 * rows * 2 * 64 * 1088 / 50e12 / (0.3 / 150))
    # nothing to read: no such kernel in the trace, no counter, another
    # family's configuration, an untraced run, a trainer
    assert read(traced({"fusion": 1.0}, counters)) is None
    assert read(traced({"paged_mla_attention": 0.3},
                       {"decode.steps": 1000})) is None
    assert read(traced({"paged_mla_attention": 0.3}, counters,
                       config={"hidden_size": 3072})) is None
    assert read(result(kind="serve", peaks=PEAKS, config=KIMI,
                       telemetry={"counters": counters})) is None
    assert read(traced({"paged_mla_attention": 0.3}, counters,
                       kind="train")) is None


def test_mla_attention_busy_share_by_hand():
    read = Manifest(toy.REPO).reader("mla_attention_busy_share.serve")
    ctx = traced({"paged_mla_attention": 0.3, "fusion": 1.0,
                  "mla_prefill_attention": 0.18, "paged_gqa_attention": 9.0},
                 {})
    assert read(ctx) == pytest.approx(100 * 0.48 / 2.4)
    assert read(traced({"paged_mla_attention": 0.3}, {})) \
        == pytest.approx(12.5)
    assert read(traced({"fusion": 1.0}, {})) is None
    assert read(traced({"paged_mla_attention": 0.3}, {}, busy_s=0)) is None
    assert read(traced({"paged_mla_attention": 0.3}, {},
                       kind="train")) is None
    assert read(result(kind="serve")) is None


def test_mla_prefill_attention_roofline_by_hand():
    """The traced sub-window holds 12 runs of the 4096 bucket's prefill
    program: 12 causal triangles of 4,096 positions over 5 layers, their
    kernels 0.3 s. Nothing of the window's counters is read."""
    read = Manifest(toy.REPO).reader("mla_prefill_attention_roofline")
    pairs = 5 * 4096 * 4097 // 2
    assert flops_kimi_k2.prefill_pairs(KIMI, 4096) == pairs
    flops_a_prefill = pairs * 2 * 64 * (128 + 64 + 128)
    assert flops_kimi_k2.mla_prefill_flops(KIMI, pairs) == flops_a_prefill
    ctx = traced({"mla_prefill_attention": 0.3, "fusion": 1.0}, {})
    assert read(ctx) == pytest.approx(
        100 * 12 * flops_a_prefill / 197e12 / 0.3)
    assert 30 < read(ctx) < 40
    # two buckets in the sub-window add up
    ctx.trace["programs"]["jit_prefill_p512(5)"] = {"runs": 3.0,
                                                    "seconds": 0.1}
    small = flops_kimi_k2.mla_prefill_flops(KIMI, 5 * 512 * 513 // 2)
    assert read(ctx) == pytest.approx(
        100 * (12 * flops_a_prefill + 3 * small) / 197e12 / 0.3)
    # nothing to read: no such kernel, no prefill program in the traced
    # window, another family's configuration, an untraced run
    assert read(traced({"fusion": 1.0}, {})) is None
    only_steps = traced({"mla_prefill_attention": 0.3}, {})
    del only_steps.trace["programs"]["jit_prefill_p4096(2)"]
    assert read(only_steps) is None
    assert read(traced({"mla_prefill_attention": 0.3}, {},
                       config={"hidden_size": 3072})) is None
    assert read(result(kind="serve", peaks=PEAKS, config=KIMI,
                       telemetry={"counters": {}})) is None
