"""The `qwen3_next` family at toy sizes through `benchmark.run`'s own path on
the CPU: its cell runs to `correct: true`, its counters reach its readers
and its byte count, the real manifest with its configuration is sound (seven
cells), the cut is the arithmetic the configuration states, and the new
readers by hand on a fixture trace."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_afmoe, flops_qwen3_next, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "Qwen3-Next-80B-A3B-Instruct"
REAL_CONFIG = "qwen3_next_80b_tp4ep4"
REAL_CELL = "qwen3_next_80b_tp4ep4_serve_closed_c96"
CELL = "qwen_closed"
TOY_QWEN = {
    "name": "toy_qwen", "kind": "serve", "family": "qwen3_next",
    "source": "none: a test preset",
    "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
    "rope_scaling": None, "tie_word_embeddings": False,
    "use_sliding_window": False,
    "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 4,
    "full_attention_interval": 4, "head_dim": 16,
    "partial_rotary_factor": 0.25, "q_heads_held": 2, "kv_heads_held": 1,
    "linear_key_heads_held": 2, "linear_value_heads_held": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "linear_chunk_size": 16,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts": 16, "num_experts_per_tok": 4, "experts_held": [0, 8],
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "layers_held": [0, 1, 2, 3], "num_dense_layers": 0,
    "max_context": 64, "dtype": "float32", "linear_state_dtype": "float32",
    "kv_pages": 8 * 16 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [15, 17, 40], "new_tokens": 8, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}}
JOINED = ("batch_occupancy_avg", "completed_requests_per_s",
          "window_hbm_gb.serve", "prefill_time_share.serve",
          "step_ahead_share.serve", "moe_held_pair_share",
          "moe_experts_hit_per_layer", "hybrid_decode_step_roofline")
NEW_METRICS = ("gated_delta_state_update_roofline",
               "linear_attention_busy_share.serve")


@pytest.fixture(scope="module")
def qwen_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy qwen3_next cell
    that reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("qwen_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_qwen.json"), "w") as f:
        json.dump(TOY_QWEN, f)
    with open(os.path.join(data, "traffic", "qwen_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_qwen", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_qwen.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_qwen", "traffic": "qwen_closed",
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
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "closed_c96_longdoc", REAL_CONFIG)
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "serve_tokens_per_s", "compile_cache_misses"} <= reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    # one step roofline and no copy of another family's kernel shares
    assert not {"routed_decode_step_roofline", "ssm_state_update_roofline",
                "paged_gqa_attention_roofline",
                "hybrid_paged_gqa_attention_roofline"} & reported
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    # the new metrics came with this cell, wherever they stand now
    for name in NEW_METRICS:
        entry = toy.entry(man, "per_layer", name)
        assert REAL_CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert entry["layer"] == "kernels and step program"


def test_the_real_manifest_is_sound_with_seven_cells():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    assert len(man.cells) >= 7 and REAL_CELL in man.cells
    assert all(w["chips"] == 1 for w in man.doc["workloads"])
    holds(man)


def test_the_family_file_keeps_the_contract():
    man = Manifest(toy.REPO)
    family = man.family("qwen3_next")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = man.config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    # every width is the published one
    assert (cfg.hidden_size, cfg.head_dim, cfg.rotary_dim) == (2048, 256, 64)
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (128, 128, 4)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok) == (512, 512, 512, 10)
    # and the share is the quarter
    assert (cfg.n_layers, cfg.num_heads, cfg.num_kv_heads) == (8, 4, 1)
    assert (cfg.linear_key_heads, cfg.linear_value_heads) == (4, 8)
    assert cfg.experts_held == (0, 128) and cfg.conv_dim == 2048
    assert [cfg.is_attention(i) for i in range(8)] \
        == [False, False, False, True] * 2
    assert cfg.linear_state_dtype == "float32" and cfg.dtype == "bfloat16"
    assert cfg.rope_theta == 1e7 and cfg.linear_chunk_size == 64
    assert family.slots(doc) == 64
    assert family.traffic_vocab(cfg, doc) == 37984
    traffic = man.traffic_doc("closed_c96_longdoc")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 64 * 288 + 1 and not eng["prefix_cache"]
    # check prompts in every prefill bucket, one just under and one just
    # over a multiple of the chunk
    buckets = eng["prefill_buckets"]
    assert buckets == [1024, 2048, 4096, 8192, 16384]
    prompts = doc["check"]["prompt_tokens"]
    assert {next(b for b in buckets if b >= n) for n in prompts} \
        == set(buckets)
    assert any(n % 64 == 63 for n in prompts) \
        and any(n % 64 == 1 for n in prompts)
    assert family.pad_to(743, 4096) == 4096
    assert family.pad_to(6140, 4096) == 8192
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=18432), traffic)
    # a switch the program implements one value of
    with pytest.raises(ValueError, match="mlp_only_layers"):
        family.model_config(dict(doc, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="whole periods"):
        family.model_config(dict(doc, num_hidden_layers=6))
    # the family's configuration keys are in its docstring
    for key in ("num_hidden_layers", "q_heads_held", "kv_heads_held",
                "linear_key_heads_held", "linear_value_heads_held",
                "experts_held", "vocab_size", "max_context", "dtype",
                "linear_state_dtype", "linear_chunk_size", "kv_pages",
                "engine", "check", "partial_rotary_factor"):
        assert f"`{key}`" in family.__doc__, key


def test_the_traffic_is_the_mix_the_issue_states():
    from benchmark.generators import requests

    traffic = Manifest(toy.REPO).traffic_doc("closed_c96_longdoc")
    assert traffic["arrival"] == {"kind": "closed", "clients": 96}
    assert traffic["temperature"] == 0.8
    # 4 s as issued, lengthened by ISSUE 45's own rule: the fill of 64 long
    # prompts is ~10 s of prefills back to back, and at 4 s the traced
    # sub-window (2-5 s in) held one decode step
    assert traffic["ramp_s"] == 12.0
    assert "ramp 12 s" in Manifest(toy.REPO).cell(REAL_CELL)["why"]
    assert traffic["prompt_tokens"] == {"median": 4096, "sigma": 0.9,
                                        "min": 512, "max": 16384}
    assert traffic["new_tokens"] == {"median": 384, "sigma": 0.7,
                                     "min": 64, "max": 2048}
    assert (traffic["distinct_lengths"], traffic["lengths_seed"],
            traffic["max_context"]) == (256, 20261001, 18432)
    prompt, new = requests.lengths(traffic, traffic["distinct_lengths"])
    assert len(prompt) == 256
    assert prompt.min() >= 512 and prompt.max() <= 16384
    assert new.min() >= 1 and new.max() <= 2048
    assert (prompt + new).max() <= 18432
    # a few retrieved passages or a whole file in, a few paragraphs out
    assert 4500 < prompt.mean() < 6500 and 350 < new.mean() < 550


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
    doc = man.config_doc(REAL_CONFIG)
    assert doc["source"] == row["source_url"] \
        == man.configs[REAL_CONFIG]["source"]
    differs = {k for k, v in row["config"].items() if doc.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert doc["reduced"] == man.configs[REAL_CONFIG]["reduced"] == [
        "num_hidden_layers", "q_heads_held", "kv_heads_held",
        "linear_key_heads_held", "linear_value_heads_held", "experts_held",
        "vocab_size", "max_context"]
    assert not [k for k in doc["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert doc["published"] == {
        "num_hidden_layers": 48, "vocab_size": 151936,
        "num_attention_heads": 16, "num_key_value_heads": 2,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "num_experts": 512, "max_position_embeddings": 262144}
    for key in ("deployment", "assumed", "departures", "reduced_note"):
        assert doc[key]
    assert "Six pipeline stages of eight layers" in doc["deployment"]
    assert "not a tuning knob" in doc["assumed"]["linear_state_dtype"]
    assert doc["linear_state_dtype"] == "float32"
    assert any("multi-token-prediction" in d for d in doc["departures"])
    assert any("hand-off" in d for d in doc["departures"])
    assert any("all-reduces" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    f = flops_qwen3_next
    assert f.layers_of(doc) == (6, 2)
    assert (f.key_dim(doc), f.value_dim(doc), f.conv_dim(doc)) \
        == (512, 1024, 2048)
    assert f.expert_params(doc) == 3 * 2048 * 512 == 3_145_728
    assert 128 * f.expert_params(doc) == 402_653_184
    assert f.delta_net_params(doc) == (
        2048 * 3072 + 2048 * 16 + 1024 * 2048 + 4 * 2048 + 16 + 128) \
        == 8_429_712
    assert f.attention_params(doc) == 2048 * 256 * (3 * 4 + 2) + 512 \
        == 7_340_544
    assert f.moe_common_params(doc) == 2048 * 512 + 3 * 2048 * 512 + 2048
    # ISSUE 45's figure: 3.48 B values, 6.95 GB
    assert int(f.params_held(doc) / 1e7) / 100 == 3.47
    assert 6.94e9 < 2 * f.params_held(doc) < 6.96e9
    # the program's own parameters are these
    from paddle_tpu.models import qwen3_next

    family = Manifest(toy.REPO).family("qwen3_next")
    specs = qwen3_next.param_specs(family.model_config(doc))
    counted = sum(int(np.prod(shape)) for shape, _, _ in specs.values())
    assert counted == f.params_held(doc)
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    assert f.kv_bytes_per_token_layer(doc) == 1024
    assert f.state_bytes(doc) == 2 * 8 * 128 * 128 * 4 == 1_048_576
    assert f.state_slot_bytes(doc) == 6 * (524_288 + 3 * 2048 * 2)
    assert 0.20e9 < 65 * f.state_slot_bytes(doc) < 0.22e9
    assert 2.41e9 < doc["kv_pages"] * 64 * 2 * 1024 < 2.42e9
    # the accepted reader of moe_experts_hit_per_layer reads it right
    assert flops_afmoe.moe_layers(doc) == 8
    # a full step at ~6k tokens a slot, 91 experts hit a layer: 6.1 GB,
    # 7.5 ms by the peak
    least = f.step_bytes(doc, experts_hit=8 * 91, kv_tokens=64 * 2 * 6000,
                         state_rows=64 * 6, rows=64)
    assert least == pytest.approx(
        f.non_expert_weight_bytes(doc) + 8 * 91 * 6_291_456
        + 64 * 2048 * 2 + 64 * 2 * 6000 * 1024 + 64 * 6 * 1_048_576)
    assert 0.38e9 < 64 * 6 * f.state_bytes(doc) < 0.42e9
    assert 6.0e9 < least < 6.3e9
    # a mean prompt of ~6k: ~2.4 TFLOP here, 0.33 GFLOP a token outside
    # attention and the rule
    assert 2.0e12 < f.prefill_flops(doc, 6000) < 3.0e12
    per_token = (f.prefill_flops(doc, 2) - f.prefill_flops(doc, 1))
    assert 0.30e9 < per_token < 0.40e9


def test_the_qwen_cell_runs_to_correct_on_the_cpu(qwen_root):
    out = run.run_cell(qwen_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_qwen_cells_counters_reach_its_readers(qwen_root):
    out = run.run_cell(qwen_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["prefill_time_share.serve"] < 100
    assert 0 < m["batch_occupancy_avg"] <= 100
    # half the experts held: about half the pairs, every layer routed
    assert 35 < m["moe_held_pair_share"] < 65
    assert 0 < m["moe_experts_hit_per_layer"] <= 8
    # no window layers: nothing of theirs to read
    assert "rows_past_window_share.serve" not in m
    # a CPU trace holds no kernel: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(m)


def test_step_bytes_reads_the_windows_counters():
    family = Manifest(toy.REPO).family("qwen3_next")
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    counters = {"decode.steps": 100, "decode.tokens": 6300,
                "decode.moe_experts_hit": 100 * 8 * 90,
                "decode.kv_tokens_attended": 100 * 63 * 2 * 6000,
                "decode.state_rows_updated": 100 * 63 * 6}
    got = family.step_bytes(None, doc, 0.0, {"counters": counters})
    assert got == pytest.approx(flops_qwen3_next.step_bytes(
        doc, experts_hit=8 * 90, kv_tokens=63 * 2 * 6000,
        state_rows=63 * 6, rows=63))
    assert family.step_bytes(None, doc, 0.0, {"counters": {}}) == 0.0


# -- the new readers, by hand --------------------------------------------------

PEAKS = flops.peaks("TPU v5 lite")
QWEN = Manifest(toy.REPO).config_doc(REAL_CONFIG)
STEPS = 190.0


def traced(op_seconds, counters, config=QWEN, kind="serve", busy_s=2.9,
           step_bytes=6.1e9, programs=None):
    return result(
        kind=kind, peaks=PEAKS, config=config, step_bytes=step_bytes,
        telemetry={"counters": counters},
        trace={"window_s": 3.0, "busy_s": busy_s, "op_seconds": op_seconds,
               "counters": {"decode.steps": STEPS, "decode.prefills": 30},
               "programs": programs if programs is not None else {
                   "jit_decode_step_b64(1)": {"runs": STEPS, "seconds": 2.2},
                   "jit_prefill_p4096(2)": {"runs": 20.0, "seconds": 1.2}}})


COUNTERS = {"decode.steps": 3000,
            "decode.state_rows_updated": 3000 * 63.5 * 6}


def test_gated_delta_state_update_roofline_by_hand():
    """63.5 live rows x 6 layers x 1.05 MB a step = 0.40 GB; the kernel
    0.19 s over the 190 steps of a traced window: 1 ms a step."""
    read = Manifest(toy.REPO).reader("gated_delta_state_update_roofline")
    ctx = traced({"gated_delta_state_update": 0.19, "fusion": 1.5}, COUNTERS)
    least_s = 63.5 * 6 * 1_048_576 / 819e9
    assert read(ctx) == pytest.approx(100 * least_s / (0.19 / STEPS))
    assert 45 < read(ctx) < 55
    # the step program by its own name, not the sub-window's longest
    ctx.trace["programs"]["jit_prefill_p16384(3)"] = {"runs": 6.0,
                                                      "seconds": 5.0}
    assert read(ctx) == pytest.approx(100 * least_s / (0.19 / STEPS))
    # nothing to read: no such kernel (the parent's program, cell 5's),
    # no counter, no decode step in the traced window, an untraced run, a
    # trainer
    assert read(traced({"ssm_state_update": 1.0}, COUNTERS)) is None
    assert read(traced({"gated_delta_state_update": 0.19},
                       {"decode.steps": 3000})) is None
    assert read(traced({"gated_delta_state_update": 0.19}, COUNTERS,
                       programs={"jit_prefill_p4096(2)": {
                           "runs": 20.0, "seconds": 1.2}})) is None
    assert read(result(kind="serve", peaks=PEAKS, config=QWEN,
                       telemetry={"counters": COUNTERS})) is None
    assert read(traced({"gated_delta_state_update": 0.19}, COUNTERS,
                       kind="train")) is None


def test_hybrid_decode_step_roofline_reads_this_cells_step_bytes():
    read = Manifest(toy.REPO).reader("hybrid_decode_step_roofline")
    ctx = traced({"gated_delta_state_update": 0.19}, COUNTERS)
    assert read(ctx) == pytest.approx(100 * 6.1e9 / 819e9 / (2.2 / STEPS))
    assert 60 < read(ctx) < 70
    assert read(traced({}, COUNTERS, programs={})) is None


def test_linear_attention_busy_share_by_hand():
    read = Manifest(toy.REPO).reader("linear_attention_busy_share.serve")
    ctx = traced({"gated_delta_state_update": 0.19, "fusion": 1.5,
                  "paged_gqa_attention": 0.1}, {})
    assert read(ctx) == pytest.approx(100 * 0.19 / 2.9)
    # a chunk kernel, once there is one, joins it
    ctx = traced({"gated_delta_state_update": 0.19,
                  "gated_delta_chunk_scan": 0.5}, {})
    assert read(ctx) == pytest.approx(100 * 0.69 / 2.9)
    assert read(traced({"fusion": 1.0, "ssm_state_update": 0.5}, {})) is None
    assert read(traced({"gated_delta_state_update": 0.3}, {},
                       busy_s=0)) is None
    assert read(traced({"gated_delta_state_update": 0.3}, {},
                       kind="train")) is None
    assert read(result(kind="serve")) is None
