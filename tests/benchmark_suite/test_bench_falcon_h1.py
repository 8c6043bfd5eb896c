"""The `falcon_h1` family at toy sizes through `benchmark.run`'s own path on
the CPU: its cell runs to `correct: true`, its counters reach its readers
and its byte count, the real manifest with its configuration is sound, the
cut is the arithmetic the configuration states, and the new readers by hand
on a fixture trace."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_falcon_h1, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "Falcon-H1-34B-Instruct"
REAL_CONFIG = "falcon_h1_34b_pp12"
REAL_CELL = "falcon_h1_34b_pp12_serve_closed_c96"
CELL = "falcon_closed"
MULTIPLIERS = {
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}
TOY_FALCON = dict(MULTIPLIERS, **{
    "name": "toy_falcon", "kind": "serve", "family": "falcon_h1",
    "source": "none: a test preset",
    "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 8,
    "intermediate_size": 64, "mamba_n_heads": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
    "projectors_bias": False, "tie_word_embeddings": False,
    "rope_scaling": None, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000,
    "max_context": 64, "dtype": "float32", "ssm_state_dtype": "float32",
    "kv_pages": 8 * 16 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [15, 17, 40], "new_tokens": 8, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}})
JOINED = ("batch_occupancy_avg", "completed_requests_per_s",
          "window_hbm_gb.serve", "prefill_time_share.serve")
NEW_METRICS = ("ssm_state_update_roofline", "hybrid_decode_step_roofline",
               "ssm_busy_share.serve", "hybrid_paged_gqa_attention_roofline")


@pytest.fixture(scope="module")
def falcon_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy falcon_h1 cell
    that reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("falcon_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_falcon.json"), "w") as f:
        json.dump(TOY_FALCON, f)
    with open(os.path.join(data, "traffic", "falcon_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_falcon", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_falcon.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_falcon", "traffic": "falcon_closed",
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
        == (1, "closed_c96_chat", REAL_CONFIG)
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "serve_tokens_per_s", "compile_cache_misses"} <= reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    # the new metrics came with this cell, wherever they stand now
    for name in NEW_METRICS:
        entry = toy.entry(man, "per_layer", name)
        assert REAL_CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert entry["layer"] == "kernels and step program"


def test_the_real_manifest_is_sound_with_the_falcon_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)


def test_the_family_file_keeps_the_contract():
    family = Manifest(toy.REPO).family("falcon_h1")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    assert (cfg.n_layers, cfg.num_heads, cfg.num_kv_heads) == (6, 20, 4)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups) == (32, 128, 256, 2)
    assert (cfg.d_ssm, cfg.conv_dim, cfg.in_proj_dim) == (4096, 5120, 9248)
    assert cfg.ssm_state_dtype == "float32" and cfg.dtype == "bfloat16"
    assert cfg.rope_theta == 1e11 and cfg.mamba_chunk_size == 128
    assert family.slots(doc) == 64
    assert family.traffic_vocab(cfg, doc) == 32640
    traffic = Manifest(toy.REPO).traffic_doc("closed_c96_chat")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 64 * 32 + 1 and not eng["prefix_cache"]
    # check prompts in every prefill bucket, one just under and one just
    # over a multiple of the chunk
    buckets = eng["prefill_buckets"]
    assert buckets == [128, 256, 512, 1024]
    prompts = doc["check"]["prompt_tokens"]
    assert {next(b for b in buckets if b >= n) for n in prompts} \
        == set(buckets)
    assert 127 in prompts and 129 in prompts
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=2048), traffic)
    # a switch the program implements one value of
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        family.model_config(dict(doc, mamba_norm_before_gate=True))
    # the family's configuration keys are in its docstring
    for key in ("num_hidden_layers", "vocab_size", "max_context", "dtype",
                "ssm_state_dtype", "kv_pages", "engine", "check",
                "mamba_chunk_size", "ssm_multipliers"):
        assert f"`{key}`" in family.__doc__, key


def test_the_traffic_is_the_mix_the_issue_states():
    from benchmark.generators import requests

    traffic = Manifest(toy.REPO).traffic_doc("closed_c96_chat")
    assert traffic["arrival"] == {"kind": "closed", "clients": 96}
    assert traffic["ramp_s"] == 4.0 and traffic["temperature"] == 0.8
    assert traffic["prompt_tokens"] == {"median": 112, "sigma": 0.9,
                                        "min": 16, "max": 1024}
    assert traffic["new_tokens"] == {"median": 270, "sigma": 0.7,
                                     "min": 32, "max": 1024}
    prompt, new = requests.lengths(traffic, traffic["distinct_lengths"])
    assert len(prompt) == 256
    assert prompt.min() >= 16 and prompt.max() <= 1024
    assert new.min() >= 1 and new.max() <= 1024
    assert (prompt + new).max() <= 2048
    # ShareGPT's means as the vLLM paper reports them: 161 in, 338 out
    assert 150 < prompt.mean() < 180 and 320 < new.mean() < 350


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
    assert doc["reduced"] == man.configs[REAL_CONFIG]["reduced"] \
        == ["num_hidden_layers", "vocab_size", "max_context"]
    assert not [k for k in doc["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 72,
                                "vocab_size": 261120,
                                "max_position_embeddings": 262144}
    for key in ("deployment", "assumed", "departures", "reduced_note"):
        assert doc[key]
    assert "12 stages of 6 layers" in doc["deployment"]
    assert "not a tuning knob" in doc["assumed"]["ssm_state_dtype"]
    assert doc["ssm_state_dtype"] == "float32"
    assert any("hand-off" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    f = flops_falcon_h1
    assert f.conv_dim(doc) == 4096 + 2 * 2 * 256 == 5120
    assert f.mixer_params(doc) == (
        5120 * 9248 + 4096 * 5120 + 5 * 5120 + 3 * 32 + 4096) == 68_351_072
    assert f.attention_params(doc) == 2 * 5120 * 128 * (20 + 4) \
        == 31_457_280
    assert f.mlp_params(doc) == 3 * 5120 * 21504 == 330_301_440
    assert f.layer_params(doc) == 430_120_032
    # ISSUE 35's figure: 2,914.9 M parameters held
    assert f.params_held(doc) == 6 * 430_120_032 + 2 * 5120 * 32640 \
        == 2_914_953_792
    assert int(f.params_held(doc) / 1e5) / 10 == 2914.9
    # the program's own parameters are these, and its muP vector
    from paddle_tpu.models import falcon_h1

    family = Manifest(toy.REPO).family("falcon_h1")
    specs = falcon_h1.param_specs(family.model_config(doc))
    counted = sum(int(np.prod(shape)) for shape, _, _ in specs.values())
    assert counted == f.params_held(doc) + 5120 + 9248    # final norm, muP
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    assert f.kv_bytes_per_token_layer(doc) == 2048
    assert f.ssm_state_bytes(doc) == 2 * 32 * 128 * 256 * 4 == 8_388_608
    assert f.state_slot_bytes(doc) == 6 * (4_194_304 + 3 * 5120 * 2)
    assert 1.64e9 < 65 * f.state_slot_bytes(doc) < 1.66e9
    assert 5.49e9 < f.weight_bytes_a_step(doc) < 5.50e9
    # a full step at a mean context of 380: 9.0 GB, 11 ms by the peak
    least = f.step_bytes(doc, kv_tokens=64 * 6 * 380, state_rows=64 * 6,
                         rows=64)
    assert least == pytest.approx(
        f.weight_bytes_a_step(doc) + 64 * 5120 * 2
        + 64 * 6 * 380 * 2048 + 64 * 6 * 8_388_608)
    assert 8.9e9 < least < 9.1e9
    assert 3.2e9 < 64 * 6 * f.ssm_state_bytes(doc) < 3.25e9


def test_the_falcon_cell_runs_to_correct_on_the_cpu(falcon_root):
    out = run.run_cell(falcon_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_falcon_cells_counters_reach_its_readers(falcon_root):
    out = run.run_cell(falcon_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["prefill_time_share.serve"] < 100
    assert 0 < m["batch_occupancy_avg"] <= 100
    # no window layers, no routed experts: nothing of theirs to read
    assert not {"rows_past_window_share.serve", "moe_held_pair_share",
                "moe_experts_hit_per_layer"} & set(m)
    # a CPU trace holds no kernel: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(m)


def test_step_bytes_reads_the_windows_counters():
    family = Manifest(toy.REPO).family("falcon_h1")
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    counters = {"decode.steps": 100, "decode.tokens": 6300,
                "decode.kv_tokens_attended": 100 * 63 * 6 * 400,
                "decode.state_rows_updated": 100 * 63 * 6}
    got = family.step_bytes(None, doc, 0.0, {"counters": counters})
    assert got == pytest.approx(flops_falcon_h1.step_bytes(
        doc, kv_tokens=63 * 6 * 400, state_rows=63 * 6, rows=63))
    assert family.step_bytes(None, doc, 0.0, {"counters": {}}) == 0.0


# -- the new readers, by hand --------------------------------------------------

PEAKS = flops.peaks("TPU v5 lite")
FALCON = Manifest(toy.REPO).config_doc(REAL_CONFIG)
STEPS = 190.0


def traced(op_seconds, counters, config=FALCON, kind="serve", busy_s=2.9,
           step_bytes=9.0e9, programs=None):
    return result(
        kind=kind, peaks=PEAKS, config=config, step_bytes=step_bytes,
        telemetry={"counters": counters},
        trace={"window_s": 3.0, "busy_s": busy_s, "op_seconds": op_seconds,
               "counters": {"decode.steps": STEPS, "decode.prefills": 30},
               "programs": programs if programs is not None else {
                   "jit_decode_step_b64(1)": {"runs": STEPS, "seconds": 2.6},
                   "jit_prefill_p256(2)": {"runs": 30.0, "seconds": 0.3}}})


COUNTERS = {"decode.steps": 3000,
            "decode.state_rows_updated": 3000 * 63.5 * 6}


def test_ssm_state_update_roofline_by_hand():
    """63.5 live rows x 6 layers x 8.39 MB a step = 3.2 GB; the kernel
    0.95 s over the 190 steps of a traced window: 5 ms a step."""
    read = Manifest(toy.REPO).reader("ssm_state_update_roofline")
    ctx = traced({"ssm_state_update": 0.95, "fusion": 1.5}, COUNTERS)
    least_s = 63.5 * 6 * 8_388_608 / 819e9
    assert read(ctx) == pytest.approx(100 * least_s / (0.95 / STEPS))
    assert 75 < read(ctx) < 80
    # the step program by its own name, not the sub-window's longest: a
    # start-up wave's prefills change nothing
    ctx.trace["programs"]["jit_prefill_p1024(3)"] = {"runs": 60.0,
                                                     "seconds": 5.0}
    assert read(ctx) == pytest.approx(100 * least_s / (0.95 / STEPS))
    # nothing to read: no such kernel, no counter (the parent's program),
    # no decode step in the traced window, an untraced run, a trainer
    assert read(traced({"fusion": 1.0}, COUNTERS)) is None
    assert read(traced({"ssm_state_update": 0.95},
                       {"decode.steps": 3000})) is None
    assert read(traced({"ssm_state_update": 0.95}, COUNTERS, programs={
        "jit_prefill_p256(2)": {"runs": 30.0, "seconds": 0.3}})) is None
    assert read(result(kind="serve", peaks=PEAKS, config=FALCON,
                       telemetry={"counters": COUNTERS})) is None
    assert read(traced({"ssm_state_update": 0.95}, COUNTERS,
                       kind="train")) is None


def test_hybrid_paged_gqa_attention_roofline_by_hand():
    """63.5 rows at a mean context of 380 in 6 layers: 145 K keys a step at
    2,048 bytes = 0.30 GB; the kernel 0.25 s over the 190 steps."""
    read = Manifest(toy.REPO).reader("hybrid_paged_gqa_attention_roofline")
    keys = 63.5 * 380 * 6
    counters = dict(COUNTERS, **{"decode.kv_tokens_attended": 3000 * keys})
    ctx = traced({"paged_gqa_attention": 0.25, "ssm_state_update": 0.95},
                 counters)
    assert read(ctx) == pytest.approx(
        100 * keys * 2048 / 819e9 / (0.25 / STEPS))
    assert 25 < read(ctx) < 30
    # nothing to read: no such kernel, another family's cell (no state
    # counter), no keys counted, no decode step traced, a trainer
    assert read(traced({"ssm_state_update": 0.95}, counters)) is None
    assert read(traced({"paged_gqa_attention": 0.25}, {
        "decode.steps": 3000,
        "decode.kv_tokens_attended": 3000 * keys})) is None
    assert read(traced({"paged_gqa_attention": 0.25}, COUNTERS)) is None
    assert read(traced({"paged_gqa_attention": 0.25}, counters,
                       programs={})) is None
    assert read(traced({"paged_gqa_attention": 0.25}, counters,
                       kind="train")) is None


def test_hybrid_decode_step_roofline_by_hand():
    read = Manifest(toy.REPO).reader("hybrid_decode_step_roofline")
    ctx = traced({"ssm_state_update": 0.95}, COUNTERS)
    assert read(ctx) == pytest.approx(100 * 9.0e9 / 819e9 / (2.6 / STEPS))
    assert 75 < read(ctx) < 85
    # another family's cell has no state counter: nothing to read
    assert read(traced({}, {"decode.steps": 3000})) is None
    assert read(traced({}, COUNTERS, programs={})) is None
    assert read(traced({}, COUNTERS, kind="train")) is None
    assert read(result(kind="serve", peaks=PEAKS, config=FALCON,
                       telemetry={"counters": COUNTERS})) is None


def test_ssm_busy_share_by_hand():
    read = Manifest(toy.REPO).reader("ssm_busy_share.serve")
    ctx = traced({"ssm_state_update": 0.95, "fusion": 1.5,
                  "paged_gqa_attention": 0.1}, {})
    assert read(ctx) == pytest.approx(100 * 0.95 / 2.9)
    # a scan kernel, once there is one, joins it
    ctx = traced({"ssm_state_update": 0.95, "ssm_chunk_scan": 0.05}, {})
    assert read(ctx) == pytest.approx(100 * 1.0 / 2.9)
    assert read(traced({"fusion": 1.0}, {})) is None
    assert read(traced({"ssm_state_update": 0.3}, {}, busy_s=0)) is None
    assert read(traced({"ssm_state_update": 0.3}, {}, kind="train")) is None
    assert read(result(kind="serve")) is None
