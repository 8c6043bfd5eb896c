"""The `lfm2` family at toy sizes through `benchmark.run`'s own path on the
CPU: its cell runs to `correct: true`, its counters reach its readers and
its byte count, the real manifest with its configuration is sound, the cut
is the arithmetic the configuration states, and the new readers by hand on
a fixture trace."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_afmoe, flops_lfm2, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "LFM2-24B-A2B"
REAL_CONFIG = "lfm2_24b_pp5"
REAL_CELL = "lfm2_24b_pp5_serve_closed_c192"
CELL = "lfm2_closed"
PATTERN = ["conv", "conv", "full_attention", "conv"]
TOY_LFM2 = {
    "name": "toy_lfm2", "kind": "serve", "family": "lfm2",
    "source": "none: a test preset",
    "conv_L_cache": 3, "conv_bias": False, "use_expert_bias": True,
    "hidden_size": 32, "intermediate_size": 64, "layer_types": PATTERN * 3,
    "moe_intermediate_size": 16, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 128,
    "layers_held": [0, 1, 2, 3], "experts_held": [0, 8],
    "max_context": 64, "dtype": "float32", "kv_pages": 8 * 16 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [1, 2, 15, 40],
              "temperatures": [0.0, 4.0, 0.0, 4.0], "new_tokens": 8,
              "pad_min": 64,
              "beside": {"requests": 4, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}}
JOINED = (
    "routed_decode_step_roofline", "batch_occupancy_avg",
    "completed_requests_per_s", "window_hbm_gb.serve",
    "moe_experts_hit_per_layer", "moe_held_pair_share",
    "prefill_time_share.serve", "step_ahead_share.serve",
    "prefill_wait_share.serve", "prefill_padded_token_share.serve",
    "idle_between_prefills_share.serve", "engine_cpu_share.serve",
    "engine_wait_unexplained_share.serve")
# the readers this PR brings as files (PERF.md section 7, Left by PR 58,
# says why a `benchmark` PR has to list them): held here by what each reads
# of a worked example, whether or not BENCHMARK.json names it
NEW_READERS = ("lfm2_paged_gqa_attention_roofline",
               "moe_rows_per_expert_hit.serve",
               "conv_rows_per_step.serve")


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy lfm2 cell that
    reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("lfm2_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_lfm2.json"), "w") as f:
        json.dump(TOY_LFM2, f)
    with open(os.path.join(data, "traffic", "lfm2_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_lfm2", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_lfm2.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_lfm2", "traffic": "lfm2_closed",
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
        == (1, "closed_c192_assistant", REAL_CONFIG)
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | {"setup_s", "serve_tokens_per_s",
                          "compile_cache_misses"} <= reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    # one whole-step share, and no copy of another family's kernel shares:
    # the hybrid readers are gated on a RECURRENT state's counter
    assert not {"hybrid_decode_step_roofline", "ssm_state_update_roofline",
                "gated_delta_state_update_roofline",
                "paged_gqa_attention_roofline",
                "hybrid_paged_gqa_attention_roofline"} & reported
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))


def test_the_new_readers_load_by_name():
    man = Manifest(toy.REPO)
    for name in NEW_READERS:
        assert callable(man.reader(name)), name


def test_the_real_manifest_is_sound_with_the_lfm2_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    assert len(man.cells) >= 10 and REAL_CELL in man.cells
    assert all(w["chips"] == 1 for w in man.doc["workloads"])
    assert len(man.cell(REAL_CELL)["why"]) <= 200
    holds(man)


def test_the_family_file_keeps_the_contract():
    man = Manifest(toy.REPO)
    family = man.family("lfm2")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = man.config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    # every width is the published one, and nothing is cut but the depth
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) \
        == (2048, 64, 32, 8)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok) == (11776, 1536, 64, 4)
    assert cfg.experts_held == (0, 64) and cfg.vocab_size == 65536
    assert cfg.layer_types == tuple(PATTERN * 2) and cfg.n_layers == 8
    assert cfg.num_dense_layers == 2
    assert [cfg.is_moe(i) for i in range(8)] == [False] * 2 + [True] * 6
    assert (cfg.conv_L_cache, cfg.rms_norm_eps, cfg.rope_theta) \
        == (3, 1e-5, 1e6)
    assert cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    assert cfg.dtype == "bfloat16" and cfg.max_seq_len == 8192
    assert family.slots(doc) == 128
    assert family.traffic_vocab(cfg, doc) == 65536
    traffic = man.traffic_doc("closed_c192_assistant")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 128 * 128 + 1 and not eng["prefix_cache"]
    assert eng["prefill_buckets"] == [256, 384, 512, 768, 1024, 1536, 2048,
                                      3072, 4096]
    # check prompts shorter than the tail, and all of them mid-bucket
    prompts = doc["check"]["prompt_tokens"]
    assert prompts == [1, 2, 127, 129, 900, 3000]
    assert not set(prompts) & set(eng["prefill_buckets"])
    assert doc["check"]["new_tokens"] >= 41          # 40 steps and more
    assert doc["check"]["beside"]["requests"] + len(prompts) == 128
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=16384), traffic)
    with pytest.raises(ValueError, match="conv_bias"):
        family.model_config(dict(doc, conv_bias=True))
    with pytest.raises(ValueError, match="use_expert_bias"):
        family.model_config(dict(doc, use_expert_bias=False))
    with pytest.raises(ValueError, match="layers held"):
        family.model_config(dict(doc, layers_held=[1, 2, 3, 4, 5, 6, 7, 8]))
    # the family's configuration keys are in its docstring
    for key in ("layers_held", "experts_held", "max_context", "dtype",
                "kv_pages", "engine", "check", "temperatures", "beside",
                "layer_types", "num_dense_layers", "conv_L_cache"):
        assert f"`{key}`" in family.__doc__, key


def test_the_traffic_is_the_mix_the_issue_states():
    from benchmark.generators import requests

    traffic = Manifest(toy.REPO).traffic_doc("closed_c192_assistant")
    assert traffic["generator"] == "requests"
    assert traffic["arrival"] == {"kind": "closed", "clients": 192}
    assert traffic["temperature"] == 0.8 and traffic["ramp_s"] == 12.0
    assert traffic["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                        "min": 128, "max": 4096}
    assert traffic["new_tokens"] == {"median": 768, "sigma": 0.6,
                                     "min": 128, "max": 3072}
    assert (traffic["distinct_lengths"], traffic["max_context"]) \
        == (256, 8192)
    # a `lengths_seed` of its own
    others = [Manifest(toy.REPO).traffic_doc(w["traffic"]).get(
        "lengths_seed") for w in Manifest(toy.REPO).doc["workloads"]
        if w["name"] != REAL_CELL]
    assert traffic["lengths_seed"] not in others
    prompt, new = requests.lengths(traffic, traffic["distinct_lengths"])
    assert len(prompt) == 256
    assert prompt.min() >= 128 and prompt.max() <= 4096
    assert new.min() >= 1 and new.max() <= 3072
    assert (prompt + new).max() <= 8192
    # a prompt of about a thousand tokens, an answer of several hundred
    assert 1000 < prompt.mean() < 1500 and 700 < new.mean() < 1100


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
    assert differs == {"num_hidden_layers"}
    assert doc["reduced"] == man.configs[REAL_CONFIG]["reduced"] \
        == ["num_hidden_layers", "max_context"]
    assert doc["published"] == {"num_hidden_layers": 40,
                                "max_position_embeddings": 128000}
    assert len(doc["layer_types"]) == 40          # the group carried whole
    for key in ("deployment", "assumed", "departures", "reduced_note"):
        assert doc[key]
    assert "A pipeline of 5 stages of 8 layers" in doc["deployment"]
    assert set(doc["assumed"]) >= {"tie_word_embeddings", "conv",
                                   "attention", "routing", "weights",
                                   "number_format"}
    assert "268 MB" in doc["assumed"]["tie_word_embeddings"]
    assert "B | C | X" in doc["assumed"]["conv"]
    assert "1e-6" in doc["assumed"]["routing"]
    assert "normal(0, 0.03)" in doc["assumed"]["routing"]
    assert any("hand-off" in d for d in doc["departures"])
    assert any("final norm and the head" in d for d in doc["departures"])
    assert any("90%" in d and "96%" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    f = flops_lfm2
    assert f.layers_of(doc) == (6, 2) and f.dense_layers(doc) == 2
    assert f.head_dim(doc) == 64
    assert f.conv_params(doc) == 4 * 2048 * 2048 + 3 * 2048 == 16_783_360
    assert f.attention_params(doc) == 2048 * 64 * (64 + 16) + 128 \
        == 10_485_888
    assert f.dense_params(doc) == 3 * 2048 * 11776 == 72_351_744
    assert f.expert_params(doc) == 3 * 2048 * 1536 == 9_437_184
    assert 64 * f.expert_params(doc) == 603_979_776
    assert f.router_params(doc) == 2048 * 64 + 64
    # ISSUE 58's figure: 4,025 M parameters, 8.05 GB; the embedding once
    assert f.params_held(doc) == 4_025_293_440
    assert 8.04e9 < 2 * f.params_held(doc) < 8.06e9
    # the program's own parameters are these
    from paddle_tpu.models import lfm2

    family = Manifest(toy.REPO).family("lfm2")
    specs = lfm2.param_specs(family.model_config(doc))
    counted = sum(int(np.prod(shape)) for shape, _, _ in specs.values())
    assert counted == f.params_held(doc)
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    assert f.kv_bytes_per_token_layer(doc) == 2048
    assert 2 * f.kv_bytes_per_token_layer(doc) == 4096      # a token
    assert 4.29e9 < doc["kv_pages"] * 64 * 4096 < 4.30e9
    assert f.tail_bytes(doc) == 2 * 2048 * 2
    assert 6 * f.tail_bytes(doc) == 49_152                  # a slot
    # the accepted reader of moe_experts_hit_per_layer reads it right
    assert flops_afmoe.moe_layers(doc) == 6
    # a full step at ~1,650 tokens a slot, every expert hit: 8.9 GB, of
    # which the experts are nine tenths of the weights
    least = f.step_bytes(doc, experts_hit=6 * 64,
                         kv_tokens=128 * 2 * 1650, conv_rows=128 * 6)
    assert least == pytest.approx(
        f.non_expert_weight_bytes(doc) + 6 * 64 * 18_874_368
        + 128 * 2 * 1650 * 2048 + 128 * 6 * 8192)
    assert 8.8e9 < least < 9.0e9
    assert 0.89 < 6 * 64 * f.expert_bytes(doc) / (2 * f.params_held(doc)) \
        < 0.91
    # a prompt of ~1,300 tokens: ~1.3 TFLOP here, ~1 GFLOP a token (493 M
    # active parameters of these eight layers)
    per_token = f.prefill_flops(doc, 2) - f.prefill_flops(doc, 1)
    assert 0.95e9 < per_token < 1.05e9
    assert 1.2e12 < f.prefill_flops(doc, 1300) < 1.5e12


def test_the_lfm2_cell_runs_to_correct_on_the_cpu(lfm2_root):
    out = run.run_cell(lfm2_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_lfm2_cells_counters_reach_its_readers(lfm2_root):
    out = run.run_cell(lfm2_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["prefill_time_share.serve"] < 100
    assert 0 < m["batch_occupancy_avg"] <= 100
    # every expert held: every pair; three routed layers of four
    assert m["moe_held_pair_share"] == 100
    assert 0 < m["moe_experts_hit_per_layer"] <= 8
    # a CPU trace holds no kernel: the roofline finds nothing to read; the
    # two counters' readers are fed whatever the trace holds
    assert "lfm2_paged_gqa_attention_roofline" not in m


def test_step_bytes_reads_the_windows_counters():
    family = Manifest(toy.REPO).family("lfm2")
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    counters = {"decode.steps": 100, "decode.tokens": 12600,
                "decode.moe_experts_hit": 100 * 6 * 63,
                "decode.kv_tokens_attended": 100 * 126 * 2 * 1600,
                "decode.conv_rows_updated": 100 * 126 * 6}
    got = family.step_bytes(None, doc, 0.0, {"counters": counters})
    assert got == pytest.approx(flops_lfm2.step_bytes(
        doc, experts_hit=6 * 63, kv_tokens=126 * 2 * 1600,
        conv_rows=126 * 6))
    assert family.step_bytes(None, doc, 0.0, {"counters": {}}) == 0.0


# -- the new readers, by hand --------------------------------------------------

PEAKS = flops.peaks("TPU v5 lite")
LFM2 = Manifest(toy.REPO).config_doc(REAL_CONFIG)
STEPS = 200.0


def traced(op_seconds, counters, config=LFM2, kind="serve", programs=None):
    return result(
        kind=kind, peaks=PEAKS, config=config, step_bytes=8.9e9,
        telemetry={"counters": counters},
        trace={"window_s": 3.0, "busy_s": 2.9, "op_seconds": op_seconds,
               "counters": {"decode.steps": STEPS, "decode.prefills": 30},
               "programs": programs if programs is not None else {
                   "jit_decode_step_b128(1)": {"runs": STEPS,
                                               "seconds": 2.6},
                   "jit_prefill_p1024(2)": {"runs": 30.0, "seconds": 0.4}}})


COUNTERS = {"decode.steps": 3000,
            "decode.kv_tokens_attended": 3000 * 126 * 2 * 1650,
            "decode.conv_rows_updated": 3000 * 126 * 6,
            "decode.moe_pairs_held": 3000 * 126 * 4 * 6,
            "decode.moe_experts_hit": 3000 * 63.5 * 6}


def test_lfm2_paged_gqa_attention_roofline_by_hand():
    """126 live rows x 2 layers x 1,650 keys x 2,048 B a step = 0.85 GB,
    1.04 ms by the peak; the kernel 0.4 s over the 200 steps of a traced
    window: 2 ms a step."""
    read = Manifest(toy.REPO).reader(NEW_READERS[0])
    ctx = traced({"paged_gqa_attention": 0.4, "fusion": 1.5}, COUNTERS)
    least_s = 126 * 2 * 1650 * 2048 / 819e9
    assert read(ctx) == pytest.approx(100 * least_s / (0.4 / STEPS))
    assert 45 < read(ctx) < 60
    # nothing to read: no such kernel, no tail counter (another family's
    # cell), no decode step in the traced window, an untraced run, a trainer
    assert read(traced({"fusion": 1.0}, COUNTERS)) is None
    assert read(traced({"paged_gqa_attention": 0.4}, {
        k: v for k, v in COUNTERS.items()
        if k != "decode.conv_rows_updated"})) is None
    assert read(traced({"paged_gqa_attention": 0.4}, COUNTERS,
                       programs={"jit_prefill_p1024(2)": {
                           "runs": 30.0, "seconds": 0.4}})) is None
    assert read(result(kind="serve", peaks=PEAKS, config=LFM2,
                       telemetry={"counters": COUNTERS})) is None
    assert read(traced({"paged_gqa_attention": 0.4}, COUNTERS,
                       kind="train")) is None
    # and the accepted copies read nothing of this cell
    for other in ("hybrid_paged_gqa_attention_roofline",
                  "hybrid_decode_step_roofline"):
        assert Manifest(toy.REPO).reader(other)(ctx) is None


def test_the_two_counter_readers_by_hand():
    man = Manifest(toy.REPO)
    ctx = traced({}, COUNTERS)
    # 126 rows x 4 experts over 63.5 experts hit: 7.9 rows share a read
    assert man.reader("moe_rows_per_expert_hit.serve")(ctx) \
        == pytest.approx(126 * 4 / 63.5)
    # the rows whose tail a step moved are the live rows
    assert man.reader("conv_rows_per_step.serve")(ctx) \
        == pytest.approx(126.0)
    for name in ("moe_rows_per_expert_hit.serve",
                 "conv_rows_per_step.serve"):
        read = man.reader(name)
        assert read(traced({}, {"decode.steps": 3000})) is None
        assert read(traced({}, COUNTERS, kind="train")) is None
        assert read(result(kind="serve")) is None
