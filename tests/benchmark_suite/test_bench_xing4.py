"""The `xing4` family at toy sizes through `benchmark.run`'s own path on the
CPU: its cell runs to `correct: true` with the module drafting, its
counters reach its readers and its byte count, the real manifest with its
configuration is sound, and the new readers by hand."""

import json
import os

import pytest

from benchmark import flops_xing4, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "Xing4.0-29B-A4B"
REAL_CONFIG = "xing4_29b_pp8"
REAL_CELL = "xing4_29b_pp8_serve_closed_c96"
CELL = "xing4_closed"
TOY_XING4 = {
    "name": "toy_xing4", "kind": "serve", "family": "xing4",
    "source": "none: a test preset",
    "vocab_size": 128, "hidden_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "layers_held": [1, 2, 3],
    "first_k_dense_replace": 2, "num_dense_layers": 1,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "experts_held": [0, 16], "routed_scaling_factor": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "max_context": 64, "dtype": "float32", "kv_pages": 8 * 16 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [10, 20, 14], "temperatures": [0.0, 1.0, 2.5],
              "new_tokens": [9, 12, 12], "rejected_rows": 2, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 2.5}}}
JOINED = ("routed_decode_step_roofline", "batch_occupancy_avg",
          "completed_requests_per_s", "window_hbm_gb.serve",
          "moe_experts_hit_per_layer", "moe_held_pair_share",
          "prefill_time_share.serve", "step_ahead_share.serve",
          "mla_attention_busy_share.serve", "mhc_busy_share.serve",
          "prefill_wait_share.serve", "prefill_padded_token_share.serve",
          "idle_between_prefills_share.serve", "engine_cpu_share.serve",
          "engine_wait_unexplained_share.serve")
NEW_METRICS = {"mtp_accept_share.serve": ("%", "higher"),
               "tokens_per_row_step.serve": ("tokens", "higher"),
               "mtp_tokens_discarded_share.serve": ("%", "lower")}


@pytest.fixture(scope="module")
def xing4_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy xing4 cell that
    reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("xing4_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_xing4.json"), "w") as f:
        json.dump(TOY_XING4, f)
    with open(os.path.join(data, "traffic", "xing4_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48, temperature=2.5), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_xing4", "source": "none: a test preset",
        "reduced": [], "file": "benchmark/configs/toy_xing4.json",
        "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_xing4", "traffic": "xing4_closed",
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
        == (1, "closed_c96_reasoning", REAL_CONFIG)
    assert "drafts 1 and verifies 2" in cell["why"]
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "serve_tokens_per_s"} <= reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    # the other models' kernels count their own bytes and pairs
    assert not {"paged_attention_roofline", "paged_gqa_attention_roofline",
                "hybrid_decode_step_roofline", "ssm_busy_share.serve",
                "linear_attention_busy_share.serve",
                "grouped_polyglu_roofline",
                "rows_past_window_share.serve"} & reported
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    for name, (unit, better) in NEW_METRICS.items():   # came with this cell
        entry = toy.entry(man, "per_layer", name)
        assert REAL_CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["moves"],
                entry["source"], entry["layer"]) == (
            unit, better, "serve_tokens_per_s", "program_counter",
            "decode engine")
        assert os.path.isfile(man.reader_path(name))


def test_the_real_manifest_is_sound_with_the_xing4_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)


def test_the_family_file_keeps_the_contract():
    man = Manifest(toy.REPO)
    family = man.family("xing4")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = man.config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    assert (cfg.n_layers, cfg.num_heads, cfg.first_k_dense, cfg.mtp_layer) \
        == (5, 32, 1, 5)
    assert [cfg.is_moe(i) for i in range(5)] == [False] + [True] * 4
    assert (cfg.latent_dim, cfg.latent_row_width, cfg.n_maps) \
        == (576, 640, 24)
    assert cfg.experts_held == (0, 64) == (0, cfg.num_experts)
    assert cfg.hc_res_clamp == (-30.0, 30.0) and cfg.hc_eps == 1e-6
    served = cfg.served()
    assert served.draft and len(served.cache_layout()) == 6
    assert family.slots(doc) == 64
    assert family.traffic_vocab(cfg, doc) == 131072
    traffic = man.traffic_doc("closed_c96_reasoning")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 64 * 96 + 1 and not eng["prefix_cache"]
    assert eng["prefill_buckets"] == [256, 384, 512, 768, 1024, 1536, 2048]
    assert traffic["prompt_tokens"]["max"] <= max(eng["prefill_buckets"])
    assert traffic["temperature"] == doc["check"]["beside"]["temperature"]
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=6144), traffic)
    with pytest.raises(ValueError, match="a temperature"):
        family.engine_config(
            dict(doc, check=dict(doc["check"], temperatures=[0.0])), traffic)


def test_the_configuration_carries_every_published_number():
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
    for key in ("published", "deployment", "departures", "reduced_note"):
        assert doc[key]
    assert set(doc["assumed"]) >= {"mtp", "residual", "block", "weights",
                                   "number_format", "temperature", "engine"}
    assert "8 stages of 5 layers" in doc["deployment"]
    assert any("LAST pipeline stage" in d for d in doc["departures"])
    assert any("a sixth of a step" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    f = flops_xing4
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
                 + 32 * 128 * 3584)
    assert attention == 28_409_856
    assert f.phi_params(doc) == 2 * 4 * 3584 * 24 == 688_128
    expert = 3 * 3584 * 1024
    assert f.moe_layer_fixed_params(doc) == attention + 688_128 + expert \
        + 3584 * 64
    moe = f.moe_layer_fixed_params(doc) + 64 * expert
    dense = attention + 688_128 + 3 * 3584 * 9216
    assert f.params_held(doc) == dense + 5 * moe + 2 * 3584 * 3584 \
        + 2 * 3584 * 131072 == 4_818_305_024
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    # a step of 64 rows at 1,500-token contexts that hits every expert of
    # the five routed layers: 9.4 GB, 11.4 ms at 819 GB/s
    rows = 6 * 64 * 1500
    least = f.step_bytes(doc, experts_hit=5 * 64, latent_rows=rows, rows=64)
    assert least == pytest.approx(
        f.non_expert_weight_bytes(doc) + 320 * expert * 2
        + 4 * 64 * 3584 * 2 + rows * 1152)
    assert 1.64e9 < f.non_expert_weight_bytes(doc) < 1.66e9
    assert 9.3e9 < least < 9.5e9


def test_the_xing4_cell_runs_to_correct_on_the_cpu(xing4_root):
    out = run.run_cell(xing4_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=True, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW_METRICS) | {"moe_experts_hit_per_layer",
                               "moe_held_pair_share"} <= set(m)
    assert 0 < m["mtp_accept_share.serve"] < 100
    assert 1 < m["tokens_per_row_step.serve"] < 2
    assert 0 <= m["mtp_tokens_discarded_share.serve"] < 50
    assert 0 < m["moe_experts_hit_per_layer"] <= 16      # the held layers'
    assert m["moe_held_pair_share"] == pytest.approx(100)   # every expert


def test_the_readings_script_judges_the_reference_and_each_fault(
        xing4_root, capsys):
    from benchmark import readings_xing4, reference_xing4

    readings_xing4.main(["--config", "toy_xing4", "--traffic",
                         "xing4_closed", "--seed", "5"], root=xing4_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] == [
        "as it is", *reference_xing4.CONTROLS, "hidden_off", "redraw_p"]
    assert lines[0]["correct"] is True and lines[0]["accepted"] > 0
    assert not any(ln["correct"] for ln in lines[1:])


# -- the new readers, by hand --------------------------------------------------

def counted(counters, kind="serve"):
    return result(kind=kind, telemetry={"counters": counters})


def test_the_draft_readers_by_hand():
    man = Manifest(toy.REPO)
    c = {"decode.draft_proposed": 1000, "decode.draft_accepted": 800,
         "decode.tokens": 1790, "decode.rows_stepped": 1010,
         "decode.tokens_discarded": 10}
    assert man.reader("mtp_accept_share.serve")(counted(c)) == 80.0
    assert man.reader("tokens_per_row_step.serve")(counted(c)) \
        == pytest.approx(1790 / 1010)
    assert man.reader("mtp_tokens_discarded_share.serve")(counted(c)) \
        == pytest.approx(100 * 10 / 1800)
    for name in NEW_METRICS:        # a program that drafts nothing
        read = man.reader(name)
        assert read(counted({"decode.tokens": 640})) is None
        assert read(counted(c, kind="train")) is None
        assert read(result(kind="serve")) is None


def test_the_step_bytes_come_from_the_windows_counters():
    man = Manifest(toy.REPO)
    family, doc = man.family("xing4"), man.config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    snap = {"counters": {"decode.steps": 10, "decode.rows_stepped": 640,
                         "decode.moe_experts_hit": 2560,
                         "decode.draft_moe_experts_hit": 640,
                         "decode.kv_tokens_attended": 10 * 6 * 64 * 1500}}
    assert family.step_bytes(cfg, doc, 0.0, snap) == pytest.approx(
        flops_xing4.step_bytes(doc, 320, 6 * 64 * 1500, 64))
    assert family.step_bytes(cfg, doc, 0.0, {"counters": {}}) == 0.0
