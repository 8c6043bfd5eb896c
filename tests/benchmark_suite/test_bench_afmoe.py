"""The `afmoe` family at toy sizes through `benchmark.run`'s own path on the
CPU: its cell runs to `correct: true`, its counters reach its readers and
its byte count, and the real manifest with its configuration is sound."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_afmoe, run
from benchmark.manifest import Manifest

from . import toy

PUBLISHED_ROW = "Trinity-Large-Preview"
REAL_CELL = "trinity_large_tp8ep8_serve_closed_c96"
CELL = "afmoe_closed"
# the published pattern's first period and a half, at toy widths
TOY_AFMOE = {
    "name": "toy_afmoe", "kind": "serve", "family": "afmoe",
    "source": "none: a test preset",
    "vocab_size": 128, "hidden_size": 32, "head_dim": 8,
    "q_heads_held": 4, "kv_heads_held": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "layers_held": [0, 4, 5, 6, 7], "num_dense_layers": 1,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "experts_held": [0, 4], "route_scale": 2.448, "route_norm": True,
    "sliding_window": 16, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "max_context": 64, "dtype": "float32",
    "kv_pages": 8 * 16 + 1, "kv_ring_pages": 8 * 5 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [6, 20, 40], "new_tokens": 8, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}}
NEW_METRICS = ("moe_experts_hit_per_layer", "moe_held_pair_share",
               "rows_past_window_share.serve", "prefill_time_share.serve")


@pytest.fixture(scope="module")
def afmoe_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy afmoe cell that
    reports what the real afmoe cell reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("afmoe_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_afmoe.json"), "w") as f:
        json.dump(TOY_AFMOE, f)
    with open(os.path.join(data, "traffic", "afmoe_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_afmoe", "source": "none: a test preset", "reduced": [],
        "file": "benchmark/configs/toy_afmoe.json", "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_afmoe", "traffic": "afmoe_closed",
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
    assert man.cell(REAL_CELL)["chips"] == 1
    reported = toy.reported(man, REAL_CELL)
    assert set(NEW_METRICS) | {"paged_gqa_attention_roofline",
                               "routed_decode_step_roofline", "setup_s",
                               "serve_tokens_per_s"} <= reported
    assert "paged_attention_roofline" not in reported   # counts xglm's bytes
    # a closed loop at saturation: tokens per second is its end-to-end
    # metric. `tpot_p90_ms` follows how a seed's order bunches the long
    # prompts (4.1%, 2.6%, 4.0% over three sets of six seeds on the chip,
    # PERF.md PR 28) and is not reported, nor is any metric that moves it
    assert "tpot_p90_ms" not in reported
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))


def test_the_real_manifest_is_sound_with_the_afmoe_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)


def test_the_configuration_carries_every_published_number():
    """The catalog row's `config`, key by key: a number that differs is
    listed under `reduced`, and no width is."""
    rows = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if not os.path.isfile(rows):
        pytest.skip("no catalog beside this checkout")
    with open(rows) as f:
        published = next(r for r in map(json.loads, f)
                         if r["name"] == PUBLISHED_ROW)["config"]
    doc = Manifest(toy.REPO).config_doc("trinity_large_tp8ep8")
    differs = {k for k, v in published.items() if doc.get(k) != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "vocab_size"}
    assert differs <= set(doc["reduced"])
    widths = ("hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "num_experts", "num_experts_per_tok",
              "sliding_window")
    assert not set(widths) & set(doc["reduced"])
    for key in ("published", "deployment", "assumed", "departures"):
        assert doc[key]
    assert "8 chips share each layer" in doc["deployment"]


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc("trinity_large_tp8ep8")
    assert flops_afmoe.params_held(doc) == 4_046_585_856
    assert flops_afmoe.expert_bytes(doc) == 3 * 3072 * 3072 * 2
    assert flops_afmoe.kv_bytes_per_token_layer(doc) == 512
    eng = doc["engine"]
    per_slot = doc["max_context"] // eng["page_size"]
    ring = doc["sliding_window"] // eng["page_size"] + 1
    assert (per_slot, ring) == (160, 65)
    assert doc["kv_pages"] == eng["max_slots"] * per_slot + 1
    assert doc["kv_ring_pages"] == eng["max_slots"] * ring + 1
    # a step that hits 20 experts a layer at 64 rows of 3000 keys
    least = flops_afmoe.step_bytes(doc, experts_hit=80,
                                   kv_tokens=64 * 5 * 3000, rows=64)
    assert least == pytest.approx(
        flops_afmoe.non_expert_weight_bytes(doc) + 80 * 56_623_104
        + 64 * 3072 * 2 + 64 * 5 * 3000 * 512)
    assert 5.6e9 < least < 5.8e9
    # the paged kernel: K and V of each key once
    assert flops_afmoe.paged_gqa_bytes(doc, 1000) == 1000 * 512


def test_the_afmoe_cell_runs_to_correct_on_the_cpu(afmoe_root):
    out = run.run_cell(afmoe_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_afmoe_cells_counters_reach_its_readers(afmoe_root):
    out = run.run_cell(afmoe_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW_METRICS) <= set(m)
    assert 0 < m["moe_experts_hit_per_layer"] <= 4       # of 4 held
    assert 5 < m["moe_held_pair_share"] < 60             # 4 of 16 held
    assert 0 <= m["rows_past_window_share.serve"] <= 100
    assert 0 < m["prefill_time_share.serve"] < 100
    assert np.isfinite(m["decode_step_ms_p50"])


def test_the_readings_script_judges_the_reference_and_each_control(
        afmoe_root, capsys):
    from benchmark import readings_afmoe, reference_afmoe

    readings_afmoe.main(["--config", "toy_afmoe", "--traffic",
                         "afmoe_closed", "--seed", "5"], root=afmoe_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] \
        == ["as it is"] + list(reference_afmoe.CONTROLS)
    assert lines[0]["correct"] is True
    assert not lines[1]["correct"]          # every weight matrix in 8 bits
    worst = [max(v for n, v, _ in ln["compared"]
                 if n.startswith("prefill_logit_err")) for ln in lines]
    assert all(w > 20 * worst[0] for w in worst[1:])


def test_routed_decode_step_roofline_by_hand():
    """5.7 GB a step (the family's `step_bytes`, from the counters) against
    a step program of 10 ms; nothing on a program without the routing
    counters, in an untraced run, or for a trained model."""
    from benchmark import flops
    from benchmark.runners import result

    read = Manifest(toy.REPO).reader("routed_decode_step_roofline")
    peaks = flops.peaks("TPU v5 lite")
    trace = {"window_s": 3.0, "programs": {
        "jit_decode_step_b64(1)": {"runs": 150.0, "seconds": 1.5},
        "jit_prefill_p4096(2)": {"runs": 20.0, "seconds": 1.0}}}
    counted = {"counters": {"decode.steps": 3000,
                            "decode.moe_experts_hit": 240000}}
    ctx = result(kind="serve", peaks=peaks, trace=trace, step_bytes=5.7e9,
                 telemetry=counted)
    assert read(ctx) == pytest.approx(
        100 * 5.7e9 / peaks["hbm_bytes_per_s"] / 0.010)
    assert 60 < read(ctx) < 75
    assert read(result(kind="serve", peaks=peaks, trace=trace,
                       step_bytes=5.7e9,
                       telemetry={"counters": {"decode.steps": 3000}})) is None
    assert read(result(kind="serve", peaks=peaks, step_bytes=5.7e9,
                       telemetry=counted)) is None
    assert read(result(kind="train", peaks=peaks, trace=trace)) is None
