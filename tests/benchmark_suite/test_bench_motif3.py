"""The `motif3` family at toy sizes through `benchmark.run`'s own path on
the CPU: its cell runs to `correct: true`, its counters reach its readers
and its byte count, the real manifest with its configuration is sound, and
the new readers by hand on a fixture trace."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_motif3, run
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import result

from . import toy

PUBLISHED_ROW = "Motif-3-Beta"
REAL_CONFIG = "motif3_beta_dp_ep8"
REAL_CELL = "motif3_beta_dp_ep8_serve_closed_c96"
CELL = "motif3_closed"
TOY_MOTIF3 = {
    "name": "toy_motif3", "kind": "serve", "family": "motif3",
    "source": "none: a test preset",
    "vocab_size": 128, "hidden_size": 32, "num_attention_heads": 10,
    "num_key_value_heads": 2, "num_noise_heads": 2, "head_dim": 12,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "layers_held": [1, 2, 3, 4, 5],
    "n_dense_first_layers": 2, "num_dense_layers": 1,
    "sliding_window": 8, "sliding_window_period": 4,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_experts": 32, "experts_top_k": 8, "num_shared_experts": 1,
    "experts_held": [0, 8], "route_scale": 2, "route_norm": True,
    "mhc_expansion_rate": 4, "mhc_sinkhorn_iters": 20,
    "hidden_clamp": 1000000, "polynorm_output_scale": 0.5,
    "polynorm_bias_clamp": 0.5, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "max_context": 64, "dtype": "float32", "kv_pages": 8 * 16 + 1,
    "kv_ring_pages": 8 * 3 + 1,
    "engine": {"max_slots": 8, "page_size": 4, "max_new_tokens": 40,
               "max_queue_depth": 64, "prefill_buckets": [16, 32, 64],
               "weight_quant": "none", "prefix_cache": False},
    "check": {"prompt_tokens": [10, 20, 40], "new_tokens": 8, "pad_min": 64,
              "beside": {"requests": 5, "prompt_tokens": [5, 12, 22],
                         "new_tokens": 40, "temperature": 0.8}}}
JOINED = ("moe_experts_hit_per_layer", "moe_held_pair_share",
          "rows_past_window_share.serve", "prefill_time_share.serve",
          "mla_attention_busy_share.serve", "prefill_wait_share.serve",
          "prefill_padded_token_share.serve",
          "idle_between_prefills_share.serve", "engine_cpu_share.serve",
          "engine_wait_unexplained_share.serve")
NEW_METRICS = ("mhc_busy_share.serve", "grouped_polyglu_roofline")
# shares that need the rows or pairs a traced window GAVE a kernel have no
# entry and no reader: `trace["programs"]` counts a program that was in
# flight when the profiler started or stopped as a whole run (its event
# begins at the trace's first timestamp: 11.5 ms of a 180 ms prefill read
# 0.849 runs, my chip run, PR 49), and a step's rows do not come from HBM
# at all (`mhc_post`: 94 MB of least bytes in 63 us a step); a decode
# step's share needs a traced window that holds a step. The counts they
# would divide are `flops_motif3`'s, held to the chip's readings below.
NOT_LISTED = ("mhc_pre_roofline", "mhc_post_roofline",
              "banded_mla_prefill_attention_roofline",
              "latent_ring_decode_step_roofline")


@pytest.fixture(scope="module")
def motif3_root(tmp_path_factory):
    """The toy root and, by files and entries alone, a toy motif3 cell that
    reports what the real one reports."""
    root = toy.make_root(str(tmp_path_factory.mktemp("motif3_root")))
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "toy_motif3.json"), "w") as f:
        json.dump(TOY_MOTIF3, f)
    with open(os.path.join(data, "traffic", "motif3_closed.json"),
              "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=9,
                       max_context=48), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_motif3", "source": "none: a test preset",
        "reduced": [], "file": "benchmark/configs/toy_motif3.json",
        "why": "toy"})
    doc["workloads"].append({
        "name": CELL, "config": "toy_motif3", "traffic": "motif3_closed",
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
    assert "attention sees its full share" in cell["why"]
    reported = toy.reported(man, REAL_CELL)
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_avg",
        "completed_requests_per_s", "window_hbm_gb.serve",
        "step_ahead_share.serve"} <= reported
    assert "tpot_p90_ms" not in reported        # a loop at saturation
    assert not set(NOT_LISTED) & reported
    for name in NOT_LISTED:             # a reader is shipped with its entry
        assert not os.path.isfile(man.reader_path(name))
    # the other models' kernels count their own bytes and pairs
    assert not {"paged_attention_roofline", "paged_gqa_attention_roofline",
                "mla_prefill_attention_roofline",
                "routed_decode_step_roofline", "hybrid_decode_step_roofline",
                "ssm_busy_share.serve", "linear_attention_busy_share.serve",
                "gated_delta_state_update_roofline"} & reported
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in man.metrics_of(REAL_CELL, "per_layer"))
    for name in NEW_METRICS:        # came with this cell
        entry = toy.entry(man, "per_layer", name)
        assert REAL_CELL in entry["workloads"]
        assert (entry["unit"], entry["moves"], entry["source"]) \
            == ("%", "serve_tokens_per_s", "device_trace")
        assert entry["better"] == ("lower" if name.startswith("mhc_busy")
                                   else "higher")
        assert os.path.isfile(man.reader_path(name))


def test_the_real_manifest_is_sound_with_the_motif3_cell():
    man = Manifest(toy.REPO)
    assert man.problems() == []
    holds(man)


def test_the_family_file_keeps_the_contract():
    man = Manifest(toy.REPO)
    family = man.family("motif3")
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn)), fn
    doc = man.config_doc(REAL_CONFIG)
    cfg = family.model_config(doc)
    assert (cfg.n_layers, cfg.num_heads, cfg.num_kv_heads, cfg.group) \
        == (5, 80, 16, 5)
    assert cfg.num_signal_heads == 64 and cfg.n_maps == 24
    assert [cfg.window_of(i) for i in range(5)] == [128, 128, 0, 128, 128]
    assert [cfg.is_moe(i) for i in range(5)] == [False] + [True] * 4
    assert (cfg.qk_nope_head_dim, cfg.latent_dim, cfg.latent_row_width) \
        == (128, 576, 640)
    assert cfg.experts_held == (0, 48) and cfg.num_experts == 384
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    assert family.slots(doc) == 64
    assert family.traffic_vocab(cfg, doc) == 27520
    traffic = man.traffic_doc("closed_c96_longdoc")
    eng = family.engine_config(doc, traffic)
    assert eng["kv_pages"] == 64 * 288 + 1 and not eng["prefix_cache"]
    assert eng["kv_ring_pages"] == 64 * 3 + 1
    buckets = eng["prefill_buckets"]
    # cell 7's powers of two and, from 2048 up, the bucket halfway between
    # two: padding a third of a prompt at most (PERF.md section 5, cell 8)
    assert buckets == [1024, 2048, 3072, 4096, 6144, 8192, 12288, 16384]
    lands = [next(b for b in buckets if b >= n)
             for n in doc["check"]["prompt_tokens"]]
    # five check prompts, five programs, the three halfway buckets among
    # them; 4096, 8192 and 16384 are the same builder at another length
    assert lands == [1024, 2048, 3072, 6144, 12288]
    with pytest.raises(ValueError, match="kv_pages"):
        family.engine_config(dict(doc, kv_pages=18432), traffic)
    with pytest.raises(ValueError, match="kv_ring_pages"):
        family.engine_config(dict(doc, kv_ring_pages=192), traffic)
    short = dict(doc, check=dict(doc["check"], prompt_tokens=[100, 703]))
    with pytest.raises(ValueError, match="window"):
        family.engine_config(short, traffic)


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
    assert differs <= set(doc["reduced"])
    assert doc["reduced"] == man.configs[REAL_CONFIG]["reduced"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "head_dim", "qk_rope_head_dim",
              "v_head_dim", "experts_top_k", "num_experts",
              "num_attention_heads", "num_key_value_heads",
              "mhc_expansion_rate")
    assert not set(widths) & set(doc["reduced"])
    assert doc["rope_scaling"] == row["config"]["rope_scaling"]
    for key in ("published", "deployment", "departures"):
        assert doc[key]
    # every inference ISSUE 49 lists has its ground
    assert set(doc["assumed"]) >= {"heads", "lambda", "gate", "layers",
                                   "residual", "polynorm", "no_ops",
                                   "norms", "weights"}
    assert "expert-parallel over 8" in doc["deployment"]
    assert any("1/8 of the pairs" in d for d in doc["departures"])
    assert any("multi-token prediction" in d for d in doc["departures"])


def test_the_cut_is_the_arithmetic_the_configuration_states():
    doc = Manifest(toy.REPO).config_doc(REAL_CONFIG)
    f = flops_motif3
    assert f.attention_weight_params(doc) == (
        4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 + 512 * 16 * 256
        + 4096 * 64 + 2 * 4096 * 8192) == 91_750_400
    assert f.phi_params(doc) == 2 * 16384 * 24 == 786_432
    assert f.expert_params(doc) == 3 * 4096 * 1280 == 15_728_640
    assert f.expert_bytes(doc) == 31_457_280
    assert (f.layers(doc), f.moe_layers(doc), f.full_layers(doc)) \
        == (5, 4, 1)
    assert f.params_held(doc) == (
        5 * (91_750_400 + 786_432) + 3 * 4096 * 12288
        + 4 * (49 * 15_728_640 + 4096 * 384) + 2 * 27520 * 4096) \
        == 3_928_227_840
    assert f.latent_row_bytes(doc) == 1152
    eng = doc["engine"]
    assert doc["kv_pages"] == eng["max_slots"] \
        * (doc["max_context"] // eng["page_size"]) + 1
    assert doc["kv_ring_pages"] == eng["max_slots"] * 3 + 1
    # a step that hits 35 experts a layer, 64 rows at 5,000-token contexts:
    # the full layer's pages and the four rings' 128 rows
    rows = 64 * (5000 + 4 * 128)
    least = f.step_bytes(doc, experts_hit=140, latent_rows=rows, rows=64)
    assert least == pytest.approx(
        f.non_expert_weight_bytes(doc) + 140 * 31_457_280 + 64 * 4096 * 2
        + rows * 1152)
    assert 1.58e9 < f.non_expert_weight_bytes(doc) < 1.60e9
    assert 6.3e9 < least < 6.5e9
    # the prefill kernel's pairs: one triangle and four bands
    assert f.prefill_pairs(doc, 4096) == 4096 * 4097 / 2 \
        + 4 * (4096 * 128 - 128 * 127 / 2)
    assert f.prefill_pairs(doc, 16384) / f.prefill_pairs(doc, 4096) \
        == pytest.approx(13.6, abs=0.1)     # the triangle 16x, the bands 4x
    assert f.mla_prefill_flops(doc, 1) == 2 * 80 * 320
    assert f.mhc_pre_bytes(doc, 1) == 4 * 4096 * 5
    assert f.mhc_post_bytes(doc, 1) == 4 * 4096 * 9
    assert f.sublayers(doc) == 10
    # 224 KB a token a sublayer
    assert f.mhc_pre_bytes(doc, 1) + f.mhc_post_bytes(doc, 1) == 229_376


def test_the_motif3_cell_runs_to_correct_on_the_cpu(motif3_root):
    out = run.run_cell(motif3_root, CELL, seed=2 ** 31 + 5, seconds=1.5,
                       trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}


def test_the_motif3_cells_counters_reach_its_readers(motif3_root):
    out = run.run_cell(motif3_root, CELL, seed=7, seconds=1.5, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"moe_experts_hit_per_layer", "moe_held_pair_share",
            "rows_past_window_share.serve",
            "prefill_time_share.serve"} <= set(m)
    assert 0 < m["moe_experts_hit_per_layer"] <= 8        # of 8 held
    assert 5 < m["moe_held_pair_share"] < 60              # 8 of 32 held
    assert 0 < m["rows_past_window_share.serve"] <= 100   # window 8
    # a CPU trace holds no kernel: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(m)


def test_the_readings_script_judges_the_reference_and_each_control(
        motif3_root, capsys):
    from benchmark import readings_motif3, reference_motif3

    readings_motif3.main(["--config", "toy_motif3", "--traffic",
                          "motif3_closed", "--seed", "5"], root=motif3_root)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [ln["reference"] for ln in lines] \
        == ["as it is"] + list(reference_motif3.CONTROLS)
    assert lines[0]["correct"] is True
    assert not lines[-1]["correct"]         # every weight matrix in 8 bits
    worst = [max(v for n, v, _ in ln["compared"]
                 if n.startswith("prefill_logit_err")) for ln in lines]
    assert all(w > 20 * worst[0] for w in worst[1:])
    # the `latent` control is read where it acts: the rows themselves
    rows = [max(v for n, v, _ in ln["compared"]
                if n.startswith("latent_err")) for ln in lines]
    assert rows[0] < 1e-5 and rows[1] > reference_motif3.LATENT_ERR


# -- the new readers, by hand --------------------------------------------------

PEAKS = flops.peaks("TPU v5 lite")
MOTIF3 = Manifest(toy.REPO).config_doc(REAL_CONFIG)
BYTES_S, FLOPS_S = 819e9, 197e12


def traced(op_seconds, counters, config=MOTIF3, kind="serve", busy_s=2.4,
           step_bytes=6.4e9):
    return result(
        kind=kind, peaks=PEAKS, config=config, step_bytes=step_bytes,
        telemetry={"counters": counters},
        trace={"window_s": 3.0, "busy_s": busy_s, "op_seconds": op_seconds,
               "counters": {"decode.steps": 100, "decode.prefills": 6},
               "programs": {
                   "jit_decode_step_b64(1)": {"runs": 100.0, "seconds": 1.0},
                   "jit_prefill_p4096(2)": {"runs": 4.0, "seconds": 0.6},
                   "jit_prefill_p16384(5)": {"runs": 2.0,
                                             "seconds": 0.8}}})


def test_mhc_busy_share_by_hand():
    read = Manifest(toy.REPO).reader("mhc_busy_share.serve")
    ctx = traced({"mhc_pre": 0.12, "mhc_post": 0.24, "fusion": 1.0}, {})
    assert read(ctx) == pytest.approx(100 * 0.36 / 2.4)
    assert read(traced({"fusion": 1.0}, {})) is None
    assert read(traced({"mhc_pre": 0.1}, {}, busy_s=0)) is None
    assert read(traced({"mhc_pre": 0.1}, {}, kind="train")) is None
    assert read(result(kind="serve")) is None


# one whole execution of each prefill program and the median decode step, ms
# of each kernel's calls inside it, from the module and operation events of
# two traced runs (my chip runs, PR 49, seeds 3000049501 and -502):
# bucket -> (mhc_pre x 10, mhc_post x 10, a band layer's call, the full
# layer's call)
ON_THE_CHIP = {2048: (3.577, 4.566, 0.515, 0.966),
               4096: (7.066, 9.095, 1.052, 3.437),
               8192: (14.029, 18.136, 2.117, 12.929),
               16384: (27.982, 36.246, 4.257, 50.107)}


@pytest.mark.parametrize("bucket", sorted(ON_THE_CHIP))
def test_the_mhc_bytes_by_hand_against_the_chips_seconds(bucket):
    """A prefill hands both kernels its bucket ten times (ten sublayers):
    `mhc_pre` reads four float32 streams and writes u, `mhc_post` reads
    them and y and writes them. Against the seconds the chip took they are
    57-59% and 81% of 819 GB/s at every bucket: under 100, and the same
    share whatever the bucket, as a count of bytes a row must be."""
    pre_ms, post_ms = ON_THE_CHIP[bucket][:2]
    n = flops_motif3.sublayers(MOTIF3)
    assert n == 10
    pre = flops_motif3.mhc_pre_bytes(MOTIF3, bucket) * n
    post = flops_motif3.mhc_post_bytes(MOTIF3, bucket) * n
    assert pre == bucket * 10 * 4 * 4096 * 5
    assert post == bucket * 10 * 4 * 4096 * 9
    assert 57 < 100 * pre / BYTES_S / (pre_ms / 1e3) < 59
    assert 80.5 < 100 * post / BYTES_S / (post_ms / 1e3) < 81.5


def test_a_steps_rows_are_not_hbm_traffic_for_mhc_post():
    """Why no roofline of `mhc_post` over a window's rows is listed: a
    step's ten calls took 63 us for 64 rows (both traced runs), 183% of
    the bandwidth by the same count: XLA keeps a step's 4 MB of streams in
    VMEM from call to call (`S(1)` on the results in the optimised HLO),
    and a window of steps alone would read over 100."""
    moved = flops_motif3.mhc_post_bytes(MOTIF3, 64) * 10
    assert moved == pytest.approx(94.4e6, rel=1e-3)
    assert 100 * moved / BYTES_S / 63.0e-6 > 105


@pytest.mark.parametrize("bucket", sorted(ON_THE_CHIP))
def test_the_prefill_pairs_by_hand_against_the_chips_seconds(bucket):
    """One full layer's triangle and four window layers' bands a bucket,
    2 x 80 x (192 + 128) operations a pair; the band's call grows with S
    (x2.0 a doubling on the chip) and the triangle's with S^2 (x3.6-3.9),
    and neither passes the peak."""
    f = flops_motif3
    band_ms, full_ms = ON_THE_CHIP[bucket][2:]
    tri = bucket * (bucket + 1) / 2
    band = bucket * 128 - 128 * 127 / 2
    assert f.prefill_pairs(MOTIF3, bucket) == tri + 4 * band
    assert f.mla_prefill_flops(MOTIF3, 1.0) == 2 * 80 * 320
    full_share = 100 * f.mla_prefill_flops(MOTIF3, tri) / FLOPS_S \
        / (full_ms / 1e3)
    band_share = 100 * f.mla_prefill_flops(MOTIF3, band) / FLOPS_S \
        / (band_ms / 1e3)
    assert 25 < full_share < 75 and 12 < band_share < 13
    if bucket > 2048:
        half = ON_THE_CHIP[bucket // 2]
        assert 1.9 < band_ms / half[2] < 2.1
        assert 3.5 < full_ms / half[3] < 4.0


def test_grouped_polyglu_roofline_by_hand():
    """100 steps that hit 140 experts each (35 a layer) and six prefill
    runs that reach all 4 x 48 held experts, 31.5 MB an expert."""
    read = Manifest(toy.REPO).reader("grouped_polyglu_roofline")
    counters = {"decode.steps": 1000, "decode.moe_experts_hit": 140_000}
    ctx = traced({"grouped_polyglu": 0.9, "fusion": 1.0}, counters)
    experts = 100 * 140 + 6 * 4 * 48
    assert read(ctx) == pytest.approx(
        100 * experts * 31_457_280 / BYTES_S / 0.9)
    assert 60 < read(ctx) < 70
    # a traced window of prefills alone (this cell's, on the chip)
    burst = traced({"grouped_polyglu": 0.06}, {})
    del burst.trace["programs"]["jit_decode_step_b64(1)"]
    assert burst is not None and read(burst) == pytest.approx(
        100 * 6 * 192 * 31_457_280 / BYTES_S / 0.06)
    assert read(traced({"fusion": 1.0}, counters)) is None
    assert read(traced({"grouped_polyglu": 0.7}, counters,
                       config={"hidden_size": 3072})) is None
    nothing = traced({"grouped_polyglu": 0.7}, counters)
    nothing.trace["programs"] = {}
    assert read(nothing) is None
    assert read(traced({"grouped_polyglu": 0.7}, counters,
                       kind="train")) is None


def test_the_step_bytes_come_from_the_windows_counters():
    family = Manifest(toy.REPO).family("motif3")
    cfg = family.model_config(MOTIF3)
    snap = {"counters": {"decode.steps": 10, "decode.tokens": 640,
                         "decode.moe_experts_hit": 1400,
                         "decode.kv_tokens_attended": 10 * 64 * 5512}}
    assert family.step_bytes(cfg, MOTIF3, 0.0, snap) == pytest.approx(
        flops_motif3.step_bytes(MOTIF3, 140, 64 * 5512, 64))
    assert family.step_bytes(cfg, MOTIF3, 0.0, {"counters": {}}) == 0.0
    assert np.isfinite(family.step_bytes(cfg, MOTIF3, 0.0, snap))
