"""The family seam of the serving runner: `decoder_lm`'s weights did not
move with the code, and a served model of another family is added to a toy
root by files and entries alone and runs to `correct: true` on the CPU."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.families import decoder_lm
from benchmark.generators import requests
from benchmark.manifest import FAMILY_FUNCTIONS, Manifest
from benchmark.runners import serve

from . import toy

# sha256 over (name, dtype, shape, bytes) of every array, names sorted, of
# `make_params(toy preset, seed 2**31 + 11)` as runners/serve.py made them at
# commit 970a0b6, before they moved to families/decoder_lm.py
PARENT_PARAMS_SHA256 = \
    "81a378cf88de57f4fab5ec991e952a3b07dab531f1657c67760e929eaf430dbd"

# another family: decoder_lm's block under parameter names of its own,
# serving the first `vocab_rows` rows of its vocabulary, held against the
# reference through the engine it is handed (not over HTTP), and with a
# byte count that reads a counter of the window
RENAMED_LM = '''
"""decoder_lm under other parameter names, on a slice of its vocabulary."""

import numpy as np

from benchmark import reference
from benchmark.families import decoder_lm as base
from benchmark.generators.requests import FIRST_TOKEN_ID

PREFIX = "w."
BYTES_A_TOKEN = 1000.0


def model_config(config):
    return base.model_config(config)


def make_params(cfg, seed):
    return {PREFIX + k: v for k, v in base.make_params(cfg, seed).items()}


def _as_decoder_lm(params):
    return {k[len(PREFIX):]: v for k, v in params.items()}


def make_engine(cfg, params, config, traffic):
    return base.make_engine(cfg, _as_decoder_lm(params), config, traffic)


def slots(config):
    return base.slots(config)


def traffic_vocab(cfg, config):
    return config["vocab_rows"]


def check_correct(url, engine, params, cfg, check, seed):
    rng = np.random.RandomState(seed % (2 ** 32))
    worst, notes, gaps = 0.0, [], {}
    for n in check["prompt_tokens"]:
        prompt = rng.randint(FIRST_TOKEN_ID, check["vocab_rows"], n)
        chosen = engine.generate(prompt, max_new_tokens=check["new_tokens"],
                                 stop_at_eos=False, timeout=120)
        ok, gap, gaps[n] = reference.check_greedy(
            _as_decoder_lm(params), cfg.n_layers, cfg.n_head, prompt, chosen,
            pad_to=check["pad_to"])
        worst = max(worst, gap)
        if not ok:
            notes.append(f"greedy token {gap} under the reference's best")
    return ([["greedy_logit_gap", worst, reference.MARGIN]], notes,
            {"gaps": gaps})


def step_bytes(cfg, config, live_context_tokens, telemetry):
    return BYTES_A_TOKEN * telemetry["counters"]["decode.tokens"]
'''
VOCAB_ROWS = toy.SERVE_CONFIG["vocab_size"] // 2


def params_digest(params) -> str:
    h = hashlib.sha256()
    for name in sorted(params):
        a = np.asarray(params[name])
        for part in (name, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_decoder_lm_params_are_the_parents_byte_for_byte():
    cfg = decoder_lm.model_config(toy.SERVE_CONFIG)
    assert params_digest(decoder_lm.make_params(cfg, 2 ** 31 + 11)) \
        == PARENT_PARAMS_SHA256
    assert params_digest(decoder_lm.make_params(cfg, 2 ** 31 + 12)) \
        != PARENT_PARAMS_SHA256


def test_the_runner_names_nothing_of_decoder_lm():
    """What knows the family is in the family's file; the runner reaches it
    through the family's functions only."""
    with open(serve.__file__) as f:
        source = f.read()
    for word in ("paddle_tpu.models", "decoder_lm import", "check_greedy",
                 "flops", "DecodeConfig", "DecodeEngine", '"engine"',
                 "max_slots", "margin"):
        assert word not in source.split('"""', 2)[2], word
    with open(decoder_lm.__file__) as f:     # nor the family of the runner
        assert "benchmark.runners" not in f.read()
    for fn in FAMILY_FUNCTIONS:
        assert f"family.{fn}(" in source, fn
        assert callable(getattr(decoder_lm, fn))


def _files(root):
    out = {}
    for folder, _, names in os.walk(root):
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


@pytest.fixture(scope="module")
def second_family_root(tmp_path_factory):
    """The toy root, then a family, a configuration, a traffic mix and the
    entries for one cell of theirs: new files and BENCHMARK.json only."""
    root = toy.make_root(str(tmp_path_factory.mktemp("family_root")))
    before = _files(root)
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "families", "renamed_lm.py"), "w") as f:
        f.write(RENAMED_LM)
    config = dict(toy.SERVE_CONFIG, name="toy_renamed", family="renamed_lm",
                  vocab_rows=VOCAB_ROWS,
                  check=dict(toy.SERVE_CONFIG["check"],
                             vocab_rows=VOCAB_ROWS))
    with open(os.path.join(data, "configs", "toy_renamed.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(data, "traffic", "fam_closed.json"), "w") as f:
        json.dump(dict(toy.TRAFFIC["toy_closed"], lengths_seed=6), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "toy_renamed", "source": "none: a test preset",
        "reduced": [], "file": "benchmark/configs/toy_renamed.json",
        "why": "toy"})
    doc["workloads"].append({
        "name": "fam_closed", "config": "toy_renamed",
        "traffic": "fam_closed", "chips": 1, "why": "toy"})
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if "toy_closed" in m.get("workloads", ()):
                m["workloads"].append("fam_closed")
    with open(path, "w") as f:
        json.dump(doc, f)
    after = _files(root)
    assert {p for p in before if after[p] != before[p]} == {"BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/families/renamed_lm.py",
        "benchmark/configs/toy_renamed.json",
        "benchmark/traffic/fam_closed.json"}
    assert Manifest(root).problems() == []
    return root


def test_a_second_family_serves_a_cell_by_files_alone(second_family_root,
                                                      monkeypatch):
    drawn_from = []
    make = requests.make

    def spy(traffic, seed, seconds, vocab):
        drawn_from.append(vocab)
        return make(traffic, seed, seconds, vocab)

    monkeypatch.setattr(requests, "make", spy)
    out = run.run_cell(second_family_root, "fam_closed", seed=2 ** 31 + 3,
                       seconds=1.5, trace=False, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                   "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert drawn_from == [VOCAB_ROWS]       # the family's slice, not cfg's 97


def test_the_second_familys_bytes_reach_the_step_roofline(
        second_family_root, monkeypatch):
    """Traced: the per-layer line, and `decode_step_roofline` read from the
    family's own `step_bytes` (a CPU trace has no program line and the
    rehearsal no peak, so both are handed to the reader here)."""
    facts = {}
    run_serve = serve.run

    def spy(job):
        facts["ctx"] = run_serve(job)
        return facts["ctx"]

    monkeypatch.setattr(serve, "run", spy)
    out = run.run_cell(second_family_root, "fam_closed", seed=7, seconds=2.0,
                       trace=True, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    assert {"decode_step_ms_p50", "tpot_p50_ms", "engine_loop_ms_p50",
            "device_idle_share.serve"} <= set(out["metrics"])
    assert "decode_step_roofline" not in out["metrics"]
    ctx = facts["ctx"]
    tokens = ctx.telemetry["counters"]["decode.tokens"]
    assert tokens > 0 and ctx.step_bytes == 1000.0 * tokens
    ctx.trace["programs"] = {"jit_decode_step_b4(1)": {"runs": 20.0,
                                                      "seconds": 0.5}}
    ctx.peaks = {"hbm_bytes_per_s": 1e6}
    read = Manifest(second_family_root).reader("decode_step_roofline")
    assert read(ctx) == pytest.approx(100.0 * ctx.step_bytes / 1e6 / 0.025)
