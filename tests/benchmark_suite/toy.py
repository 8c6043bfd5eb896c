"""A toy benchmark root in a temporary directory: the real readers,
families, generators and runners, with tiny configurations, cells and
traffic added as files and BENCHMARK.json entries only."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRAIN_CONFIG = {
    "name": "toy_bert", "kind": "train", "source": "none: a test preset",
    "vocab_size": 211, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 2, "intermediate_size": 64,
    "max_position_embeddings": 16, "type_vocab_size": 2,
    "runner": {"dtype": "float32", "use_flash_attention": False,
               "optimizer": "adamw", "lr": 1e-3},
    "check": {"num_hidden_layers": 1, "batch": 2,
              "grads": ["layer_0_attn_q_w", "word_embedding"]}}
SERVE_CONFIG = {
    "name": "toy_lm", "kind": "serve", "family": "decoder_lm",
    "source": "none: a test preset",
    "vocab_size": 97, "d_model": 32, "attention_heads": 4, "num_layers": 2,
    "ffn_dim": 64, "max_position_embeddings": 64,
    "max_context": 64, "kv_pages": 65,
    "engine": {"max_slots": 4, "page_size": 4, "max_new_tokens": 16, "max_queue_depth": 64,
               "prefill_buckets": [16, 32], "weight_quant": "none",
               "prefix_cache": False},
    "check": {"prompt_tokens": [5, 9], "new_tokens": 4, "pad_to": 16}}
TRAFFIC = {
    "toy_ring": {"generator": "train_ring", "batch_per_replica": 2,
                 "seq_len": 16, "max_predictions_per_seq": 3, "ring": 3,
                 "warmup_steps": 4, "loss_every": 2},
    "toy_closed": {
        "generator": "requests", "arrival": {"kind": "closed", "clients": 3},
        "ramp_s": 0.2, "distinct_lengths": 8, "lengths_seed": 5,
        "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 3, "max": 30},
        "new_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 12},
        "max_context": 40, "temperature": 0.8},
    "toy_open": {
        "generator": "requests", "arrival": {"kind": "poisson", "rate": 6.0},
        "ramp_s": 0.2, "lengths_seed": 5,
        "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 3, "max": 30},
        "new_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 12},
        "max_context": 40, "temperature": 0.8},
}
CELLS = {"toy_train": ("toy_bert", "toy_ring"),
         "toy_closed": ("toy_lm", "toy_closed"),
         "toy_open": ("toy_lm", "toy_open")}


def make_root(tmp, extra_metric=None, chips=None, mesh=None):
    """Writes the toy root under `tmp` and returns its path. The metric
    lists are the repo's own, pointed at the toy cells."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    data = os.path.join(tmp, "benchmark")
    os.makedirs(os.path.join(data, "configs"))
    os.makedirs(os.path.join(data, "traffic"))
    for files in ("readers", "families"):
        shutil.copytree(os.path.join(REPO, "benchmark", files),
                        os.path.join(data, files),
                        ignore=shutil.ignore_patterns("__pycache__"))
    kinds = {}
    for cfg in (TRAIN_CONFIG, SERVE_CONFIG):
        if mesh and cfg["kind"] == "train":
            cfg = dict(cfg, runner=dict(cfg["runner"], mesh_by_chips=mesh))
        with open(os.path.join(data, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
        kinds[cfg["name"]] = cfg["kind"]
    for name, doc in TRAFFIC.items():
        with open(os.path.join(data, "traffic", name + ".json"), "w") as f:
            json.dump(doc, f)
    real_kind = {}
    for w in real["workloads"]:
        with open(os.path.join(REPO, next(
                c["file"] for c in real["configs"]
                if c["name"] == w["config"]))) as f:
            real_kind[w["name"]] = json.load(f)["kind"]

    def toy_cells(metric):
        """A real metric's cells, mapped onto the toy cells of the same
        kind: both toy serving cells report what a real serving cell does."""
        if "workloads" not in metric:
            return None
        out = set()
        for w in metric["workloads"]:
            out |= ({"toy_train"} if real_kind[w] == "train"
                    else {"toy_closed", "toy_open"})
        return sorted(out)

    doc = dict(real)
    doc["configs"] = [
        {"name": c["name"], "source": c["source"], "reduced": [],
         "file": f"benchmark/configs/{c['name']}.json", "why": "toy"}
        for c in (TRAIN_CONFIG, SERVE_CONFIG)]
    doc["workloads"] = [
        {"name": n, "config": c, "traffic": t, "why": "toy",
         "chips": (chips or {}).get(n, 1)}
        for n, (c, t) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        out = []
        for m in real[group]:
            m = dict(m)
            cells = toy_cells(m)
            if cells is not None:
                m["workloads"] = cells
            out.append(m)
        doc[group] = out
    if extra_metric:
        doc["per_layer"].append(extra_metric)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return tmp


def keep_cells(root, names):
    """Cuts the toy root's BENCHMARK.json to the cells in `names` (old name
    -> new name) and the configurations they use. A test file gives its
    cells names of its own, because a run's work directory goes by the
    cell's name and another file's tests may run beside it."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"] = [dict(w, name=names[w["name"]])
                        for w in doc["workloads"] if w["name"] in names]
    used = {w["config"] for w in doc["workloads"]}
    doc["configs"] = [c for c in doc["configs"] if c["name"] in used]
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if "workloads" in m:
                m["workloads"] = [names[w] for w in m["workloads"]
                                  if w in names]
        doc[group] = [m for m in doc[group] if m.get("workloads", True)]
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def entry(man, group, name):
    """The one entry of that name in a list of the manifest. A test finds
    what it checks by name: a list's end and length are the next PR's."""
    found, = [e for e in man.doc[group] if e["name"] == name]
    return found


def reported(man, cell):
    """The names of every metric the cell reports, end to end and by layer.
    A family's test holds its own as a subset of them."""
    return {m["name"] for group in ("end_to_end", "per_layer")
            for m in man.metrics_of(cell, group)}
