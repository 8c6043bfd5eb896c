"""`toy.make_root` maps each real metric's cells onto the toy cells of the
same kind, and reads every kind but "train" as a serving one: written when
`train` and `serve` were the only kinds. A training cell of another kind
(`train_lm`, runners/train_lm.py) would put `train_tokens_per_s` and the
trainer's per-layer metrics onto the toy SERVING cells. toy.py is an
accepted benchmark file and not this PR's to edit, so the mapping is
repaired here, after the fact and for such kinds alone: a real cell whose
kind starts with "train" stands for the toy training cell. A `benchmark`
PR that makes `toy_cells` ask `kind.startswith("train")` deletes this file
(PERF.md, Open questions)."""

import json
import os

from . import toy

_make_root = toy.make_root


def _kind_of(real, cell):
    config = next(c for c in real["configs"] if c["name"] == next(
        w["config"] for w in real["workloads"] if w["name"] == cell))
    with open(os.path.join(toy.REPO, config["file"])) as f:
        return json.load(f)["kind"]


def make_root(tmp, *args, **kwargs):
    root = _make_root(tmp, *args, **kwargs)
    with open(os.path.join(toy.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    kinds = {w["name"]: _kind_of(real, w["name"]) for w in real["workloads"]}
    if all(k in ("train", "serve") for k in kinds.values()):
        return root
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for group in ("end_to_end", "per_layer"):
        cells = {m["name"]: m["workloads"] for m in real[group]
                 if "workloads" in m}
        for m in doc[group]:
            if m["name"] in cells:
                m["workloads"] = sorted(set().union(*(
                    {"toy_train"} if kinds[w].startswith("train")
                    else {"toy_closed", "toy_open"}
                    for w in cells[m["name"]])))
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


toy.make_root = make_root
