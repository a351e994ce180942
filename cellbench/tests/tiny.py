"""A tiny benchmark root for the CPU tests: the committed manifest and
data files, plus a tiny configuration and cells added *as files only*."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_CONFIG = {
    "source": "test", "n_layer": 2, "n_embd": 64, "n_head": 4,
    "n_positions": 64, "vocab_size": 500, "padded_vocab_size": 512,
    "layer_norm_epsilon": 1e-5, "initializer_range": 0.02,
    "training": {"amp_opt_level": "O2", "lr": 1e-4, "betas": [0.9, 0.999],
                 "adam_eps": 1e-8, "recompute": False,
                 "scan_unroll": "depth"},
    "serving": {"max_slots": 4, "max_len": 64, "page_size": 8},
}
TINY_TRAFFIC = {
    "tiny-train": {"kind": "train", "batch": 4, "seq": 32,
                   "mesh": {"data": 1, "tensor": 1}},
    "tiny-train-dp2tp2": {"kind": "train", "batch": 8, "seq": 32,
                          "mesh": {"data": 2, "tensor": 2}},
    "tiny-closed": {"kind": "closed", "callers": 6, "max_waiting": 2,
                    "pool": 16, "prompt_tokens": [4, 12],
                    "output_tokens": [6, 20], "greedy_share": 0.5},
    "tiny-open": {"kind": "open", "rate_rps": 8.0, "drain_s": 3.0,
                  "prompt_tokens": [8, 40], "output_tokens": [2, 6],
                  "greedy_share": 0.5},
}
# between what the tiny program reads on the CPU (gradient gap up to 0.005,
# update gap up to 0.017, logit gap 0) and what the fp8 control reads (from
# 0.0117, 0.028 and 0.006): the same rule as the chip's limits, tiny sizes
TRAIN_LIMITS = {"first_grad_norm_gap": 0.008,
                "update_norm_gap": 0.022, "compiles_in_window": 0,
                "failed_steps": 0}
SERVE_LIMITS = {"greedy_logit_gap": 0.003, "compiles_in_window": 0}


def make_root(tmp: str) -> str:
    """Copy the manifest and its data files to ``tmp`` and add the tiny
    cells: new files and new manifest entries, nothing edited."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    for d in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(REPO, "cellbench", d),
                        os.path.join(tmp, "cellbench", d))
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        man = json.load(f)

    def put(rel, obj):
        path = os.path.join(tmp, "cellbench", rel)
        assert not os.path.exists(path), f"{rel} would be edited, not added"
        with open(path, "w") as f:
            json.dump(obj, f)

    put("configs/tiny.json", TINY_CONFIG)
    man["configs"].append({"name": "tiny", "source": "test",
                           "file": "cellbench/configs/tiny.json",
                           "reduced": [], "why": "test"})
    by_kind = {"train": "train_tok_per_s_per_chip",
               "closed": "serve_tok_per_s", "open": "tpot_p90_ms"}
    for traffic, spec in TINY_TRAFFIC.items():
        cell = f"tiny.{traffic}"
        put(f"traffic/{traffic}.json", spec)
        put(f"limits/{cell}.json", {
            "sample": 12, "limits": TRAIN_LIMITS if spec["kind"] == "train"
            else SERVE_LIMITS})
        chips = 4 if "dp2" in traffic else 1
        man["workloads"].append({"name": cell, "config": "tiny",
                                 "traffic": traffic, "chips": chips,
                                 "why": "test"})
        for m in man["end_to_end"]:
            if m["name"] == by_kind[spec["kind"]]:
                m["workloads"].append(cell)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return tmp
