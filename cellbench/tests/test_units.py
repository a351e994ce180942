"""stats, traffic, work and the manifest loader."""

import json
import math
import os

import pytest

from cellbench import manifest, readers, stats, traffic as T, work
from cellbench import weights as W
from cellbench.tests import tiny

REPO = tiny.REPO


# -- stats --------------------------------------------------------------------

def test_percentile_is_nearest_rank_over_all_samples():
    assert stats.percentile([3, 1, 2, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert stats.percentile([5.0], 90) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_misses_count_as_infinite_samples():
    ok = [10.0] * 95
    assert stats.percentile(ok + [math.inf] * 5, 90) == 10.0
    assert stats.percentile(ok[:85] + [math.inf] * 15, 90) == math.inf


def test_quartile_spread_is_iqr_over_median():
    assert stats.quartile_spread([100, 100, 100, 100, 100, 100]) == 0
    assert 0.02 < stats.quartile_spread([98, 99, 100, 100, 101, 102]) < 0.04


# -- traffic ------------------------------------------------------------------

OPEN = {"kind": "open", "rate_rps": 15.0, "drain_s": 3.0,
        "prompt_tokens": [256, 960], "output_tokens": [16, 64],
        "greedy_share": 0.5}


def _sig(plan):
    return [(p.due_s, tuple(p.prompt), p.max_new_tokens, p.greedy,
             p.sample_seed) for p in plan]


def test_same_seed_same_schedule():
    a = T.open_schedule(OPEN, 2 ** 31 + 5, 10.0, 50257)
    b = T.open_schedule(OPEN, 2 ** 31 + 5, 10.0, 50257)
    assert _sig(a) == _sig(b)


def test_two_seeds_same_count_and_multiset_of_lengths():
    a = T.open_schedule(OPEN, 1, 10.0, 50257)
    b = T.open_schedule(OPEN, 2, 10.0, 50257)
    assert len(a) == len(b) == 150
    for field in (lambda p: len(p.prompt), lambda p: p.max_new_tokens,
                  lambda p: p.greedy):
        assert sorted(map(field, a)) == sorted(map(field, b))
    assert _sig(a) != _sig(b)
    assert all(0.0 < p.due_s < 10.0 for p in a + b)
    assert [p.due_s for p in a] == sorted(p.due_s for p in a)
    assert sum(p.greedy for p in a) == 75
    assert all(len(p.prompt) + p.max_new_tokens <= 1024 for p in a)


def test_closed_pool_and_midlife():
    spec = {"pool": 64, "prompt_tokens": [32, 128],
            "output_tokens": [256, 768], "greedy_share": 0.5}
    a, b = T.closed_pool(spec, 3, 50257), T.closed_pool(spec, 4, 50257)
    assert sorted(len(p.prompt) for p in a) == sorted(
        len(p.prompt) for p in b)
    rng = T.rng_for(3, "midlife")
    m = T.midlife(a[0], 0.5, 50257, rng)
    assert len(m.prompt) + m.max_new_tokens == \
        len(a[0].prompt) + a[0].max_new_tokens
    assert m.max_new_tokens >= 1


def test_train_stream_is_seeded_and_rows_differ():
    a = next(T.train_stream(9, 8, 1024, 50257))
    b = next(T.train_stream(9, 8, 1024, 50257))
    assert (a == b).all() and a.shape == (8, 1025)
    assert len({row.tobytes() for row in a}) == 8
    assert a.max() < 50257


def test_prompt_buckets_are_the_engines():
    from apex_tpu.serving.scheduler import bucket_for

    lengths = [1, 32, 33, 128, 256, 511, 513, 960]
    want = sorted({bucket_for(n, 1024) for n in lengths})
    assert T.prompt_buckets(lengths, 1024) == want


# -- work ---------------------------------------------------------------------

@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-large"])
def test_train_flops_agree_with_the_programs_estimator(name):
    from apex_tpu.utils.flops import transformer_train_flops

    with open(os.path.join(REPO, "cellbench/configs", name + ".json")) as f:
        sz = W.sizes(json.load(f))
    n_all = (work.matmul_params(sz) + sz["pos"] * sz["h"]
             + sz["L"] * (13 * sz["h"]) + 2 * sz["h"])   # + tables, biases
    theirs = transformer_train_flops(n_all, 8 * 1024, sz["L"], sz["h"],
                                     1024, causal=True)
    ours = work.train_flops(sz, 8, 1024)
    assert abs(ours - theirs) / theirs < 0.01


def test_peaks_refuse_an_unknown_chip():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


def test_roofline_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, peak) == pytest.approx(1.0)


# -- manifest -----------------------------------------------------------------

def test_committed_manifest_is_consistent():
    loaded = manifest.load(REPO)
    man = loaded["manifest"]
    for w in man["workloads"]:
        c = manifest.cell(w["name"], REPO)
        assert any(m.name == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer
        for m in c.per_layer:
            assert m.reader["reader"] in readers.REDUCTIONS
            if m.unit == "%":
                assert "_pct" in m.name
    assert len(json.dumps(man)) < 64 * 1024


@pytest.mark.parametrize("field,value", [
    ("name", "tok/s"), ("name", "a b"), ("name", "x" * 65),
    ("unit", "tokens per second"), ("unit", "µs"), ("better", "faster"),
    ("source", "cpu_clock")])
def test_loader_rejects_forbidden_names_and_units(tmp_path, field, value):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["per_layer"][0][field] = value
    with open(path, "w") as f:
        json.dump(man, f)
    with pytest.raises(manifest.ManifestError):
        manifest.load(root)


def test_loader_rejects_a_metric_that_moves_what_its_cell_lacks(tmp_path):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    train = next(m for m in man["per_layer"]
                 if m["name"] == "step.mfu_pct.train")
    train["moves"] = "serve_tok_per_s"      # the train cell reports none
    with open(path, "w") as f:
        json.dump(man, f)
    with pytest.raises(manifest.ManifestError):
        manifest.load(root)


def test_a_cell_added_as_files_only_is_found(tmp_path):
    root = tiny.make_root(str(tmp_path))     # asserts nothing is overwritten
    c = manifest.cell("tiny.tiny-open", root)
    assert c.config["n_layer"] == 2 and c.traffic["kind"] == "open"
    assert [m.name for m in c.end_to_end] == ["tpot_p90_ms", "setup_s"]
    # and the committed cells still load from the same root
    assert manifest.cell("gpt2m.train-1k", root).traffic["batch"] == 8


def test_committed_manifest_keeps_the_contracts_limits():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        raw = f.read()
    man = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["cellbench"] and len(man["command"]) <= 32
    assert 1 <= man["run_seconds"] <= 51
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("cellbench/") and len(c["source"]) <= 200
        assert os.path.isfile(os.path.join(REPO, c["file"]))
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 4)
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200, w["name"]
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in man["end_to_end"])
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    layers = set()
    for m in man["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"], m["name"]
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.add(m["layer"])
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer one; a roofline or mfu share moves what its cell reports
    for w in man["workloads"]:
        c = manifest.cell(w["name"], REPO)
        assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
        assert any("mfu" in m.name for m in c.per_layer)
