"""The dots.vlm1 cell: its files load, a tiny run is correct (chunked
prefill over the latent cache included), its control is not, every work
fact equals a hand count from ISSUE 34, and the new metrics' patterns find
the op names of the builder's own trace.

The committed configuration runs only on the chip (9.1 GB of weights). A
tiny one of the same architecture (latent attention under YaRN, 16 experts
in 4 groups of which 2 are kept, a share of 8 of them held, a dense layer
before the routed ones) comes in as files in a temporary root.
"""

import json
import math
import os
import re

import pytest

from cellbench import loop_serve, manifest, program, run
from cellbench.tests import tiny

CELL = "dotsvlm1.serve-decode-8k"
SEED = 4

TINY_CELL = "dots-tiny.tiny-closed-chunked"
TINY_TRAFFIC = {"kind": "closed", "callers": 6, "max_waiting": 2, "pool": 16,
                "prompt_tokens": [2, 28], "output_tokens": [6, 20],
                "greedy_share": 0.5, "engine": {"prefill_token_budget": 16}}
# on the seeds used below the tiny program reads 0.0007-0.0042 on the CPU
# (bf16 against float32) and its fp8 control 0.030-0.099: the chip's
# rule, tiny sizes
TINY_LIMITS = {"greedy_logit_gap": 0.012, "compiles_in_window": 0}
# the shortest prompt is 3 tokens, so set-up warms the bucket of 4: a
# chunk that is not a whole prompt is a quarter of the budget (4 tokens)
# or more, as the committed cell's is 512 beside prompts of 257 and more


def tiny_config() -> dict:
    with open(os.path.join(tiny.REPO, "cellbench/configs/"
                           "dots-vlm1-inst.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=8, router_width=16,
             expert_range=[4, 12], n_group=4, topk_group=2,
             num_experts_per_tok=4, vocab_size=128, num_hidden_layers=3,
             first_k_dense_replace=1, max_position_embeddings=256,
             serving={"max_slots": 4, "max_len": 64, "page_size": 8})
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    c["seeded_weights"] = {"expert_spread": 4.0}
    return c


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with tiny.package_restored():
        root = tiny.make_root(str(tmp_path_factory.mktemp("dots")))
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            man = json.load(f)
        for rel, obj in (
                ("configs/dots-tiny.json", tiny_config()),
                ("traffic/tiny-closed-chunked.json", TINY_TRAFFIC),
                (f"limits/{TINY_CELL}.json",
                 {"sample": 12, "limits": TINY_LIMITS})):
            with open(os.path.join(root, "cellbench", rel), "x") as f:
                json.dump(obj, f)
        man["configs"].append({
            "name": "dots-tiny", "source": "test", "reduced": [],
            "file": "cellbench/configs/dots-tiny.json", "why": "test"})
        man["workloads"].append({
            "name": TINY_CELL, "config": "dots-tiny", "chips": 1,
            "traffic": "tiny-closed-chunked", "why": "test"})
        for m in man["end_to_end"]:
            if m["name"] == "serve_tok_per_s":
                m["workloads"].append(TINY_CELL)
        with open(path, "w") as f:
            json.dump(man, f)
        yield root


def test_the_committed_cell_loads_with_its_files():
    cell = manifest.cell(CELL, tiny.REPO)
    assert cell.arch.__name__ == "cellbench.arch.dots_vlm"
    assert cell.reference.__name__ == "cellbench.reference.dots_vlm"
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "dots-vlm1-inst", "serve-decode-8k")
    sz = cell.arch.sizes(cell.config)
    assert (sz["L"], sz["D"], sz["h"], sz["heads"]) == (5, 1, 7168, 128)
    assert (sz["q_rank"], sz["rank"], sz["nope"], sz["rope"], sz["dv"]) == \
        (1536, 512, 128, 64, 128)
    assert (sz["E"], sz["held"], sz["lo"], sz["hi"], sz["k"]) == \
        (256, 16, 0, 16, 8)
    assert (sz["n_group"], sz["topk_group"], sz["f"], sz["ffn"]) == \
        (8, 4, 2048, 18432)
    assert cell.arch.vocab_ids(cell.config) == sz["V"] == 16160
    assert sz["yarn"] == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    ec = program.engine_config(cell.config, cell.traffic)
    assert (ec.max_slots, ec.max_len, ec.page_size) == (64, 8192, 64)
    assert ec.prefix_cache and ec.prefill_token_budget == 2048
    assert ec.kv_dtype == "bf16" and ec.speculation == 0
    t = cell.traffic
    assert t["prompt_tokens"][1] + t["output_tokens"][1] == 8064 < ec.max_len
    # the mix is ISSUE 34's, number for number
    assert {k: t[k] for k in ("kind", "callers", "max_waiting", "pool",
                              "prompt_tokens", "output_tokens",
                              "greedy_share", "trace_seconds", "engine")} == {
        "kind": "closed", "callers": 96, "max_waiting": 32, "pool": 1024,
        "prompt_tokens": [256, 2048], "output_tokens": [1024, 6016],
        "greedy_share": 0.5, "trace_seconds": 4.0,
        "engine": {"prefill_token_budget": 2048}}
    # and every seeded matrix is drawn at initializer_range
    assert set(cell.config["seeded_weights"]) == {"expert_spread", "note"}
    names = {m.name for m in cell.per_layer}
    assert {"step.mla_dev_ms.decode", "kernel.mla_decode_roofline_pct.decode",
            "step.moe_dev_ms.decode", "step.moe_router_dev_ms.decode",
            "kernel.moe_experts_roofline_pct.decode",
            "step.mfu_pct.decode"} <= names
    assert "kernel.paged_decode_roofline_pct.decode" not in names
    assert all(n.endswith(".decode") for n in names)
    assert [m.name for m in cell.end_to_end] == ["serve_tok_per_s",
                                                 "setup_s"]
    assert set(cell.limits["limits"]) == {"greedy_logit_gap",
                                          "compiles_in_window"}


def test_published_keys_are_kept_and_the_cuts_are_listed():
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(guide):
        pytest.skip("no catalog here")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots.vlm1.inst")
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "dots-vlm1-inst")
    cell = manifest.cell(CELL, tiny.REPO)
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(cell.config["reduced"]) \
        == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
            "vocab_size", "num_nextn_predict_layers"}
    assert set(cell.config["changed"]) == differs | {"modality"}
    assert cell.config["published"] == {k: row["config"][k] for k in differs}
    # the floors of a cut: four layers after the dense one, 8 experts or
    # more held, an eighth of the vocabulary or more
    assert cell.config["num_hidden_layers"] - 1 >= 4
    assert cell.config["n_routed_experts"] >= 8
    assert cell.config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cell.config["router_width"] == row["config"]["n_routed_experts"]


def test_work_facts_against_a_hand_count():
    """ISSUE 34's arithmetic: 187.1 M parameters of attention a layer,
    396.4 M of dense feed-forward, 44.04 M an expert, 4,566 M in all; 278.5
    kFLOP and 1,152 B a cached position a layer."""
    cell = manifest.cell(CELL, tiny.REPO)
    A = cell.arch
    sz = A.sizes(cell.config)
    attn = (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768
            + 16384 * 7168)
    assert A.attention_params(sz) == attn and round(attn / 1e6, 1) == 187.1
    one = 3 * 7168 * 2048
    dense = 3 * 7168 * 18432
    assert (round(one / 1e6, 2), round(dense / 1e6, 1)) == (44.04, 396.4)
    held = 5 * attn + dense + 4 * (17 * one + 7168 * 256) + 2 * 16160 * 7168
    assert round(held / 1e6) == 4566                  # what the chip holds
    assert A.expected_assignments(sz) == 0.5
    per_token = (5 * attn + dense + 4 * (1.5 * one + 7168 * 256)
                 + 16160 * 7168)
    assert A.matmul_params_per_token(sz) == per_token
    assert A.serve_flops(sz, 1000, 2e6) == pytest.approx(
        2.0 * per_token * 1000 + 2.0 * 128 * (192 + 128) * 2e6 * 5)
    work = A.serve_kernel_work(sz, [100, 3000], 2e6, 1000)
    flops, nbytes = work["mla_decode_work"]
    assert flops == 2e6 * 128 * 2 * (576 + 512) * 5
    assert flops / 2e6 / 5 == 278528
    assert nbytes == (2e6 + 1000) * 576 * 2 * 5
    flops, nbytes = work["flash_prefill_work"]
    pairs = 100 * 101 / 2 + 3000 * 3001 / 2
    assert flops == pytest.approx(2.0 * 128 * 320 * pairs * 5)
    assert nbytes == pytest.approx(3100 * 128 * 2 * 320 * 2 * 5)
    # routed products: the expected half assignment a row; every held
    # expert that some row of a call of 62.5 rows chooses, once a call
    flops, nbytes = work["moe_experts_decode_work"]
    assert flops == pytest.approx(2.0 * 1000 * 0.5 * one * 4)
    calls = math.ceil(1000 / 64)
    share = 1 - (31 / 32) ** (1000 / calls)
    assert 0.85 < share < 0.87
    assert nbytes == pytest.approx(calls * 16 * share * one * 2 * 4)
    assert round(one * 2 / 1e6) == 88                  # MB an expert
    shapes = A.shapes(sz, cell.traffic)
    assert (shapes["latent"], shapes["rope_lanes"], shapes["experts"]) == \
        (512, 128, 16)


#: op names of the decode program in the builder's own trace of the cell
#: (my chip run, PR 34, seed 2147484008): the latent kernel's call and the
#: two row appends (the ``kR`` append as the ``c`` one reads, at 128 lanes)
TRACED_OPS = [
    '%mla_decode_attention.10 = bf16[64,128,512]{2,1,0:T(8,128)(2,1)S(1)} '
    'custom-call(s32[64,128]{1,0:T(8,128)S(1)} %broadcast_minimum_fusion, '
    's32[64]{0:T(128)S(1)} %copy-done.15, s32[64]{0:T(128)S(1)} '
    '%get-tuple-element.518, s32[65]{0:T(128)S(1)} %reduce.84, '
    'bf16[64,128,640]{2,1,0:T(8,128)(2,1)S(1)} %fusion.105, '
    'bf16[8192,64,512]{2,1,0:T(8,128)(2,1)} %fusion.58, '
    'bf16[8192,64,128]{2,1,0:T(8,128)(2,1)} %fusion.59), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
    '%fusion.58 = bf16[8192,64,512]{2,1,0:T(8,128)(2,1)} fusion('
    'bf16[8192,64,512]{2,1,0:T(8,128)(2,1)} %caches_0__0_.1, '
    's32[64]{0:T(128)S(1)} %fusion.386, bf16[64,512]{1,0:T(8,128)(2,1)S(1)} '
    '%fusion.296), kind=kCustom, calls=%fused_computation.26.clone',
    '%fusion.59 = bf16[8192,64,128]{2,1,0:T(8,128)(2,1)} fusion('
    'bf16[8192,64,128]{2,1,0:T(8,128)(2,1)} %caches_0__1_.1, '
    's32[64]{0:T(128)S(1)} %fusion.386, bf16[64,128]{1,0:T(8,128)(2,1)S(1)} '
    '%fusion.297), kind=kCustom, calls=%fused_computation.27.clone',
]


def test_metric_patterns_find_the_traced_ops():
    cell = manifest.cell(CELL, tiny.REPO)
    metric = next(m for m in cell.per_layer
                  if m.name == "kernel.mla_decode_roofline_pct.decode")
    assert metric.reader["reader"] == "roofline_pct"
    args = metric.reader["args"]
    assert args["work_fact"] == "mla_decode_work"
    shapes = {"n_pages": 8192, "page_size": 64, "slots": 64,
              **cell.arch.shapes(cell.arch.sizes(cell.config), cell.traffic)}
    rxs = [re.compile(p.format(**shapes)) for p in args["patterns"]]
    for op in TRACED_OPS:
        assert any(rx.search(op) for rx in rxs), op
    # and nothing of another kind: a K/V pool pair of one width, the
    # routed products, a fusion that only reads a pool
    for other in (
            "%paged_decode_attention = bf16[96,32,512] custom-call(bf16[6144"
            ",64,512] %a, bf16[6144,64,512] %b), custom_call_target=\"tpu_"
            "custom_call\"",
            "%moe_experts_up = bf16[1024,2048] custom-call(bf16[16,7168,4096]"
            " %w), custom_call_target=\"tpu_custom_call\"",
            "%fusion.3 = bf16[64,7168] fusion(bf16[8192,64,512] %p)"):
        assert not any(rx.search(other) for rx in rxs), other
    step = next(m for m in cell.per_layer
                if m.name == "step.mla_dev_ms.decode")
    assert step.reader == {"reader": "scope_dev_ms_per", "args": {
        "scope": "mla", "module": "paged_decode_body", "per": "cb.tick"}}


def test_the_decode_program_carries_the_scopes_the_metrics_read(root):
    """The lowered decode program of the tiny engine names ``mla``,
    ``mla_absorb`` and ``mla_decode_attention`` (and ``moe``) as path
    elements of its ops' names."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import InferenceEngine
    from cellbench.readers_program import in_scope

    cell = manifest.cell(TINY_CELL, root)
    A = cell.arch
    sz = A.sizes(cell.config)
    tree = jax.tree.map(lambda x: x.astype(jnp.bfloat16), A.program_tree(
        A.canonical(jax.random.PRNGKey(0), sz), sz))
    eng = InferenceEngine(A.model_for(cell.config), tree,
                          program.engine_config(cell.config, cell.traffic))
    text = eng.decode_program_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in ("mla", "mla_absorb", "mla_decode_attention", "moe",
                  "moe_router"):
        assert any(in_scope(n, {scope}) for n in names), scope
    assert any(in_scope(n, {"mla"}) and in_scope(n, {"mla_decode_attention"})
               for n in names)


def test_weights_are_the_same_numbers_in_both_layouts(root):
    import jax
    import jax.numpy as jnp

    cell = manifest.cell(TINY_CELL, root)
    A = cell.arch
    sz = A.sizes(cell.config)
    assert (sz["held"], sz["E"], sz["lo"], sz["hi"]) == (8, 16, 4, 12)
    key = jax.random.PRNGKey(5)
    w = A.canonical(key, sz, round_to=jnp.bfloat16)
    tree = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                        A.program_tree(A.canonical(key, sz), sz))
    model = A.model_for(cell.config)
    want = jax.eval_shape(model.init, key)
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, tree, want)))
    # the round trip: canonical -> the program's tree -> canonical
    back = A.canonical_names(A.program_tree(w, sz))
    assert set(back) == set(w)
    for name in w:
        for a, b in zip(jax.tree.leaves(w[name]), jax.tree.leaves(back[name])):
            assert a.shape == b.shape and bool(jnp.array_equal(a, b)), name
    assert w["e_in"][0].shape == (8, 64, 64)
    assert w["router"][0].shape == (64, 16)
    assert w["e_in"][0].dtype == w["w_ukv"].dtype == jnp.bfloat16
    with pytest.raises(NotImplementedError, match="training"):
        A.train_flops(sz, 1, 1)


def test_sizes_refuse_what_the_program_has_not(root):
    cell = manifest.cell(TINY_CELL, root)
    for key, value in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                       ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            cell.arch.sizes(dict(cell.config, **{key: value}))
    with pytest.raises(ValueError, match="HELD"):
        cell.arch.sizes(dict(cell.config, expert_range=[0, 16]))


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
    assert facts["mla_decode_work"][1] > 0
