"""Multi-LoRA tests: adapter bank lifecycle, fused fine-tuning, and
multi-tenant serving correctness.

Correctness anchor: an engine serving adapter traffic through the
stacked device bank must be TOKEN-EXACT against a reference engine
serving ``merge_adapter(params, factors)`` (``W' = W + (A @ B).T``) —
the per-slot factored delta is an execution strategy, never an
approximation. The structural satellites ride along: typed submit
validation with its own shed counter, a per-adapter admission ledger
the monitor reconciles key-for-key, adapter-salted prefix chains (no
cross-tenant page aliasing), and conservation under randomized
multi-tenant churn with a mid-run unload.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.lora import (
    LORA_TARGETS,
    AdapterStore,
    UnknownAdapterError,
    init_adapter,
    lora_finetune,
    merge_adapter,
    random_adapter,
    target_dims,
)
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import (
    JsonlSink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    Request,
    SamplingParams,
    ShardedEngine,
)


@pytest.fixture(scope="module")
def small():
    model = GPTModel(TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=n).tolist() for n in lens]


def _store(config, ids=("a",), rank=4, max_adapters=4, scale=0.05):
    """An AdapterStore with nonzero (random_adapter) factors per id —
    the adapters are also returned so tests can merge them."""
    store = AdapterStore(config, rank, max_adapters=max_adapters)
    factors = {}
    for i, aid in enumerate(ids):
        factors[aid] = random_adapter(config, rank,
                                      jax.random.PRNGKey(i + 1),
                                      scale=scale)
        store.load(aid, factors[aid])
    return store, factors


# ---------------------------------------------------------------------------
# adapter format + store lifecycle (host-side, no engine)


class TestAdapterStore:
    def test_bank_shape_and_reserved_null_row(self, small):
        model, _ = small
        store = AdapterStore(model.config, rank=4, max_adapters=3)
        assert store.null_index == 3
        dims = target_dims(model.config)
        assert set(store.bank) == set(dims) == set(LORA_TARGETS)
        L = model.config.num_layers
        for t, (din, dout) in dims.items():
            assert store.bank[t]["A"].shape == (L, 4, din, 4)
            assert store.bank[t]["B"].shape == (L, 4, 4, dout)
        # null row stays all-zeros through load/unload traffic
        ix = store.load("a", random_adapter(model.config, 4,
                                            jax.random.PRNGKey(1)))
        assert ix != store.null_index
        for t in store.bank:
            assert not np.asarray(
                store.bank[t]["A"][:, store.null_index]).any()
            assert not np.asarray(
                store.bank[t]["B"][:, store.null_index]).any()

    def test_load_unload_index_lifecycle(self, small):
        model, _ = small
        store, _ = _store(model.config, ids=("a", "b"), max_adapters=3)
        assert store.ids() == ["a", "b"]
        assert "a" in store and "ghost" not in store
        assert len(store) == 2
        ia, ib = store.index_of("a"), store.index_of("b")
        assert ia != ib
        assert store.index_of(None) == store.null_index
        # overwrite keeps the index; the row content changes in place
        before = np.asarray(store.bank[LORA_TARGETS[0]]["A"][:, ia]).copy()
        assert store.load("a", random_adapter(
            model.config, 4, jax.random.PRNGKey(9))) == ia
        after = np.asarray(store.bank[LORA_TARGETS[0]]["A"][:, ia])
        assert not np.array_equal(before, after)
        # unload zeroes the row, frees the index, and forgets the id
        store.unload("a")
        assert "a" not in store and store.ids() == ["b"]
        for t in store.bank:
            assert not np.asarray(store.bank[t]["A"][:, ia]).any()
            assert not np.asarray(store.bank[t]["B"][:, ia]).any()
        with pytest.raises(UnknownAdapterError):
            store.index_of("a")
        with pytest.raises(UnknownAdapterError):
            store.unload("a")
        # freed index is reused (lowest-first, like the slot pool)
        assert store.load("c", random_adapter(
            model.config, 4, jax.random.PRNGKey(3))) == min(
                ia, store.null_index)

    def test_full_bank_and_bad_factors_rejected(self, small):
        model, _ = small
        store, _ = _store(model.config, ids=("a", "b"), max_adapters=2)
        with pytest.raises(ValueError, match="full"):
            store.load("c", random_adapter(model.config, 4,
                                           jax.random.PRNGKey(5)))
        # rank mismatch / missing target fail the shape check
        with pytest.raises(ValueError, match="shape"):
            store.load("a", random_adapter(model.config, 2,
                                           jax.random.PRNGKey(5)))
        wrong = random_adapter(model.config, 4, jax.random.PRNGKey(5))
        wrong.pop("dense_h_to_4h")
        with pytest.raises(ValueError, match="targets"):
            store.load("a", wrong)
        with pytest.raises(ValueError, match="adapter_id"):
            store.load("", random_adapter(model.config, 4,
                                          jax.random.PRNGKey(5)))

    def test_constructor_validation(self, small):
        model, _ = small
        with pytest.raises(ValueError, match="rank"):
            AdapterStore(model.config, rank=0)
        with pytest.raises(ValueError, match="max_adapters"):
            AdapterStore(model.config, rank=4, max_adapters=0)

    def test_unknown_adapter_error_is_key_error(self):
        # submit paths catch it as the typed error; callers that treat
        # the store as a mapping still catch their KeyError
        assert issubclass(UnknownAdapterError, KeyError)


# ---------------------------------------------------------------------------
# merge math: the ground truth the parity tests compare against


class TestMergeMath:
    def test_merge_matches_manual_fold(self, small):
        model, params = small
        f = random_adapter(model.config, 4, jax.random.PRNGKey(2))
        merged = merge_adapter(params, f)
        layers = params["transformer"]["layers"]
        mlayers = merged["transformer"]["layers"]
        paths = {"query_key_value": ("self_attention", "query_key_value"),
                 "dense_h_to_4h": ("mlp", "dense_h_to_4h")}
        for t, (sub, name) in paths.items():
            w = np.asarray(layers[sub][name]["weight"], np.float32)
            got = np.asarray(mlayers[sub][name]["weight"], np.float32)
            for layer in range(model.config.num_layers):
                delta = (np.asarray(f[t]["A"][layer]) @
                         np.asarray(f[t]["B"][layer])).T
                np.testing.assert_allclose(got[layer], w[layer] + delta,
                                           rtol=1e-5, atol=1e-5)
        # untouched leaves are the SAME arrays; the input pytree is not
        # mutated (merge returns a new tree)
        assert merged["embedding"] is params["embedding"]
        assert merged["transformer"]["layers"]["self_attention"] \
            ["dense"] is layers["self_attention"]["dense"]

    def test_zero_init_adapter_merges_to_identity(self, small):
        model, params = small
        f = init_adapter(model.config, 4, jax.random.PRNGKey(2))
        merged = merge_adapter(params, f)
        same = jax.tree.map(np.array_equal, params, merged)
        assert all(jax.tree.leaves(same))


# ---------------------------------------------------------------------------
# fused fine-tuning: batched jobs, frozen base, flat-bucket updates


class TestFinetune:
    def test_batched_jobs_loss_decreases_base_frozen(self, small):
        model, params = small
        rng = np.random.RandomState(11)
        tokens = jnp.asarray(rng.randint(0, 64, size=(2, 2, 8)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 64, size=(2, 2, 8)), jnp.int32)
        base_snapshot = jax.tree.map(lambda x: np.asarray(x).copy(), params)
        factors, losses = lora_finetune(model, params, tokens, labels,
                                        rank=2, steps=8, lr=1e-2,
                                        rng=jax.random.PRNGKey(0))
        assert losses.shape == (8, 2)
        # B init is zero, so step-0 loss IS the base-model loss; every
        # job must then improve on it — only the factors trained
        for j in range(2):
            assert float(losses[-1, j]) < float(losses[0, j])
        same = jax.tree.map(lambda a, b: np.array_equal(np.asarray(a), b),
                            params, base_snapshot)
        assert all(jax.tree.leaves(same)), "base params were touched"
        # the stacked output slices into per-job adapters that the store
        # accepts — the finetune -> serve handoff
        store = AdapterStore(model.config, 2, max_adapters=2)
        for j in range(2):
            store.load(f"job{j}", jax.tree.map(lambda x: x[j], factors))
        assert store.ids() == ["job0", "job1"]

    @pytest.mark.slow  # training-convergence claim: slow tier (ROADMAP)

    def test_trained_adapter_beats_base_when_merged(self, small):
        model, params = small
        rng = np.random.RandomState(13)
        tokens = jnp.asarray(rng.randint(0, 64, size=(1, 2, 8)), jnp.int32)
        labels = tokens  # learn to echo: an easy, monotone objective
        factors, losses = lora_finetune(model, params, tokens, labels,
                                        rank=2, steps=10, lr=2e-2,
                                        rng=jax.random.PRNGKey(1))
        merged = merge_adapter(params, jax.tree.map(lambda x: x[0],
                                                    factors))
        base_loss = float(model.apply(params, tokens[0], labels[0]))
        tuned_loss = float(model.apply(merged, tokens[0], labels[0]))
        assert tuned_loss < base_loss

    def test_label_shape_mismatch_rejected(self, small):
        model, params = small
        tokens = jnp.zeros((1, 2, 8), jnp.int32)
        with pytest.raises(ValueError, match="labels"):
            lora_finetune(model, params, tokens,
                          jnp.zeros((1, 2, 7), jnp.int32))


# ---------------------------------------------------------------------------
# submit validation + the per-adapter ledger (no compile: every request
# here is shed or cancelled before prefill)


class TestSubmitValidation:
    def test_unknown_adapter_typed_error_counter_and_record(self, small):
        model, params = small
        store, _ = _store(model.config, ids=("a",))
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              adapters=store)
        req = Request(prompt=[1, 2], max_new_tokens=2,
                      sampling=SamplingParams(adapter_id="ghost"))
        with pytest.raises(UnknownAdapterError, match="ghost"):
            eng.submit(req)
        assert eng.metrics.counters()["requests_shed_adapter"] == 1
        # terminal rejected record, conservation-safe: the result exists
        # even though submit raised
        res = eng.completed[req.request_id]
        assert res.finish_reason == "rejected"
        assert res.adapter_id == "ghost"
        assert eng.queued_count == 0 and eng.active_count == 0
        eng.close()

    def test_engine_without_store_rejects_adapter_requests(self, small):
        model, params = small
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16))
        with pytest.raises(UnknownAdapterError, match="AdapterStore"):
            eng.submit(Request(prompt=[1], max_new_tokens=1,
                               sampling=SamplingParams(adapter_id="a")))
        assert eng.metrics.counters()["requests_shed_adapter"] == 1
        eng.close()

    def test_ledger_reconciles_key_for_key(self, small, tmp_path):
        """The satellite acceptance: per-adapter counters, the
        adapter_request event stream, and the adapter_id-stamped result
        rows all reconcile key-for-key through the monitor report."""
        model, params = small
        store, _ = _store(model.config, ids=("a", "b", "c"))
        log = tmp_path / "lora.jsonl"
        reg = MetricsRegistry([JsonlSink(str(log))])
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              metrics=reg, adapters=store)
        mix = ["a", "a", "a", "b", "b", None, "c"]
        reqs = [Request(prompt=[1, 2], max_new_tokens=2,
                        sampling=SamplingParams(adapter_id=aid))
                for aid in mix]
        for r in reqs:
            eng.submit(r)
        with pytest.raises(UnknownAdapterError):
            eng.submit(Request(prompt=[1], max_new_tokens=1,
                               sampling=SamplingParams(adapter_id="ghost")))
        for r in reqs:          # cancelled while queued: no compile
            assert eng.cancel(r.request_id)
        eng.close()
        report = build_report(str(log))
        sec = report["adapters"]
        assert sec is not None
        assert sec["admitted_by_adapter"] == {"a": 3, "b": 2, "c": 1}
        assert sec["admitted_by_index"] == {
            str(store.index_of("a")): 3, str(store.index_of("b")): 2,
            str(store.index_of("c")): 1}
        # counter view matches the event view key-for-key
        assert sec["counters"] == {
            f"adapter{store.index_of('a')}_requests": 3,
            f"adapter{store.index_of('b')}_requests": 2,
            f"adapter{store.index_of('c')}_requests": 1}
        assert sec["shed_unknown"] == 1
        # every terminal row carries its adapter_id (incl. the shed one)
        assert sec["finished_by_adapter"] == {"a": 3, "b": 2, "c": 1,
                                              "ghost": 1}
        text = render_report(report)
        assert "adapters (multi-LoRA):" in text

    def test_base_only_log_has_no_adapter_section(self, small, tmp_path):
        model, params = small
        log = tmp_path / "base.jsonl"
        reg = MetricsRegistry([JsonlSink(str(log))])
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              metrics=reg)
        req = Request(prompt=[1, 2], max_new_tokens=2)
        eng.submit(req)
        eng.cancel(req.request_id)
        eng.close()
        assert build_report(str(log))["adapters"] is None


# ---------------------------------------------------------------------------
# randomized multi-tenant churn (tier-1: one engine, one compile set)


class TestMultiTenantChurn:
    @pytest.mark.slow  # compile-bound churn integration (ROADMAP tiers)
    def test_churn_terminal_once_no_leaks_co_tenant_exact(self, small):
        """Seeded random multi-tenant arrivals x cancellations x a
        mid-run unload on one paged engine: every request reaches
        exactly one terminal state, pages/slots drain back to full,
        decode never retraces, and co-tenant duplicates (same prompt,
        same adapter, greedy) stay token-exact with each other."""
        model, params = small
        store, _ = _store(model.config, ids=("a", "b"))
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=16, page_size=4, retrace_budget=0),
            adapters=store)
        rng = np.random.RandomState(53)
        twin_prompt = rng.randint(0, 64, size=5).tolist()
        twins = [Request(prompt=list(twin_prompt), max_new_tokens=5,
                         sampling=SamplingParams(adapter_id="a"))
                 for _ in range(2)]
        randoms = [
            Request(prompt=rng.randint(0, 64,
                                       size=rng.randint(1, 9)).tolist(),
                    max_new_tokens=int(rng.randint(1, 6)),
                    sampling=SamplingParams(
                        adapter_id=[None, "a", "b"][rng.randint(3)]))
            for _ in range(10)]
        reqs = randoms[:4] + twins[:1] + randoms[4:] + twins[1:]
        shed = 0
        with eng:
            done = {}
            pending = list(reqs)
            ticks = 0
            unloaded = False
            while pending or eng.active_count or eng.queued_count:
                while pending and eng.queued_count < 4:
                    try:
                        eng.submit(pending.pop(0))
                    except UnknownAdapterError:
                        shed += 1   # recorded terminally by the engine
                for res in eng.tick():
                    done[res.request_id] = res
                ticks += 1
                if ticks == 6 and not unloaded:
                    # mid-run unload: in-flight "b" requests degrade to
                    # the zero row; queued/new "b" submits shed
                    store.unload("b")
                    unloaded = True
                if ticks % 5 == 0 and eng.active_count:
                    req, _, _ = eng.inflight()[
                        int(rng.randint(eng.active_count))]
                    eng.cancel(req.request_id)
                assert eng.pages.free_count + eng.pages.in_use_count == \
                    eng.pages.n_pages
            assert eng.decode_retraces == 0
            eng.pages.check()
            eng.slots.check()
            done.update(eng.completed)
        # conservation: every request terminal exactly once
        assert len(done) == len(reqs)
        assert sorted(done) == sorted(r.request_id for r in reqs)
        reasons = {r.finish_reason for r in done.values()}
        assert reasons <= {"length", "eos", "cancelled", "rejected"}
        rejected = [r for r in done.values()
                    if r.finish_reason == "rejected"]
        assert len(rejected) == shed
        assert all(r.adapter_id == "b" for r in rejected)
        assert eng.metrics.counters()["requests_shed_adapter"] == shed
        # co-tenant exactness: both twins finished under adapter "a"
        # (never unloaded) and emitted identical streams
        t0, t1 = (done[t.request_id] for t in twins)
        if t0.finish_reason != "cancelled" and \
                t1.finish_reason != "cancelled":
            assert t0.tokens == t1.tokens


# ---------------------------------------------------------------------------
# slow tier: merged-weights token-exactness (compile-bound parity)


@pytest.mark.slow
class TestAdapterParity:
    def test_paged_token_exact_greedy_and_sampled(self, small):
        """Acceptance: per-slot bank gathers are token-exact vs a
        reference engine serving merge_adapter'd params — greedy AND
        sampled, multiple tenants and base interleaved in one batch,
        with zero decode retraces."""
        model, params = small
        store, factors = _store(model.config, ids=("ta", "tb"))
        prompts = _prompts([5, 9, 3])
        ec = EngineConfig(max_slots=4, max_len=64, retrace_budget=0)
        eng = InferenceEngine(model, params, ec, adapters=store)

        def mk(p, aid, **kw):
            return Request(prompt=list(p), max_new_tokens=6,
                           sampling=SamplingParams(adapter_id=aid, **kw))

        reqs = [mk(prompts[0], "ta"), mk(prompts[0], "tb"),
                mk(prompts[1], None),
                mk(prompts[2], "ta", temperature=0.8, top_k=8, seed=11)]
        with eng:
            res = eng.serve(reqs)
            assert eng.decode_retraces == 0
        got = {q.request_id: r.tokens for q, r in zip(reqs, res)}
        merged = {"ta": merge_adapter(params, factors["ta"]),
                  "tb": merge_adapter(params, factors["tb"]),
                  None: params}
        for aid in ("ta", "tb", None):
            ref = InferenceEngine(model, merged[aid], ec)
            sel = [q for q in reqs if q.sampling.adapter_id == aid]
            with ref:
                rres = ref.serve([
                    Request(prompt=list(q.prompt),
                            max_new_tokens=q.max_new_tokens,
                            sampling=SamplingParams(
                                temperature=q.sampling.temperature,
                                top_k=q.sampling.top_k,
                                seed=q.sampling.seed))
                    for q in sel])
            for q, rr in zip(sel, rres):
                assert got[q.request_id] == rr.tokens, aid

    def test_variant_engines_token_exact(self, small):
        """The adapter path composes with the serving variants:
        speculation and int8+speculation both match their own
        merged-weights reference under the same config."""
        model, params = small
        store, factors = _store(model.config, ids=("a",))
        merged = merge_adapter(params, factors["a"])
        prompts = _prompts([5, 9, 3], seed=3)
        for name, ec in [
            ("spec", EngineConfig(max_slots=4, max_len=64, speculation=3,
                                  retrace_budget=0)),
            ("int8+spec", EngineConfig(max_slots=4, max_len=64,
                                       speculation=3, kv_dtype="int8",
                                       retrace_budget=0)),
        ]:
            eng = InferenceEngine(model, params, ec, adapters=store)
            with eng:
                res = eng.serve([
                    Request(prompt=list(p), max_new_tokens=6,
                            sampling=SamplingParams(adapter_id="a"))
                    for p in prompts])
            ref = InferenceEngine(model, merged, ec)
            with ref:
                rres = ref.serve([Request(prompt=list(p),
                                          max_new_tokens=6)
                                  for p in prompts])
            for r, rr in zip(res, rres):
                assert r.tokens == rr.tokens, name

    def test_hot_unload_degrades_inflight_rejects_new(self, small):
        model, params = small
        store, factors = _store(model.config, ids=("a",))
        ec = EngineConfig(max_slots=2, max_len=32, retrace_budget=0)
        prompt = _prompts([6], seed=9)[0]
        with InferenceEngine(model, params, ec, adapters=store) as eng:
            # admit under "a", then unload BEFORE prefill: the queued
            # request degrades to the null row — base-model output
            req = Request(prompt=list(prompt), max_new_tokens=6,
                          sampling=SamplingParams(adapter_id="a"))
            eng.submit(req)
            store.unload("a")
            while req.request_id not in eng.completed:
                eng.tick()
            degraded = eng.completed[req.request_id]
            with pytest.raises(UnknownAdapterError):
                eng.submit(Request(prompt=list(prompt), max_new_tokens=6,
                                   sampling=SamplingParams(
                                       adapter_id="a")))
        with InferenceEngine(model, params, ec) as base:
            ref = base.serve([Request(prompt=list(prompt),
                                      max_new_tokens=6)])
        assert degraded.tokens == ref[0].tokens

    def test_prefix_cache_no_cross_tenant_aliasing(self, small):
        """The aliasing regression at engine level: with the prefix
        cache ON, one prompt served under two adapters and base must
        give each tenant ITS merged-reference stream — adapter-salted
        chains keep adapter-specific K/V pages from crossing tenants —
        while same-tenant repeats still hit the cache."""
        model, params = small
        store, factors = _store(model.config, ids=("a", "b"))
        prompt = _prompts([8], seed=17)[0]
        ec = EngineConfig(max_slots=4, max_len=32, page_size=4,
                          prefix_cache=True, retrace_budget=0)

        def mk(aid):
            return Request(prompt=list(prompt), max_new_tokens=6,
                           sampling=SamplingParams(adapter_id=aid))

        eng = InferenceEngine(model, params, ec, adapters=store)
        with eng:
            first = eng.serve([mk("a"), mk("b"), mk(None)])
            again = eng.serve([mk("a")])   # same tenant: cache hit
            assert eng.metrics.counters()["prefix_hits"] >= 1
        expected = {}
        for aid, p in (("a", merge_adapter(params, factors["a"])),
                       ("b", merge_adapter(params, factors["b"])),
                       (None, params)):
            with InferenceEngine(model, p, ec) as ref:
                expected[aid] = ref.serve(
                    [Request(prompt=list(prompt),
                             max_new_tokens=6)])[0].tokens
        assert first[0].tokens == expected["a"]
        assert first[1].tokens == expected["b"]
        assert first[2].tokens == expected[None]
        assert again[0].tokens == expected["a"]


# ---------------------------------------------------------------------------
# slow tier: tp=2 sharded adapters (B bank shards with the heads)


@pytest.fixture()
def tp2_mesh():
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


class TestShardedAdapters:
    @pytest.mark.slow
    def test_tp2_token_exact_vs_unsharded(self, small, tp2_mesh):
        """ShardedEngine with adapters on a tp=2 CPU mesh: the B bank
        shards its out dim with the weights (A replicated), and decode
        stays token-exact vs the unsharded adapter engine — greedy and
        sampled — with zero decode retraces."""
        model, params = small
        store, _ = _store(model.config, ids=("a",))
        prompts = _prompts([4, 7, 3], seed=61)

        def reqs():
            return [Request(prompt=list(prompts[0]), max_new_tokens=6,
                            sampling=SamplingParams(adapter_id="a")),
                    Request(prompt=list(prompts[1]), max_new_tokens=5,
                            sampling=SamplingParams(
                                adapter_id="a", temperature=0.8,
                                top_k=8, seed=3)),
                    Request(prompt=list(prompts[2]), max_new_tokens=6)]

        ec = EngineConfig(max_slots=4, max_len=32, retrace_budget=0)
        from apex_tpu.transformer import parallel_state

        parallel_state.destroy_model_parallel()
        ref = InferenceEngine(model, params, ec, adapters=store)
        with ref:
            base = ref.serve(reqs())
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2)
        sh = ShardedEngine(model, params, ec, adapters=store)
        with sh:
            out = sh.serve(reqs())
            assert sh.decode_retraces == 0
        for a, b in zip(base, out):
            assert a.tokens == b.tokens


# ---------------------------------------------------------------------------
# a gated model: the engine holds the MLP's gate/up weight halves apart,
# the adapter's B keeps its interleaved out columns


@pytest.fixture(scope="module")
def gated():
    model = GPTModel(TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, activation="swiglu",
        untie_embeddings_and_output_weights=True, init_method_std=0.3))
    return model, model.init(jax.random.PRNGKey(0))


def _gated_requests():
    prompts = _prompts([5, 9, 3], seed=23)
    return [Request(prompt=list(prompts[0]), max_new_tokens=5,
                    sampling=SamplingParams(adapter_id="a")),
            Request(prompt=list(prompts[1]), max_new_tokens=4,
                    sampling=SamplingParams(adapter_id="a", temperature=0.8,
                                            top_k=8, seed=11)),
            Request(prompt=list(prompts[2]), max_new_tokens=5)]


class TestGatedModelAdapters:
    def test_delta_meets_its_columns_under_the_relaid_weight(self, gated):
        """An adapter on ``dense_h_to_4h`` of a gated model: the engine
        (weight re-laid to ``[2, ffn, h]``, delta re-ordered to match)
        serves the tokens of an engine over ``merge_adapter``'d params,
        whose interleaved fold knows nothing of the serving form; greedy
        and sampled, base traffic beside it."""
        model, params = gated
        store, factors = _store(model.config, ids=("a",), scale=0.3)
        ec = EngineConfig(max_slots=4, max_len=32, retrace_budget=0)
        with InferenceEngine(model, params, ec, adapters=store) as eng:
            got = eng.serve(_gated_requests())
            assert eng.decode_retraces == 0
        merged = merge_adapter(params, factors["a"])
        for q, r in zip(_gated_requests(), got):
            ref = Request(prompt=list(q.prompt),
                          max_new_tokens=q.max_new_tokens,
                          sampling=SamplingParams(
                              temperature=q.sampling.temperature,
                              top_k=q.sampling.top_k, seed=q.sampling.seed))
            with InferenceEngine(
                    model, merged if q.sampling.adapter_id else params,
                    ec) as one:
                assert r.tokens == one.serve([ref])[0].tokens
        # the adapter is no bystander: the same prompt without it differs
        bare = Request(prompt=list(_gated_requests()[0].prompt),
                       max_new_tokens=5)
        with InferenceEngine(model, params, ec) as base:
            assert base.serve([bare])[0].tokens != got[0].tokens

    def test_tp2_delta_stays_rank_local(self, gated, tp2_mesh):
        """Under tensor parallelism ``B`` shards its interleaved out
        columns and the weight its ``ffn`` axis: each rank's delta slice
        holds the pairs of its own ``ffn`` rows, so the re-ordering is
        rank-local and the tokens are the unsharded engine's."""
        from apex_tpu.transformer import parallel_state

        model, params = gated
        store, _ = _store(model.config, ids=("a",), scale=0.3)
        ec = EngineConfig(max_slots=4, max_len=32, retrace_budget=0)
        parallel_state.destroy_model_parallel()
        with InferenceEngine(model, params, ec, adapters=store) as ref:
            base = ref.serve(_gated_requests())
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2)
        with ShardedEngine(model, params, ec, adapters=store) as sh:
            out = sh.serve(_gated_requests())
            assert sh.decode_retraces == 0
        for a, b in zip(base, out):
            assert a.tokens == b.tokens
