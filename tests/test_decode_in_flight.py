"""One decode step in flight (docs/serving.md#one-step-in-flight).

The plain decode path dispatches step k before it reads step k-1: a
continuing slot's fed token stays on the device, its position is known
without it, and the host learns EOS, a poisoned row, a cancel or a
deadline one step late, at the price of at most one dropped row a slot.
Whatever the scenario, a served stream equals the per-request reference
(``tests/serving_reference.py``) token for token, greedy and sampled; the
two counters read what the scenario implies; the decode program compiles
once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import InMemorySink, MetricsRegistry
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    InferenceEngine,
    Request,
    SamplingParams,
)
from apex_tpu.serving.clock import VirtualClock, use_clock
from apex_tpu.serving.request import PRIORITY_BATCH
from apex_tpu.testing_faults import ServingFaultInjector
from serving_reference import reference_stream

MAX_LEN = 24
SAMPLED = SamplingParams(temperature=0.9, top_k=12, seed=17)


@pytest.fixture(scope="module")
def small():
    # an untied head and a wide init: the greedy streams then vary with
    # the prompt and from token to token (the usual tiny model repeats
    # one token, under which a wrongly fed token would not show)
    model = GPTModel(TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, untie_embeddings_and_output_weights=True,
        init_method_std=0.3))
    return model, model.init(jax.random.PRNGKey(0))


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 64, size=n).tolist()


def _engine(small, *, faults=None, **config):
    model, params = small
    config = {"max_slots": 2, "max_len": MAX_LEN, "page_size": 4, **config}
    return InferenceEngine(model, params, EngineConfig(**config),
                           metrics=MetricsRegistry([InMemorySink()]),
                           faults=faults)


def _ref(small, request):
    model, params = small
    return reference_stream(model, params, request, MAX_LEN)


def _with_eos_at(small, n, prompt_len, seed, max_new, sampling=None):
    """A request whose ``n``-th token (1-based) is its first EOS, and
    the stream it then serves: the reference stream without EOS names
    the token; a prompt under which the token shows up earlier is
    passed over for the next seed."""
    sampling = sampling or SamplingParams()
    for seed in range(seed, seed + 800, 100):
        prompt = _prompt(prompt_len, seed)
        free = _ref(small, Request(prompt=prompt, max_new_tokens=max_new,
                                   sampling=sampling))
        if free[n - 1] not in free[:n - 1]:
            return Request(prompt=prompt, max_new_tokens=max_new,
                           sampling=sampling, eos_token=free[n - 1]), free[:n]
    raise AssertionError("no prompt gives a first EOS there")


def _counters(engine):
    c = engine.metrics.counters()
    return (c["decode_steps"], c["decode_steps_overlapped"],
            c["decode_rows_dropped"])


def _compiled_once(engine):
    return engine.decode_compiles == 1 and engine.decode_retraces == 0


# -- the tick, step by step -------------------------------------------------

@pytest.mark.parametrize("sampling", [SamplingParams(), SAMPLED],
                         ids=["greedy", "sampled"])
def test_a_stream_is_committed_one_tick_behind_its_dispatch(small, sampling):
    """One request, six tokens: the prefill's token in tick 1, then one
    token a tick, each from the step dispatched the tick before; the
    step that would deliver a seventh is never dispatched, and the last
    tick has nothing to dispatch and still commits."""
    req = Request(prompt=_prompt(5, 1), max_new_tokens=6, sampling=sampling)
    want = _ref(small, req)
    with _engine(small) as eng:
        eng.submit(req)
        for tick in range(1, 6):
            assert eng.tick() == []
            assert eng._flight is not None
            # inflight() shows committed tokens only
            ((_, tokens, _),) = eng.inflight()
            assert tokens == want[:tick]
        (done,) = eng.tick()
        assert eng._flight is None and eng.active_count == 0
        assert done.tokens == want and done.finish_reason == "length"
        assert _counters(eng) == (5, 4, 0)
        assert eng.metrics.counters()["tokens_generated"] == 6
        assert _compiled_once(eng)


def test_max_len_is_reached_without_a_row_past_it(small):
    """``prompt + max_new_tokens == max_len``: the stream ends by length
    on the last row of the table, nothing dropped, no page past the
    reservation."""
    req = Request(prompt=_prompt(MAX_LEN - 6, 2), max_new_tokens=6,
                  sampling=SAMPLED)
    with _engine(small, max_slots=1, n_pages=MAX_LEN // 4,
                 prefix_cache=False) as eng:
        (res,) = eng.serve([req])
        assert res.tokens == _ref(small, req)
        assert res.finish_reason == "length"
        assert _counters(eng) == (5, 4, 0)
        assert eng.pages.free_count == eng.pages.n_pages
        eng.pages.check()


@pytest.mark.parametrize("sampling", [SamplingParams(), SAMPLED],
                         ids=["greedy", "sampled"])
def test_eos_is_learned_one_step_late(small, sampling):
    """EOS as the third token: it is read in tick 3, after step 2 went
    out with a row for the slot; that row's result is dropped in tick
    4."""
    req, want = _with_eos_at(small, 3, 4, 3, 8, sampling)
    with _engine(small) as eng:
        eng.submit(req)
        assert eng.tick() == [] and eng.tick() == []
        (done,) = eng.tick()
        assert done.finish_reason == "eos" and done.tokens == want
        assert eng.active_count == 0 and eng._flight is not None
        assert _counters(eng) == (3, 2, 0)
        assert eng.tick() == []
        assert eng._flight is None
        assert _counters(eng) == (3, 2, 1)
        assert eng.pages.free_count + eng.pages.reclaimable_count \
            == eng.pages.n_pages
        assert _compiled_once(eng)


@pytest.mark.parametrize("mix", ["greedy", "sampled"])
def test_a_mixed_batch_equals_the_reference(small, mix):
    """Five requests over three slots, arrivals and retirements on
    different ticks: length, EOS mid-stream, EOS as the very last token
    (no row dropped), ``max_len`` reached. ``serve`` runs until the last
    step in flight is read."""
    sampling = (lambda i: SamplingParams()) if mix == "greedy" else (
        lambda i: SamplingParams(temperature=0.8, top_k=10, seed=31 + i))
    late, _ = _with_eos_at(small, 4, 6, 11, 9, sampling(1))
    last, _ = _with_eos_at(small, 5, 3, 12, 5, sampling(2))
    reqs = [Request(prompt=_prompt(5, 10), max_new_tokens=7,
                    sampling=sampling(0)),
            late, last,
            Request(prompt=_prompt(MAX_LEN - 4, 13), max_new_tokens=4,
                    sampling=sampling(3)),
            Request(prompt=_prompt(2, 14), max_new_tokens=10,
                    sampling=sampling(4))]
    with _engine(small, max_slots=3) as eng:
        results = eng.serve(reqs)
        for req, res in zip(reqs, results):
            assert res.tokens == _ref(small, req), req.request_id
        assert [r.finish_reason for r in results] == [
            "length", "eos", "eos", "length", "length"]
        steps, overlapped, dropped = _counters(eng)
        # only the EOS before the budget's end cost a row
        assert dropped == 1
        assert 0 < overlapped < steps
        batch = eng.metrics.histogram("decode_batch_size")
        assert eng.metrics.counters()["tokens_generated"] \
            == len(reqs) + batch.sum - dropped
        assert eng._flight is None
        assert _compiled_once(eng)


# -- a request that leaves while its row is in flight -----------------------

def _two_decoding(eng, ticks=3):
    """Two requests admitted in tick 1, ticked until each has ``ticks``
    committed tokens and a row in flight."""
    a = Request(prompt=_prompt(4, 21), max_new_tokens=9)
    b = Request(prompt=_prompt(6, 22), max_new_tokens=9, sampling=SAMPLED)
    eng.submit(a)
    eng.submit(b)
    for _ in range(ticks):
        eng.tick()
    assert [len(t) for _, t, _ in eng.inflight()] == [ticks, ticks]
    assert len(eng._flight.rows) == 2
    return a, b


def _finish(eng):
    while eng.active_count or eng.queued_count or eng._flight is not None:
        eng.tick()


def test_cancel_drops_the_row_in_flight(small):
    with _engine(small) as eng:
        a, b = _two_decoding(eng)
        assert eng.cancel(a.request_id)
        (gone,) = eng.tick()
        assert gone.finish_reason == "cancelled"
        assert gone.tokens == _ref(small, a)[:3]     # committed tokens only
        assert _counters(eng)[2] == 1
        _finish(eng)
        assert eng.completed[b.request_id].tokens == _ref(small, b)
        assert _counters(eng)[2] == 1
        assert _compiled_once(eng)


def test_deadline_drops_the_row_in_flight(small):
    with use_clock(VirtualClock()) as vc, _engine(small) as eng:
        a = Request(prompt=_prompt(4, 23), max_new_tokens=9, deadline_s=5.0)
        b = Request(prompt=_prompt(6, 24), max_new_tokens=9)
        eng.submit(a)
        eng.submit(b)
        for _ in range(3):
            eng.tick()
        vc.advance(10.0)
        (gone,) = eng.tick()
        assert gone.finish_reason == "timeout"
        assert gone.tokens == _ref(small, a)[:3]
        _finish(eng)
        assert eng.completed[b.request_id].tokens == _ref(small, b)
        assert _counters(eng)[2] == 1


def test_park_drops_the_row_and_the_resume_samples_it_again(small):
    """A parked request keeps its committed tokens; the token of its row
    in flight is sampled again, bit for bit, when the supervisor resumes
    it (``(seed, position)`` keys the draw)."""
    model, params = small
    batch = SamplingParams(temperature=0.9, top_k=12, seed=5,
                           priority=PRIORITY_BATCH)
    a = Request(prompt=_prompt(4, 25), max_new_tokens=9, sampling=batch)
    b = Request(prompt=_prompt(6, 26), max_new_tokens=9)
    sup = EngineSupervisor(model, params, EngineConfig(
        max_slots=2, max_len=MAX_LEN, page_size=4))
    with sup:
        sup.submit(a)
        sup.submit(b)
        for _ in range(3):
            sup.tick()
        assert sup.engine._flight is not None
        assert sup.preempt_class(PRIORITY_BATCH, cause="test") == 1
        while sup.inflight_count:
            sup.tick()
        assert sup.completed[a.request_id].tokens == _ref(small, a)
        assert sup.completed[b.request_id].tokens == _ref(small, b)
        counters = sup.metrics.counters()
        assert counters["requests_preempted"] == 1
        assert counters["decode_rows_dropped"] == 1
        assert sup.engine.decode_retraces == 0


def test_a_slot_is_reused_in_the_tick_after_its_late_eos(small):
    """One slot, a pool of exactly one request's pages: B is prefilled
    into the slot, and onto the pages, that A's late row (still in
    flight) was dispatched for; the row is dropped, B's stream is
    untouched."""
    a, want_a = _with_eos_at(small, 3, 4, 27, 8)
    b = Request(prompt=_prompt(5, 28), max_new_tokens=7, sampling=SAMPLED)
    with _engine(small, max_slots=1, n_pages=3, prefix_cache=False) as eng:
        eng.submit(a)
        eng.submit(b)
        eng.tick(), eng.tick()
        (done,) = eng.tick()
        assert done.tokens == want_a
        late = eng._flight
        assert [rec.request.request_id for _, rec in late.rows] \
            == [a.request_id]
        eng.tick()                  # B's prefill, behind the late row
        assert eng.admission_log == [a.request_id, b.request_id]
        assert eng._flight is not late and _counters(eng)[2] == 1
        _finish(eng)
        assert eng.completed[b.request_id].tokens == _ref(small, b)
        assert _counters(eng)[2] == 1
        eng.pages.check()
        assert _compiled_once(eng)


# -- faults -----------------------------------------------------------------

@pytest.mark.parametrize("kind", ["nonfinite", "oov"])
def test_a_poisoned_row_is_quarantined_a_step_late(small, kind):
    """Decode call 1's output is poisoned for slot 0: the host reads it
    in tick 3, after call 2 went out with a row for the slot. The
    victim keeps its two clean tokens, its further row is dropped, the
    pages it frees are scrubbed behind that row, and the co-tenant's
    stream is the reference's."""
    reqs = [Request(prompt=_prompt(3, 31), max_new_tokens=7),
            Request(prompt=_prompt(5, 32), max_new_tokens=7,
                    sampling=SAMPLED)]
    inj = ServingFaultInjector(poison_decode={1: (0, kind)})
    with _engine(small, faults=inj) as eng:
        victim, cotenant = eng.serve(reqs)
        assert victim.finish_reason == "error"
        assert victim.tokens == _ref(small, reqs[0])[:2]
        assert cotenant.finish_reason == "length"
        assert cotenant.tokens == _ref(small, reqs[1])
        counters = eng.metrics.counters()
        assert counters["slots_quarantined"] == 1
        assert counters["decode_rows_dropped"] == 1
        assert inj.log == [("poison", 1, 0, kind)]
        eng.slots.check()
        eng.pages.check()
        assert eng.pages.free_count + eng.pages.reclaimable_count \
            == eng.pages.n_pages
        assert _compiled_once(eng)


def test_a_restart_with_a_row_in_flight_resumes_from_committed_tokens(small):
    """Decode call 2 raises while call 1's result is still unread: the
    supervisor's restart sees the two committed tokens of each request,
    re-prefills, and the streams are the reference's."""
    model, params = small
    reqs = [Request(prompt=_prompt(3, 33), max_new_tokens=6),
            Request(prompt=_prompt(5, 34), max_new_tokens=8,
                    sampling=SAMPLED)]
    sink = InMemorySink()
    sup = EngineSupervisor(
        model, params, EngineConfig(max_slots=2, max_len=MAX_LEN,
                                    page_size=4),
        metrics=MetricsRegistry([sink]),
        faults=ServingFaultInjector(decode_raise_calls={2}))
    with sup:
        results = sup.serve(reqs)
        assert sup.restarts == 1
        for req, res in zip(reqs, results):
            assert res.tokens == _ref(small, req)
        resumed = [r["tokens_resumed"] for r in sink.of_kind("event")
                   if r.get("event") == "request_recovered"]
        assert resumed == [2, 2]


def test_close_counts_the_rows_nobody_read(small):
    eng = _engine(small)
    eng.submit(Request(prompt=_prompt(4, 35), max_new_tokens=6))
    eng.tick()
    eng.tick()
    assert eng._flight is not None
    eng.close()
    assert eng._flight is None
    assert _counters(eng) == (2, 1, 1)


# -- other pools and paths --------------------------------------------------

def test_an_int8_pool_serves_what_the_lock_step_engine_serves(small):
    """int8 pages: the plain engine (one step in flight) against the
    same pool under ``speculation=2``, whose tick reads every step it
    dispatches, greedy and sampled, a late EOS among them (its token
    taken from the lock-step engine's own stream: int8 rounding moves
    tokens off the float reference's); the scale sidecars pass the
    pool's check after a late row touched a page of a slot that was
    then retired."""
    specs = [(_prompt(6, 41), 8, SamplingParams()),
             (_prompt(4, 42), 9, SAMPLED),
             (_prompt(7, 43), 6, SamplingParams())]

    def requests(eos=None):
        return [Request(prompt=p, max_new_tokens=m, sampling=s,
                        eos_token=eos if i == 0 else None)
                for i, (p, m, s) in enumerate(specs)]

    int8 = dict(kv_dtype="int8", prefix_cache=False)
    with _engine(small, speculation=2, **int8) as lock_step:
        free = lock_step.serve(requests())[0].tokens
        n = next(i for i in range(1, 7) if free[i] not in free[:i])
        want = [r.tokens for r in lock_step.serve(requests(free[n]))]
        assert want[0] == free[:n + 1]
        assert _counters(lock_step)[1:] == (0, 0)
    with _engine(small, **int8) as eng:
        got = eng.serve(requests(free[n]))
        assert [r.tokens for r in got] == want
        assert got[0].finish_reason == "eos"
        steps, overlapped, dropped = _counters(eng)
        assert overlapped > 0 and dropped == 1
        for (_, ks), (_, vs) in eng._caches:
            eng.pages.check(np.asarray(ks), np.asarray(vs))
        assert _compiled_once(eng)


def test_speculation_reads_every_step_in_its_own_tick(small):
    """``speculation >= 2`` keeps the lock-step tick: nothing is ever in
    flight between ticks and the streams are the reference's."""
    reqs = [Request(prompt=_prompt(5, 44), max_new_tokens=8),
            Request(prompt=_prompt(3, 45), max_new_tokens=6,
                    sampling=SAMPLED)]
    with _engine(small, speculation=3) as eng:
        for r in reqs:
            eng.submit(r)
        while eng.active_count or eng.queued_count:
            eng.tick()
            assert eng._flight is None
        for r in reqs:
            assert eng.completed[r.request_id].tokens == _ref(small, r)
        steps, overlapped, dropped = _counters(eng)
        assert steps > 0 and (overlapped, dropped) == (0, 0)


def test_the_default_arguments_feed_the_hosts_tokens(small):
    """``_decode_args()`` with nothing said (what ``decode_program_text``
    and the kernel probes lower) takes every fed token from the host's
    vector and leaves table and positions as the host arrays stand."""
    with _engine(small) as eng:
        eng.submit(Request(prompt=_prompt(4, 46), max_new_tokens=4))
        eng.tick()
        args = eng._decode_args()
        assert len(args) == 12
        _, _, table, tokens, carry, from_host, positions = args[:7]
        assert np.asarray(from_host).all()
        assert np.array_equal(np.asarray(table), eng._page_table_h)
        assert np.array_equal(np.asarray(positions), eng._positions_h)
        assert np.array_equal(np.asarray(tokens), eng._tokens_h)
        assert carry.shape == (eng.config.max_slots,)


# -- an admission tick: the first token rides to the step on the device -----

class _Spans:
    """Stands in for the engine's ``span``: every span as entered, its
    name and attributes (``set_metadata`` adds to them)."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **attrs):
        self.log.append((name, attrs))

        class _Span:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **more):
                attrs.update(more)

        return _Span()


def _lock_step(eng):
    """The order the plain engine kept before a prefill's first token
    rode to the decode step on the device: every prefill and chunk is
    read as soon as it went out, before the tick's step is built. The
    same programs on the same inputs: only the host's order differs."""
    for name in ("_prefill_into", "_run_chunk"):
        run = getattr(eng, name)

        def read_at_once(*args, run=run):
            out = run(*args)
            eng._read_prefills(args[-1])
            return out

        setattr(eng, name, read_at_once)
    return eng


@pytest.mark.parametrize("spec", [0, 2], ids=["plain", "speculation"])
def test_an_admission_tick_dispatches_its_step_before_the_read(
        small, monkeypatch, spec):
    """Plain: the step that goes out in the admitting tick is fed the
    prefill's token on the device (``carried``), and the prefill is read
    after it. Speculation drafts from the host's tokens: it reads the
    prefill at once, before the step, and carries nothing."""
    import apex_tpu.serving.engine as engine_module

    spans = _Spans()
    monkeypatch.setattr(engine_module, "span", spans)
    with _engine(small, speculation=spec) as eng:
        eng.submit(Request(prompt=_prompt(5, 51), max_new_tokens=4))
        eng.tick()
        order = [(n, a) for n, a in spans.log
                 if n in ("tick.dispatch", "tick.readback")]
        kinds = [a.get("program") or ("step" if "lag" in a else "prefill")
                 for _, a in order]
        decode = next(a for _, a in order if a.get("program") == "decode")
        counted = eng.metrics.counters()["first_tokens_carried"]
        if spec:
            assert kinds == ["paged_prefill", "prefill", "decode", "step"]
            assert decode["carried"] == 0 and counted == 0
        else:
            assert kinds == ["paged_prefill", "decode", "prefill"]
            assert decode["carried"] == 1 and counted == 1
        # the first token is committed in its tick; with speculation the
        # step's tokens are too
        ((_, tokens, _),) = eng.inflight()
        assert len(tokens) > 1 if spec else len(tokens) == 1


def _served(eng, requests):
    """``requests`` served through ``eng`` (copies: an engine keeps what
    it completed by request id), results in order."""
    return eng.serve([Request(prompt=list(r.prompt),
                              max_new_tokens=r.max_new_tokens,
                              sampling=r.sampling, eos_token=r.eos_token)
                      for r in requests])


def _case(small, kind):
    """Engine settings and requests of one kind of admission."""
    model, _ = small
    mixed = [SamplingParams(), SAMPLED,
             SamplingParams(temperature=0.7, top_k=9, seed=3)]
    prompts = [_prompt(n, 60 + n) for n in (5, 11, 3, 17, 8)]
    config, adapters, ids = {"max_slots": 3}, None, [None] * 5
    if kind == "prefix_hit":
        # two full pages shared: the second and later prompts hit the
        # first's pages, in the tick it was prefilled and after
        shared = _prompt(8, 70)
        prompts = [shared + _prompt(n, 71 + n) for n in (3, 5, 0, 9, 1)]
    elif kind == "int8":
        config.update(kv_dtype="int8", prefix_cache=False)
    elif kind == "lora":
        from apex_tpu.lora import AdapterStore, random_adapter

        adapters = AdapterStore(model.config, 4, max_adapters=2)
        for i, aid in enumerate(("ta", "tb")):
            adapters.load(aid, random_adapter(
                model.config, 4, jax.random.PRNGKey(i + 1), scale=0.05))
        ids = ["ta", None, "tb", "ta", "tb"]
    elif kind == "chunked":
        config.update(prefill_token_budget=8)
    requests = [
        Request(prompt=p, max_new_tokens=3 + i,
                sampling=SamplingParams(
                    temperature=mixed[i % 3].temperature,
                    top_k=mixed[i % 3].top_k, seed=mixed[i % 3].seed,
                    adapter_id=aid))
        for i, (p, aid) in enumerate(zip(prompts, ids))]
    return config, adapters, requests


@pytest.mark.parametrize("kind", ["fresh", "prefix_hit", "int8", "lora",
                                  "chunked"])
def test_carried_first_tokens_serve_what_the_lock_step_order_serves(
        small, kind):
    """Mixed batches, greedy and sampled, each kind of admission: the
    tokens are the lock-step order's bit for bit (the same programs,
    the prefill read before the step is built), every first token fed
    its tick's step on the device, one decode program, a prefill
    program a bucket at most."""
    model, params = small
    config, adapters, requests = _case(small, kind)

    def engine():
        return InferenceEngine(
            model, params, EngineConfig(max_len=MAX_LEN, page_size=4,
                                        **config),
            metrics=MetricsRegistry([InMemorySink()]), adapters=adapters)

    with _lock_step(engine()) as lock_step:
        want = _served(lock_step, requests)
        assert lock_step.metrics.counters()["first_tokens_carried"] == 0
    with engine() as eng:
        got = _served(eng, requests)
        assert [r.tokens for r in got] == [r.tokens for r in want]
        assert [r.finish_reason for r in got] \
            == [r.finish_reason for r in want] == ["length"] * 5
        counters = eng.metrics.counters()
        assert counters["prefills"] == len(requests)
        carried = counters["first_tokens_carried"]
        if kind == "prefix_hit":
            # an admission whose prompt starts with the page of a prefill
            # of its tick has that prefill read first, so that it finds
            # the pages interned: the hits are the lock-step order's
            assert counters["prefix_hits"] \
                == lock_step.metrics.counters()["prefix_hits"] > 0
            assert 0 < carried < counters["prefills"]
        else:
            assert carried == counters["prefills"]
        if kind == "chunked":
            assert counters["prefill_chunks"] > len(requests)
        assert _compiled_once(eng)
        assert eng.prefill_compiles <= len(eng.buckets)
        assert eng.chunk_compiles <= len(eng.buckets)


def test_a_first_token_that_is_eos_drops_its_row(small):
    """EOS as the first token: the tick's step went out with a row for
    the slot before the read found it; the request ends in that tick,
    the row is dropped in the next, where the slot takes the next
    request."""
    a, want_a = _with_eos_at(small, 1, 4, 81, 6)
    b = Request(prompt=_prompt(5, 82), max_new_tokens=4, sampling=SAMPLED)
    with _engine(small, max_slots=1) as eng:
        eng.submit(a)
        eng.submit(b)
        (done,) = eng.tick()
        assert done.finish_reason == "eos" and done.tokens == want_a
        assert [rec.request.request_id for _, rec in eng._flight.rows] \
            == [a.request_id]
        assert _counters(eng) == (1, 0, 0)
        assert eng.tick() == []
        assert eng.admission_log == [a.request_id, b.request_id]
        assert _counters(eng) == (2, 1, 1)
        _finish(eng)
        assert eng.completed[b.request_id].tokens == _ref(small, b)
        assert eng.metrics.counters()["first_tokens_carried"] == 2
        assert _compiled_once(eng)


def test_a_single_token_request_gets_no_row(small):
    """``max_new_tokens == 1``: the prefill's token is the last, known
    without reading it; no step goes out for the slot."""
    req = Request(prompt=_prompt(6, 83), max_new_tokens=1, sampling=SAMPLED)
    with _engine(small) as eng:
        eng.submit(req)
        (done,) = eng.tick()
        assert done.tokens == _ref(small, req)
        assert done.finish_reason == "length"
        assert eng._flight is None
        counters = eng.metrics.counters()
        assert counters["decode_steps"] == 0
        assert counters["first_tokens_carried"] == 0


def test_a_nonfinite_prefill_is_quarantined_and_not_interned(small):
    """A prefill whose logits are not finite: read after the tick's
    step went out with a row for it, the slot is quarantined (that row
    dropped), and its prompt's pages are not published; the same prompt
    again misses and serves the reference's stream."""
    prompt = _prompt(10, 84)
    with _engine(small) as eng:
        real = eng._prefill_fn

        def poisoned(*args):
            first, _, caches, carry = real(*args)
            return first, jnp.zeros((), bool), caches, carry

        eng._prefill_fn = poisoned
        eng.submit(Request(prompt=prompt, max_new_tokens=5))
        (bad,) = eng.tick()
        assert bad.finish_reason == "error" and bad.tokens == []
        eng._prefill_fn = real
        again = Request(prompt=prompt, max_new_tokens=5)
        (res,) = eng.serve([again])
        assert res.tokens == _ref(small, again)
        counters = eng.metrics.counters()
        assert counters["slots_quarantined"] == 1
        assert counters["decode_rows_dropped"] == 1
        assert (counters["prefix_hits"], counters["prefix_misses"]) == (0, 2)
        eng.pages.check()
        assert _compiled_once(eng)
