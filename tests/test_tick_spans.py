"""The program's own tracing: host spans inside the serving tick
(``observability.tracing.span`` = ``jax.profiler.TraceAnnotation``) and
scope names inside the step programs (``nvtx_range`` =
``jax.named_scope``).

A tiny engine is ticked under ``jax.profiler.start_trace`` on the CPU and
the trace file is read back: every ``engine.tick`` holds the five leaf
spans in order, disjoint, with their attributes, and a prefill carries its
request's ``trace_id`` and the prompt tokens its program computed; a
decode dispatch says whether a step was in flight ahead of it and how
many of its rows were fed a prefill's first token on the device, which the
tick reads after it, and a read-back how many went out behind the step it
reads. With no profiler on, a tick writes nothing new into the registry. The compiled text of a decode program, a prefill
program and a sharded train step carries every scope name in some
``op_name``. Token streams do not change under the profiler.
"""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import MetricsRegistry, tracing
from apex_tpu.observability.tracing import TICK_LEAF_SPANS
from apex_tpu.ops import _support
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    InferenceEngine,
    Request,
    SamplingParams,
)

CONFIG = TransformerConfig(
    num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
    max_position_embeddings=32, hidden_dropout=0.0, attention_dropout=0.0)
ENGINE = EngineConfig(max_slots=4, max_len=32, page_size=8)


@pytest.fixture(scope="module")
def small():
    model = GPTModel(CONFIG)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(seed=3):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, 64, size=n).tolist(),
                    max_new_tokens=new, sampling=s)
            for n, new, s in (
                (5, 6, SamplingParams()),
                (9, 5, SamplingParams(temperature=0.7, top_k=5, seed=11)),
                (3, 7, SamplingParams(temperature=1.1, seed=4)))]


def _traced(fn, log_dir):
    """Run ``fn`` under a profiler session (Python tracer off, as the
    benchmark's traced runs have it); returns (result, host events) with
    the events as ``(name, start_ns, end_ns, stats)`` in start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.split(".")[0] in ("tick", "engine",
                                                   "supervisor")]
    return result, sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(events, outer):
    return [e for e in events
            if e is not outer and outer[1] <= e[1] and e[2] <= outer[2]]


@pytest.fixture(scope="module")
def traced_ticks(small, tmp_path_factory):
    """Three requests through a warm engine, ticked to the end under the
    profiler: (requests, results by id, host events)."""
    model, params = small
    engine = InferenceEngine(model, params, ENGINE)
    # every program compiled, on other prompts of the same lengths (the
    # same prompts again would be prefix-cache hits)
    engine.serve(_requests(seed=5))
    requests = _requests()

    def go():
        for r in requests:
            engine.submit(r)
        while engine.active_count or engine.queued_count:
            engine.tick()
        return {r.request_id: engine.completed[r.request_id]
                for r in requests}

    results, events = _traced(go, tmp_path_factory.mktemp("trace"))
    engine.close()
    return requests, results, events


def test_every_tick_holds_the_leaf_spans_disjoint_and_in_order(traced_ticks):
    _, _, events = traced_ticks
    ticks = [e for e in events if e[0] == tracing.TICK_ENGINE]
    assert len(ticks) >= 5
    decode_only = 0
    for tick in ticks:
        leaves = [e for e in _inside(events, tick)
                  if e[0] in TICK_LEAF_SPANS]
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1], (a, b)            # disjoint, in time order
        names = [e[0] for e in leaves]
        runs = [n for i, n in enumerate(names) if i == 0 or names[i - 1] != n]
        if not any(e[0] == tracing.TICK_PREFILL
                   for e in _inside(events, tick)):
            if tracing.TICK_DISPATCH in runs:
                assert tuple(runs) == TICK_LEAF_SPANS
                decode_only += 1
        # whatever the tick did, it starts by scheduling and ends by
        # committing
        assert runs[0] == tracing.TICK_SCHEDULE
        assert runs[-1] == tracing.TICK_COMMIT
    assert decode_only >= 3


@pytest.mark.parametrize("name,keys", [
    (tracing.TICK_SCHEDULE, {"queued", "active"}),
    (tracing.TICK_SCHEDULE, {"active", "pages_mapped"}),
    (tracing.TICK_UPLOAD, {"arrays", "bytes"}),
    (tracing.TICK_DISPATCH, {"program", "rows"}),
    (tracing.TICK_DISPATCH, {"program", "rows", "in_flight"}),
    (tracing.TICK_READBACK, {"reads", "bytes"}),
    (tracing.TICK_READBACK, {"reads", "bytes", "lag"}),
    (tracing.TICK_COMMIT, {"tokens", "retired"}),
    (tracing.TICK_COMMIT, {"tokens", "dropped", "retired"}),
])
def test_leaf_spans_carry_their_counts(traced_ticks, name, keys):
    _, results, events = traced_ticks
    mine = [e for e in events if e[0] == name and keys <= set(e[3])]
    assert mine, f"no {name} span with {sorted(keys)}"
    if name == tracing.TICK_DISPATCH and "in_flight" in keys:
        # one decode step in flight: only the first step of the run goes
        # out with nothing ahead of it
        assert [int(e[3]["in_flight"]) for e in mine] \
            == [0] + [1] * (len(mine) - 1)
    elif name == tracing.TICK_DISPATCH:
        assert {e[3]["program"] for e in mine} == {"decode", "paged_prefill"}
        # a decode step says how many pages its kernel walks: at least
        # one a row, never more than the rows' whole tables
        for e in mine:
            if e[3]["program"] == "decode":
                rows = int(e[3]["rows"])
                assert rows <= int(e[3]["pages"]) \
                    <= rows * ENGINE.pages_per_slot
    if name == tracing.TICK_READBACK and "lag" in keys:
        # a step is read a tick after its dispatch, behind the next
        # step's; the run's last step has none behind it
        lags = [int(e[3]["lag"]) for e in mine]
        assert lags[-1] == 0 and set(lags[:-1]) == {1}
        steps = [e for e in events if e[0] == tracing.TICK_DISPATCH
                 and e[3]["program"] == "decode"]
        assert len(lags) == len(steps)
    if name == tracing.TICK_COMMIT and "dropped" in keys:
        assert sum(int(e[3]["dropped"]) for e in mine) == 0   # no EOS here
    elif name == tracing.TICK_COMMIT:
        # the spans count the tokens the requests got, and each
        # retirement once
        commits = [e[3] for e in events if e[0] == name]
        assert (sum(c.get("tokens", 0) for c in commits)
                == sum(len(r.tokens) for r in results.values()))
        assert sum(c.get("retired", 0) for c in commits) == len(results)


def test_a_prefill_span_links_the_tick_to_the_request(traced_ticks):
    """A request's prefill is two ``tick.prefill`` spans of one tick:
    the one that dispatches its program (``bucket``), and the one that
    reads and commits its first token, after the tick's decode step went
    out. Between them they hold every leaf."""
    requests, _, events = traced_ticks
    prefills = [e for e in events if e[0] == tracing.TICK_PREFILL]
    assert ({e[3]["trace_id"] for e in prefills}
            == {r.trace_id for r in requests})
    by_id = {r.trace_id: r for r in requests}
    for e in prefills:
        assert e[3]["prompt_tokens"] == by_id[e[3]["trace_id"]].prompt_len
    for r in requests:
        sent, read = [e for e in prefills if e[3]["trace_id"] == r.trace_id]
        assert sent[3]["bucket"] >= sent[3]["prompt_tokens"]
        assert "bucket" not in read[3] and sent[2] <= read[1]
        (tick,) = [t for t in events if t[0] == tracing.TICK_ENGINE
                   and t[1] <= sent[1] and read[2] <= t[2]]
        (step,) = [d for d in _inside(events, tick)
                   if d[0] == tracing.TICK_DISPATCH
                   and d[3]["program"] == "decode"]
        assert sent[2] <= step[1] and step[2] <= read[1]
        inner = [x[0] for x in _inside(events, sent) + _inside(events, read)]
        for leaf in TICK_LEAF_SPANS:
            assert leaf in inner


def test_an_admission_tick_carries_its_first_tokens(traced_ticks):
    """An admission tick's leaves stay disjoint and in order; its decode
    step carries a row for each prefill it dispatched (``carried``) and
    goes out before the first of their reads."""
    requests, _, events = traced_ticks
    admitting = 0
    for tick in (e for e in events if e[0] == tracing.TICK_ENGINE):
        inner = _inside(events, tick)
        sent = [e for e in inner
                if e[0] == tracing.TICK_PREFILL and "tokens" in e[3]]
        if not sent:
            continue
        admitting += 1
        leaves = [e for e in inner if e[0] in TICK_LEAF_SPANS]
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1], (a, b)
        (step,) = [e for e in inner if e[0] == tracing.TICK_DISPATCH
                   and e[3]["program"] == "decode"]
        assert int(step[3]["carried"]) == len(sent)
        reads = [e for e in inner if e[0] == tracing.TICK_READBACK
                 and "lag" not in e[3]]
        assert len(reads) == len(sent)
        assert all(step[2] <= r[1] for r in reads)
    assert admitting >= 1
    carried = [int(e[3]["carried"]) for e in events
               if e[0] == tracing.TICK_DISPATCH
               and e[3]["program"] == "decode"]
    assert sum(carried) == len(requests)


@pytest.mark.parametrize("kind", ["monolithic", "prefix_hit", "chunked"])
def test_a_prefill_span_counts_the_tokens_its_program_computed(
        small, tmp_path, kind):
    """``tokens`` is what the program dispatched under the span computed:
    the prompt, a prefix hit's suffix, or the chunk. Never more than the
    ``bucket`` it was padded to; a request's spans add up to the tokens it
    prefilled. A span under which no program went out (a chunk with no
    room left in the tick's budget) carries neither."""
    model, params = small
    config = ENGINE if kind != "chunked" else dataclasses.replace(
        ENGINE, prefill_token_budget=ENGINE.page_size)
    engine = InferenceEngine(model, params, config)
    rng = np.random.RandomState(7)
    if kind == "prefix_hit":
        # an earlier request interns two full pages; the first traced
        # prompt prefills its 5-token suffix after them, the second is
        # those two pages whole and recomputes its last token only
        shared = rng.randint(0, 64, size=2 * ENGINE.page_size).tolist()
        engine.serve([Request(prompt=shared + [1, 2, 3], max_new_tokens=2)])
        prompts = [shared + rng.randint(0, 64, size=5).tolist(), shared]
        prefilled = [5, 1]
    else:
        prompts = [rng.randint(0, 64, size=n).tolist() for n in (5, 20, 27)]
        prefilled = [5, 20, 27]
    requests = [Request(prompt=p, max_new_tokens=3) for p in prompts]

    _, events = _traced(lambda: engine.serve(requests), tmp_path)
    engine.close()
    spans = [e[3] for e in events if e[0] == tracing.TICK_PREFILL]
    assert all(("tokens" in st) == ("bucket" in st) for st in spans)
    tokens, count = {}, {}
    for st in (st for st in spans if "bucket" in st):
        assert 1 <= int(st["tokens"]) <= int(st["bucket"]), st
        tokens[st["trace_id"]] = tokens.get(st["trace_id"], 0) \
            + int(st["tokens"])
        count[st["trace_id"]] = count.get(st["trace_id"], 0) + 1
    assert tokens == {r.trace_id: n for r, n in zip(requests, prefilled)}
    if kind == "chunked":
        # the 20- and 27-token prompts take several chunks of 8 or less
        assert sorted(count.values())[-2:] >= [3, 4]
    else:
        assert set(count.values()) == {1}


def test_supervisor_tick_encloses_the_engine_tick(small, tmp_path):
    model, params = small
    sup = EngineSupervisor(model, params, ENGINE)
    sup.serve(_requests(seed=5))

    def go():
        for r in _requests():
            sup.submit(r)
        for _ in range(3):
            sup.tick()

    _, events = _traced(go, tmp_path)
    sup.close()
    outer = [e for e in events if e[0] == tracing.TICK_SUPERVISOR]
    assert len(outer) == 3
    for o in outer:
        inner = _inside(events, o)
        engine = [e for e in inner if e[0] == tracing.TICK_ENGINE]
        assert len(engine) == 1
        leaves = [e for e in inner if e[0] in TICK_LEAF_SPANS]
        # the supervisor's own schedule span before the engine's tick, its
        # harvest under commit after it
        assert leaves[0][0] == tracing.TICK_SCHEDULE
        assert leaves[0][2] <= engine[0][1]
        assert leaves[-1][0] == tracing.TICK_COMMIT
        assert leaves[-1][1] >= engine[0][2]
        assert "retired" in leaves[-1][3]


class _Recording(MetricsRegistry):
    """Every write, by kind and name."""

    def __init__(self):
        super().__init__()
        self.writes = []

    def inc(self, name, *a, **k):
        self.writes.append(("inc", name))
        return super().inc(name, *a, **k)

    def observe(self, name, *a, **k):
        self.writes.append(("observe", name))
        return super().observe(name, *a, **k)

    def set_gauge(self, name, *a, **k):
        self.writes.append(("set_gauge", name))
        return super().set_gauge(name, *a, **k)


def test_a_tick_writes_nothing_new_into_the_registry(small):
    """What a decode tick wrote before the spans existed is what it
    writes now, and one counter for the step that went out behind
    another: no ``span/*`` histogram, no per-span counter."""
    model, params = small
    reg = _Recording()
    engine = InferenceEngine(model, params, ENGINE, metrics=reg)
    engine.submit(_requests()[0])
    engine.tick()                       # prefill, and step 0 goes out
    reg.writes.clear()
    engine.tick()                       # step 1 goes out, step 0 is read
    writes = sorted(reg.writes)
    engine.close()
    assert writes == sorted([
        ("set_gauge", "kv_bytes_per_step"), ("inc", "decode_steps"),
        ("inc", "decode_steps_overlapped"),
        ("observe", "decode_batch_size"), ("inc", "tokens_generated"),
        ("observe", "slot_occupancy"), ("set_gauge", "kv_pages_in_use"),
        ("set_gauge", "kv_pages_free"), ("observe", "kv_page_occupancy")])
    assert not [h for h in reg.histograms() if h.startswith("span/")]


def test_token_streams_are_the_same_with_the_profiler_on(small,
                                                         traced_ticks):
    model, params = small
    _, traced, _ = traced_ticks
    engine = InferenceEngine(model, params, ENGINE)
    plain = engine.serve(_requests())
    engine.close()
    # request ids differ between the two runs; the order of submission
    # does not
    assert ([r.tokens for r in plain]
            == [traced[i].tokens for i in sorted(traced)])
    assert any(len(set(r.tokens)) > 1 for r in plain)


# -- scope names inside the step programs ---------------------------------------

@pytest.fixture(scope="module")
def program_texts(small):
    """Compiled text of a tiny decode program, prefill program and
    sharded train step (data 2 x tensor 2 on four virtual devices), with
    the Pallas kernels interpreted so that their scopes are on the path."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import make_resilient_train_step
    from apex_tpu.transformer import parallel_state

    old = os.environ.get("APEX_TPU_FORCE_PALLAS")
    os.environ["APEX_TPU_FORCE_PALLAS"] = "interpret"
    _support.pallas_mode.cache_clear()
    try:
        model, params = small
        engine = InferenceEngine(model, params, ENGINE)
        texts = {"decode": engine.decode_program_text()}
        padded = np.zeros((1, 8), np.int32)
        texts["prefill"] = engine._prefill_fn._fn.lower(
            engine._params, engine._caches,
            jnp.asarray(engine._page_table_h[0]), jnp.asarray(padded),
            jnp.int32(5), jnp.float32(0.0), jnp.int32(64), jnp.int32(0),
            jnp.asarray([0], jnp.int32), engine._bank,
            *engine._carry_args(0)).compile().as_text()
        engine.close()

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:4])
        tp_model = GPTModel(CONFIG)
        amp_state = amp.initialize("O2")
        opt = FusedAdam(lr=1e-3, master_weights=True)

        def initial(key):
            return amp_state.policy.cast_to_param(tp_model.init(key))

        template = jax.eval_shape(initial, jax.random.PRNGKey(0))
        step = make_resilient_train_step(
            lambda p, b, rng: tp_model.apply(p, b["tokens"], b["labels"]),
            opt, amp_state.scaler, mesh=mesh, param_spec=tp_model.spec(),
            batch_spec={"tokens": P("data"), "labels": P("data")},
            params_template=template)
        state = jax.eval_shape(lambda k: {
            "params": initial(k), "opt_state": opt.init(initial(k)),
            "step": jnp.zeros((), jnp.int32),
            "scaler": amp_state.scaler.init()}, jax.random.PRNGKey(0))
        batch = {k: jax.ShapeDtypeStruct((4, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        texts["train"] = step.lower(state, batch, None).compile().as_text()
        parallel_state.destroy_model_parallel()
    finally:
        if old is None:
            os.environ.pop("APEX_TPU_FORCE_PALLAS", None)
        else:
            os.environ["APEX_TPU_FORCE_PALLAS"] = old
        _support.pallas_mode.cache_clear()
    return texts


def _has_scope(text, scope):
    """Some instruction's ``op_name`` holds ``scope`` as a path element,
    bare or under a transformation's wrapper (``jvp(mlp)``)."""
    return re.search(r'op_name="[^"]*[/(]%s[/)"]' % re.escape(scope), text)


@pytest.mark.parametrize("program,scope", [
    ("decode", tracing.SCOPE_SAMPLE),
    ("decode", tracing.SCOPE_PAGED_DECODE),
    ("decode", tracing.SCOPE_ATTENTION),
    ("decode", tracing.SCOPE_MLP),
    ("decode", tracing.SCOPE_LAYER_NORM),
    ("prefill", tracing.SCOPE_SAMPLE),
    ("prefill", tracing.SCOPE_FLASH_FWD),
    ("prefill", tracing.SCOPE_ATTENTION),
    ("prefill", tracing.SCOPE_MLP),
    ("prefill", tracing.SCOPE_LAYER_NORM),
    ("train", tracing.SCOPE_FLASH_FWD),
    ("train", tracing.SCOPE_FLASH_BWD),
    ("train", tracing.SCOPE_LAYER_NORM),
    ("train", tracing.SCOPE_ATTENTION),
    ("train", tracing.SCOPE_MLP),
    ("train", tracing.SCOPE_LM_HEAD_LOSS),
    ("train", tracing.SCOPE_OPTIMIZER),
    ("train", tracing.SCOPE_LOSS_SCALE),
    ("train", tracing.SCOPE_TP_ALL_REDUCE),
    ("train", tracing.SCOPE_DP_GRAD_ALL_REDUCE),
])
def test_compiled_programs_carry_the_scope_names(program_texts, program,
                                                 scope):
    assert _has_scope(program_texts[program], scope), (
        f"no op_name of the {program} program holds {scope!r}")


def test_step_bodies_keep_the_names_the_benchmark_finds_them_by(
        program_texts):
    """``module_dev_ms`` finds programs by these names."""
    assert "jit__paged_decode_body" in program_texts["decode"]
    assert "jit__paged_prefill_body" in program_texts["prefill"]
    assert ("jit_sharded" in program_texts["train"]
            or "jit_per_rank" in program_texts["train"])
