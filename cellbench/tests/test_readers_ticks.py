"""The reductions that split the serving tick into admission and plain
ticks (``cellbench/readers_ticks.py``): on hand-built tuples with known
answers, on the trace recorded on a v5e before ``tick.prefill`` carried
``tokens`` (``serve_spans``: the attributes are read, and every reduction
reads nothing), on the same trace with ``tokens`` put in, and on a tiny
engine traced on the CPU (what the program writes is what they read)."""

import glob
import gzip
import os
import shutil
import statistics

import jax
import numpy as np
import pytest

from cellbench import manifest, readers, readers_ticks as rt
from cellbench import trace_reduce as tr
from cellbench.tests import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PREFILL = "tick.prefill"
MODULE = "paged_prefill_body|suffix_prefill_body"
MINE = {"engine.admit_ms_per_tick.decode": "admit_excess_ms_per",
        "engine.admit_idle_ms_per_tick.decode": "admit_idle_ms_per",
        "step.prefill_dev_ms_per_tick.decode": "admit_dev_ms_per",
        "engine.prefill_pad_pct.decode": "padding_pct"}


def test_the_reductions_are_found_like_any_other():
    for name in MINE.values():
        assert readers.table()[name] is getattr(rt, name)


@pytest.mark.parametrize("cell_name", ["gpt2m.serve-decode",
                                       "trinitym.serve-decode-4k",
                                       "dotsvlm1.serve-decode-8k"])
def test_every_decode_cell_reads_the_four(cell_name):
    cell = manifest.cell(cell_name, tiny.REPO)
    got = {m.name: m for m in cell.per_layer if m.name in MINE}
    assert set(got) == set(MINE)
    for name, m in got.items():
        assert m.reader["reader"] == MINE[name]
        assert m.better == "lower" and m.moves == "serve_tok_per_s"
        assert m.reader["args"]["span"] == PREFILL
    assert got["step.prefill_dev_ms_per_tick.decode"].source \
        == "device_trace"
    assert got["step.prefill_dev_ms_per_tick.decode"].reader["args"][
        "module"] == MODULE
    assert not [m for m in manifest.cell("gpt2m.serve-prefill",
                                         tiny.REPO).per_layer
                if m.name in MINE]


# -- hand-built tuples ------------------------------------------------------------

class _Recorder:
    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.host = {}

    def traced_from(self, name):
        return 0


class _Context:
    """What ``readers.Context`` holds, as far as these reductions look."""

    def __init__(self, trace, trace_dir=None, stats=None):
        self.trace = trace
        self.t0, self.t1 = tr.window_of(trace.spans)
        self.recorder = _Recorder(trace_dir)
        if stats is not None:
            self._host_stats = {PREFILL: stats}

    def devices(self):
        return [self.trace.devices[k] for k in sorted(self.trace.devices)]


MS = 1e-3


def _ms(*pairs):
    return [(a * MS, (b - a) * MS) for a, b in pairs]


# five ticks: 2, 10 (admits), 1, 3 (a tick.prefill without tokens: a
# request put back in the queue), 5 (admits a chunk) ms
TICKS = _ms((0, 2), (3, 13), (14, 15), (16, 19), (20, 25))
PREFILLS = [(4 * MS, 6 * MS, {"trace_id": "a", "prompt_tokens": 100,
                              "bucket": 128, "tokens": 100}),
            (16.5 * MS, 0.2 * MS, {"trace_id": "b", "prompt_tokens": 40}),
            (20.5 * MS, 3.5 * MS, {"trace_id": "c", "prompt_tokens": 300,
                                   "bucket": 32, "tokens": 20,
                                   "chunk": 2})]
# every chip busy 0-2, 4-9 (a prefill), 11-12, 14-19, 21-25 ms: device 0's
# idle inside the admission ticks 1 + 2 + 1 (3-13) and 1 (20-25) = 5 ms
OPS = [("%op", s, d) for s, d in _ms((0, 2), (4, 9), (11, 12), (14, 19),
                                      (21, 25))]
MODULES = {0: _ms((4, 9), (21, 22.5)), 1: _ms((4, 8), (21, 22))}


def _trace(ticks=TICKS):
    devices = {}
    for chip, runs in MODULES.items():
        names = ["jit__paged_prefill_body", "jit__suffix_prefill_body"]
        devices[chip] = tr.Device(
            ops=OPS,
            modules=[(n, s, d) for n, (s, d) in zip(names, runs)]
            + [("jit__paged_decode_body", 14 * MS, 5 * MS)])
    spans = [("cb.tick", s, d) for s, d in ticks]
    return tr.Trace(devices=devices, spans=spans, host=[])


def _stats(prefills=PREFILLS):
    return [(PREFILL, s, d, dict(st)) for s, d, st in prefills]


def test_the_excess_of_admission_ticks_over_the_median_plain_tick():
    ctx = _Context(_trace(), stats=_stats())
    # plain 2, 1, 3 -> median 2; (10 - 2) + (5 - 2) over 5 ticks
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") == \
        pytest.approx(2.2, abs=1e-9)


def test_the_idle_inside_admission_ticks():
    ctx = _Context(_trace(), stats=_stats())
    assert rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick") == \
        pytest.approx(1.0, abs=1e-9)


def test_the_prefill_programs_device_time_mean_over_chips():
    ctx = _Context(_trace(), stats=_stats())
    # chip 0: 5 + 1.5 ms, chip 1: 4 + 1; the decode program is not counted
    assert rt.admit_dev_ms_per(ctx, MODULE, PREFILL, "cb.tick") == \
        pytest.approx((6.5 + 5.0) / 2 / 5, abs=1e-9)
    assert rt.admit_dev_ms_per(ctx, "no_such_program", PREFILL,
                               "cb.tick") is None


def test_the_padding_share_of_the_prefill_rows():
    ctx = _Context(_trace(), stats=_stats())
    assert rt.padding_pct(ctx, PREFILL) == \
        pytest.approx(100.0 * (1.0 - 120 / 160), abs=1e-9)


def test_the_mean_tick_is_the_median_plain_tick_plus_the_excess():
    """Exactly so where the plain ticks' mean is their median."""
    ctx = _Context(_trace(), stats=_stats())
    mean = statistics.mean(d for _, d in TICKS) / MS
    plain = statistics.median([2.0, 1.0, 3.0])
    assert plain + rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") == \
        pytest.approx(mean, abs=1e-9)


def test_spans_without_tokens_read_as_nothing():
    """The parent's program: the same spans, no ``tokens``."""
    bare = [(s, d, {k: v for k, v in st.items() if k != "tokens"})
            for s, d, st in PREFILLS]
    ctx = _Context(_trace(), stats=_stats(bare))
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_dev_ms_per(ctx, MODULE, PREFILL, "cb.tick") is None
    assert rt.padding_pct(ctx, PREFILL) is None


def test_a_window_that_admitted_nothing_reads_as_nothing():
    ctx = _Context(_trace(), stats=[])
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_dev_ms_per(ctx, MODULE, PREFILL, "cb.tick") is None
    assert rt.padding_pct(ctx, PREFILL) is None
    # and a run traced without a file has no attributes to read
    ctx = _Context(_trace())
    assert rt.padding_pct(ctx, PREFILL) is None


def test_a_prefill_outside_the_window_is_not_counted():
    late = PREFILLS + [(30 * MS, 1 * MS, {"bucket": 64, "tokens": 1})]
    ctx = _Context(_trace(), stats=_stats(late))
    assert rt.padding_pct(ctx, PREFILL) == \
        pytest.approx(25.0, abs=1e-9)


def test_with_every_tick_admitting_there_is_no_plain_tick_to_exceed():
    ticks = [TICKS[1], TICKS[4]]
    ctx = _Context(_trace(ticks), stats=_stats())
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick") == \
        pytest.approx(5.0 / 2, abs=1e-9)


# -- the trace recorded on a v5e ---------------------------------------------------

@pytest.fixture(scope="module")
def spanned(tmp_path_factory):
    """The same 2-layer model at GPT-2 medium's width, 8 slots, 14
    supervisor ticks under ``cb.tick``; four prefills ride the first."""
    path = tmp_path_factory.mktemp("spans") / "serve_spans.xplane.pb"
    with gzip.open(os.path.join(DATA, "serve_spans.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_the_recorded_attributes_are_read(spanned):
    events = rt.host_stats(spanned, {PREFILL})
    assert [e[0] for e in events] == [PREFILL] * 4
    assert [e[3]["prompt_tokens"] for e in events] == [100, 130, 160, 190]
    assert [e[3]["bucket"] for e in events] == [128, 256, 256, 256]
    assert all(len(e[3]["trace_id"]) == 16 for e in events)
    assert all("tokens" not in e[3] for e in events)


def test_the_recorded_trace_predates_tokens(spanned):
    ctx = _Context(tr.load(spanned), trace_dir=os.path.dirname(spanned))
    assert rt.padding_pct(ctx, PREFILL) is None
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick") is None
    assert rt.admit_dev_ms_per(ctx, MODULE, PREFILL, "cb.tick") is None
    assert ctx._host_stats[PREFILL]       # read once, kept on the context


def test_the_recorded_trace_with_tokens_put_in(spanned):
    """Each prompt prefilled whole: ``tokens`` is ``prompt_tokens``."""
    stats = [(n, s, d, dict(st, tokens=st["prompt_tokens"]))
             for n, s, d, st in rt.host_stats(spanned, {PREFILL})]
    ctx = _Context(tr.load(spanned), stats=stats)
    ticks = [(s, d) for n, s, d in ctx.trace.spans if n == "cb.tick"]
    assert len(ticks) == 14
    first, rest = ticks[0], [d for _, d in ticks[1:]]
    base = statistics.median(rest)
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") == \
        pytest.approx((first[1] - base) * 1e3 / 14, abs=1e-9)
    busy = tr.busy_seconds(ctx.devices()[0].ops, first[0],
                           first[0] + first[1])
    idle = rt.admit_idle_ms_per(ctx, PREFILL, "cb.tick")
    assert idle == pytest.approx((first[1] - busy) * 1e3 / 14, abs=1e-9)
    runs = tr.module_runs(ctx.devices()[0].modules, MODULE, ctx.t0, ctx.t1)
    assert len(runs) == 4
    dev = rt.admit_dev_ms_per(ctx, MODULE, PREFILL, "cb.tick")
    assert dev == pytest.approx(sum(d for _, _, d in runs) * 1e3 / 14,
                                abs=1e-9)
    assert rt.padding_pct(ctx, PREFILL) == \
        pytest.approx(100.0 * (1.0 - 580 / 896), abs=1e-9)
    # the prefills and the chip's idle inside their tick are most of it
    excess = rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick")
    assert 0.0 < dev + idle <= excess * 1.05


# -- what the program writes -----------------------------------------------------

def test_the_reductions_read_what_a_tiny_engine_writes(tmp_path):
    """A tiny engine, chunked, ticked under ``cb.tick`` on the CPU: the
    attributes reach the reductions (the CPU trace has no device plane,
    so the two device readings are the chip's alone)."""
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.serving import EngineConfig, InferenceEngine, Request

    config = TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0)
    model = GPTModel(config)
    engine = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(max_slots=2, max_len=64, page_size=8,
                     prefill_token_budget=16))
    rng = np.random.RandomState(0)
    lengths = (5, 40, 9)
    for n in lengths:
        engine.submit(Request(prompt=rng.randint(0, 64, size=n).tolist(),
                              max_new_tokens=4))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while engine.active_count or engine.queued_count \
                or engine._prefilling or engine._flight is not None:
            with jax.profiler.TraceAnnotation("cb.tick"):
                engine.tick()
    finally:
        jax.profiler.stop_trace()
        engine.close()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    ctx = _Context(tr.load(path), trace_dir=str(tmp_path))
    spans = [st for _, _, st in rt._attributed(ctx, PREFILL)]
    assert sum(int(st["tokens"]) for st in spans) == sum(lengths)
    assert all(int(st["tokens"]) <= int(st["bucket"]) for st in spans)
    assert rt.padding_pct(ctx, PREFILL) == pytest.approx(
        100.0 * (1.0 - sum(lengths) / sum(int(st["bucket"])
                                          for st in spans)), abs=1e-9)
    assert rt.admit_excess_ms_per(ctx, PREFILL, "cb.tick") is not None
