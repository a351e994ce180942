"""The reductions over the program's own spans and scopes
(``cellbench/readers_program.py``): on made-up bytes and intervals, on the
trace recorded before the program had either (``serve_probe``: nothing to
read, and no error), and on one recorded on a v5e with both
(``serve_spans``: the same 2-layer model at GPT-2 medium's width, 8 slots,
14 supervisor ticks under ``cb.tick``)."""

import gzip
import os
import shutil

import pytest

from cellbench import readers, readers_program as rp, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LEAVES = ["tick.schedule", "tick.upload", "tick.dispatch", "tick.readback",
          "tick.commit"]


def test_the_reductions_are_found_like_any_other():
    for name in ("span_idle_ms_per", "unspanned_idle_ms_per",
                 "scope_dev_ms_per"):
        assert readers.REDUCTIONS[name] is getattr(rp, name)


def test_the_proposed_entries_load_as_manifest_entries(tmp_path):
    """``per_layer_proposed.json`` is what a benchmark PR appends to
    ``BENCHMARK.json``'s ``per_layer``: the loader takes it as it stands,
    and ``cell`` adds to a cell just the entries that list it."""
    import json

    from cellbench import manifest
    from cellbench.tests import tiny

    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    with open(os.path.join(os.path.dirname(rp.__file__), rp.PROPOSED)) as f:
        proposed = json.load(f)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        dict(man, per_layer=man["per_layer"] + proposed)))
    loaded = manifest.load(str(tmp_path))
    assert len(loaded["per_layer"]) == len(man["per_layer"]) + 15
    for name, more in (("gpt2m.serve-decode", 7), ("gpt2m.serve-prefill", 7),
                       ("gpt2m.train-1k", 1)):
        before = manifest.cell(name, tiny.REPO).per_layer
        after = rp.cell(name, tiny.REPO).per_layer
        assert [m.name for m in after[:len(before)]] == \
            [m.name for m in before]
        assert len(after) == len(before) + more
        assert all(m.reader["reader"] in readers.REDUCTIONS for m in after)


# -- the wire format ------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_fields_walks_varints_bytes_and_skips_fixed_width():
    msg = (_field(1, 300) + _field(2, b"name")
           + _varint(3 << 3 | 1) + b"\0" * 8       # a double
           + _varint(4 << 3 | 5) + b"\0" * 4       # a float
           + _field(5, _field(1, 7)))
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in rp._fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"name"), (5, _field(1, 7))]


def test_op_names_reads_the_tf_op_stat_of_device_planes(tmp_path):
    def plane(name, events):
        stat_meta = _field(5, _field(1, 9) + _field(2, _field(
            1, 9) + _field(2, b"tf_op")))
        other = _field(5, _field(1, 4) + _field(2, _field(
            1, 4) + _field(2, b"source")))
        body = _field(2, name) + stat_meta + other
        for i, (event, op) in enumerate(events):
            meta = _field(1, i) + _field(2, event)
            meta += _field(5, _field(1, 4) + _field(5, b"engine.py:1"))
            if op is not None:
                meta += _field(5, _field(1, 9) + _field(5, op))
            body += _field(4, _field(1, i) + _field(2, meta))
        return _field(1, body)

    space = (plane(b"/device:TPU:1", [(b"%sort.5 = f32[8]", b"jit(f)/sample/sort:"),
                                      (b"%copy.1 = f32[8]", None)])
             + plane(b"/host:CPU", [(b"%sort.5 = f32[8]", b"ignored")]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert rp.op_names(str(path)) == {
        1: {"%sort.5 = f32[8]": ["jit(f)/sample/sort"]}}


@pytest.mark.parametrize("op_name,scope,hit", [
    ("jit(_paged_decode_body)/sample/sort", "sample", True),
    ("jit(sharded)/transpose(jvp(mlp))/dot_general", "mlp", True),
    ("jit(sharded)/jvp(attention)/flash_attention_fwd/pallas_call",
     "attention", True),
    ("jit(sharded)/jvp(attention)/flash_attention_fwd/pallas_call",
     "flash_attention_fwd", True),
    ("jit(_paged_decode_body)/vmap()/_sample_tokens.<locals>.draw/add",
     "sample", False),
    ("jit(sharded)/optimizer/mul", "loss_scale", False),
    ("jit(sample_more)/add", "sample", False),
])
def test_in_scope_matches_whole_path_elements(op_name, scope, hit):
    assert rp.in_scope(op_name, {scope}) is hit


def test_overlap_of_sorted_pieces():
    pieces = [(0.0, 1.0), (2.0, 4.0), (5.0, 6.0)]
    assert rp._overlap(pieces, [(0.5, 2.5), (3.0, 5.5)]) == pytest.approx(
        0.5 + 0.5 + 1.0 + 0.5)
    assert rp._overlap(pieces, []) == 0.0
    assert rp._overlap([], [(0.0, 1.0)]) == 0.0


# -- recorded traces ------------------------------------------------------------

class _Recorder:
    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.host = {}

    def traced_from(self, name):
        return 0


class _Context:
    """What ``readers.Context`` holds, as far as these reductions look."""

    def __init__(self, path):
        self.trace = tr.load(path)
        self.t0, self.t1 = tr.window_of(self.trace.spans)
        self.recorder = _Recorder(os.path.dirname(path))

    def devices(self):
        return [self.trace.devices[k] for k in sorted(self.trace.devices)]


def _unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return _Context(str(path))


@pytest.fixture(scope="module")
def before(tmp_path_factory):
    return _unpacked(tmp_path_factory, "serve_probe")


@pytest.fixture(scope="module")
def spanned(tmp_path_factory):
    return _unpacked(tmp_path_factory, "serve_spans")


def test_a_program_without_spans_or_scopes_reads_as_nothing(before):
    """The parent of the PR that added them: every new metric is left out
    of the line, and nothing raises."""
    for leaf in LEAVES:
        assert rp.span_idle_ms_per(before, leaf, "cb.tick") is None
    assert rp.unspanned_idle_ms_per(before, LEAVES, "cb.tick") is None
    assert rp.scope_dev_ms_per(before, "sample", "paged_decode_body",
                               "cb.tick") is None
    # the file holds op names all the same: those of the unscoped program
    names = rp.op_names(tr.newest_xplane(before.recorder.trace_dir))
    assert any("_sample_tokens" in op for ops in names[0].values()
               for op in ops)


def test_a_run_traced_without_a_file_reads_no_scope(spanned):
    ctx = _Context.__new__(_Context)
    ctx.trace, ctx.t0, ctx.t1 = spanned.trace, spanned.t0, spanned.t1
    ctx.recorder = _Recorder(None)
    assert rp.scope_dev_ms_per(ctx, "sample", "paged_decode_body",
                               "cb.tick") is None


def test_the_six_idle_parts_add_up_to_the_idle_inside_the_ticks(spanned):
    parts = {leaf: rp.span_idle_ms_per(spanned, leaf, "cb.tick")
             for leaf in LEAVES}
    assert all(v is not None and v >= 0.0 for v in parts.values()), parts
    rest = rp.unspanned_idle_ms_per(spanned, LEAVES, "cb.tick")
    ticks = [(s, s + d) for n, s, d in spanned.trace.spans if n == "cb.tick"]
    assert len(ticks) == 14
    idle = tr.gaps(spanned.devices()[0].ops, spanned.t0, spanned.t1)
    inside = rp._overlap(idle, ticks)
    total = (sum(parts.values()) + rest) * 1e-3 * len(ticks)
    assert total == pytest.approx(inside, rel=0.02)
    # nearly all of it has a name: Python between the spans is the rest
    assert 0.0 <= rest < 0.1 * (sum(parts.values()) + rest)
    # the chip waits longest while the host reads the tokens back
    assert max(parts, key=parts.get) == "tick.readback"


def test_the_gap_ranking_names_the_steps_of_the_tick(spanned):
    idle = tr.gaps(spanned.devices()[0].ops, spanned.t0, spanned.t1)
    top = [k for k, _ in tr.attribute_gaps(idle, spanned.trace.spans,
                                           spanned.trace.host)]
    assert "cb.tick" not in top[:3]
    assert any(k.startswith("cb.tick>tick.") for k in top)


def test_sampling_is_found_by_its_scope_and_holds_the_sort(spanned):
    got = rp.scope_dev_ms_per(spanned, "sample", "paged_decode_body",
                              "cb.tick")
    dev = spanned.devices()[0]
    decode = tr.in_modules(dev.ops, dev.modules, "paged_decode_body")
    sort_s = sum(d for n, _, d in decode if n.startswith("%sort"))
    whole_s = sum(d for _, _, d in tr.module_runs(
        dev.modules, "paged_decode_body", spanned.t0, spanned.t1))
    assert sort_s > 0.0
    assert sort_s * 1e3 / 14 <= got < whole_s * 1e3 / 14
    assert rp.scope_dev_ms_per(spanned, "no_such_scope",
                               "paged_decode_body", "cb.tick") is None
    assert rp.scope_dev_ms_per(spanned, "sample", "no_such_program",
                               "cb.tick") is None


def test_scopes_split_a_program_and_lists_add_up(spanned):
    def read(scope, module="paged_decode_body"):
        return rp.scope_dev_ms_per(spanned, scope, module, "cb.tick")

    attention, mlp = read("attention"), read("mlp")
    kernel = read("paged_decode_attention")
    assert 0.0 < kernel <= attention      # the kernel runs inside attention
    assert read(["attention", "mlp"]) == pytest.approx(attention + mlp)
    # the prefill programs have their own sampling and flash kernels
    assert read("sample", "paged_prefill_body") > 0.0
    assert read("flash_attention_fwd", "paged_prefill_body") > 0.0
    assert read("flash_attention_fwd") is None


def test_every_new_serving_metric_reads_the_recorded_trace(spanned):
    from cellbench.tests import tiny

    for cell_name, kind in (("gpt2m.serve-decode", "decode"),
                            ("gpt2m.serve-prefill", "prefill")):
        cell = rp.cell(cell_name, tiny.REPO)
        mine = [m for m in cell.per_layer
                if m.name.startswith(("engine.idle_ms_per_tick.",
                                      "step.sample_dev_ms."))]
        assert len(mine) == 7 and all(m.name.endswith(kind) for m in mine)
        for m in mine:
            assert m.better == "lower" and m.unit == "ms"
            assert readers.read(spanned, m) is not None, m.name
            assert "sort" not in str(m.reader)   # by scope, not by shape


def test_every_reader_of_a_serving_cell_reads_the_spanned_trace(spanned):
    """What ``test_trace_reduce`` asks of the trace recorded before the
    spans, asked of the one recorded with them: every per-layer metric of
    a serving cell, old and new, finds something to read."""
    from cellbench import work
    from cellbench.tests import tiny
    from cellbench.tests.test_trace_reduce import _context

    sz = {"L": 2, "h": 1024, "heads": 16, "V": 50304}
    prompts = [100, 130, 160, 190]
    _, ctx = _context(spanned.trace, {
        "max_slots": 8, "n_pages": 128, "occupancy": [4] * 14,
        "kv_pages": [14] * 14, "lag_s": [0.001, 0.002], "queue_s": [0.01],
        "prefill_tokens_traced": prompts,
        "serve_flops_traced": work.serve_flops(sz, 580 + 28, 4000.0),
        "paged_decode_work": (0.0, work.paged_decode_bytes(sz, 4000, 28)),
        "flash_prefill_work": work.flash_prefill_work(sz, prompts)})
    ctx.recorder.trace_dir = spanned.recorder.trace_dir
    cell = rp.cell("gpt2m.serve-prefill", tiny.REPO)
    got = {m.name: readers.read(ctx, m) for m in cell.per_layer}
    assert len(got) == 16
    assert all(v is not None for v in got.values()), got
    # the shape patterns still find the renamed kernels
    for name in ("kernel.paged_decode_roofline_pct.prefill",
                 "kernel.flash_roofline_pct.prefill"):
        assert 0.0 < got[name] < 100.0


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Three steps of the same 2-layer model under ``cb.step``, amp-O2
    and FusedAdam through ``make_resilient_train_step``, on one v5e."""
    return _unpacked(tmp_path_factory, "train_scopes")


def test_the_optimizer_metric_reads_its_two_scopes(trained):
    from cellbench.tests import tiny

    cell = rp.cell("gpt2m.train-1k", tiny.REPO)
    metric = next(m for m in cell.per_layer
                  if m.name == "step.optimizer_dev_ms.train")
    args = metric.reader["args"]
    got = readers.read(trained, metric)
    optimizer = rp.scope_dev_ms_per(trained, "optimizer", args["module"],
                                    "cb.step")
    loss_scale = rp.scope_dev_ms_per(trained, "loss_scale", args["module"],
                                     "cb.step")
    assert got == pytest.approx(optimizer + loss_scale)
    assert 0.0 < loss_scale < optimizer
    step_ms = [d * 1e3 for _, _, d in tr.module_runs(
        trained.devices()[0].modules, args["module"], trained.t0,
        trained.t1)]
    assert len(step_ms) == 3 and got < min(step_ms)
    # forward and backward of a layer's parts read under one scope each
    for scope in ("attention", "mlp", "layer_norm", "lm_head_loss",
                  "flash_attention_fwd", "flash_attention_bwd"):
        assert rp.scope_dev_ms_per(trained, scope, args["module"],
                                   "cb.step") > 0.0
