"""The reduction from a trace to numbers: on made-up intervals, and on a
small xplane recorded on a v5e (2-layer model at GPT-2 medium's width, 8
slots, 14 engine ticks under ``cb.tick`` spans)."""

import gzip
import os
import shutil

import pytest

from cellbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_union_and_gaps():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)]
    assert tr.busy_seconds(ev, 0.0, 5.0) == pytest.approx(2.5)
    assert tr.busy_seconds(ev, 1.0, 3.5) == pytest.approx(1.0)
    assert tr.gaps(ev, 0.0, 5.0) == [(1.5, 3.0), (4.0, 5.0)]
    assert tr.gaps([], 0.0, 2.0) == [(0.0, 2.0)]


def test_innermost_segments_name_the_deepest_event():
    ev = [("outer", 0.0, 10.0), ("mid", 2.0, 4.0), ("leaf", 3.0, 1.0)]
    assert tr.innermost_segments(ev) == [
        (0.0, 2.0, "outer"), (2.0, 3.0, "mid"), (3.0, 4.0, "leaf"),
        (4.0, 6.0, "mid"), (6.0, 10.0, "outer")]


def test_gap_attribution_by_enclosing_span_and_runtime_event():
    spans = [("cb.tick", 0.0, 4.0), ("cb.tick", 5.0, 2.0)]
    host = [("ToLiteral", 1.0, 2.0)]
    idle = [(0.5, 3.5), (4.2, 4.8), (5.0, 6.0)]
    got = dict(tr.attribute_gaps(idle, spans, host))
    assert got["cb.tick>ToLiteral"] == pytest.approx(2.0)
    assert got["cb.tick"] == pytest.approx(0.5 + 0.5 + 1.0)
    assert got["(outside_spans)"] == pytest.approx(0.6)


def test_short_names_fold_layers_together():
    a = "%fusion.12 = bf16[96,1024]{1,0:T(8,128)(2,1)} fusion(bf16[96,3072] %x)"
    b = "%fusion.99 = bf16[96,1024]{1,0} fusion(bf16[96,3072] %y)"
    assert tr.short_name(a) == tr.short_name(b) == "fusion bf16[96,1024]"
    c = "%sort.3 = (f32[96,50304]{1,0}, s32[96,50304]{1,0}) sort(f32[96,50304] %l)"
    assert tr.short_name(c) == "sort f32[96,50304]"


def test_collective_seconds_reads_the_cores_own_timeline():
    ops = [("%all-reduce.3 = bf16[8] all-reduce(bf16[8] %x)", 0.0, 0.25),
           ("%fusion.1 = bf16[8] fusion(bf16[8] %x)", 0.25, 1.0),
           ("%all-gather-done.1 = bf16[8] all-gather-done(...)", 1.25, 0.5),
           ("%psum.7 = bf16[8192,1280] all-reduce(bf16[8192,1280] %y)", 1.75,
            0.125)]
    assert tr.collective_seconds(ops, 0.0, 2.0) == pytest.approx(0.875)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "serve.xplane.pb"
    with gzip.open(os.path.join(DATA, "serve_probe.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(str(path))


def test_recorded_trace_has_one_chip_and_the_harness_spans(recorded):
    assert list(recorded.devices) == [0]
    names = {n for n, _, _ in recorded.spans}
    assert names == {"cb.submit", "cb.tick"}
    assert sum(n == "cb.tick" for n, _, _ in recorded.spans) == 14


def test_recorded_trace_busy_idle_and_attribution(recorded):
    t0, t1 = tr.window_of(recorded.spans)
    dev = recorded.devices[0]
    busy = tr.busy_seconds(dev.ops, t0, t1)
    idle = tr.gaps(dev.ops, t0, t1)
    assert 0.0 < busy < t1 - t0
    assert busy + sum(b - a for a, b in idle) == pytest.approx(t1 - t0)
    top = tr.attribute_gaps(idle, recorded.spans, recorded.host)
    assert len(top) <= 10 and top[0][0].startswith("cb.tick")
    assert sum(v for _, v in top) <= sum(b - a for a, b in idle) + 1e-9
    ops = tr.top_ops(dev.ops, t0, t1)
    assert ops[0][0].startswith("sort_f32[8,50304]")   # the vocabulary sort


def test_recorded_trace_scope_matching(recorded):
    dev = recorded.devices[0]
    t0, t1 = tr.window_of(recorded.spans)
    runs = tr.module_runs(dev.modules, "paged_decode_body", t0, t1)
    assert len(runs) == 7
    decode = tr.in_modules(dev.ops, dev.modules, "paged_decode_body")
    pools = [r"custom-call\(.*bf16\[128,64,1024\].*bf16\[128,64,1024\]"
             r".*tpu_custom_call", r"^%\S+ = bf16\[128,64,1024\]"]
    hit = tr.matching(decode, pools)
    # 7 ticks x 2 layers x (K append, V append, the kernel)
    assert len(hit) == 42
    assert tr.matching(decode, [r"no_such_op"]) == []


# -- the readers, on the recorded trace ----------------------------------------

class _Recorder:
    """The host-side record a run would hold for the recorded trace."""

    def __init__(self, spans):
        self.host = {}
        for n, s, d in spans:
            self.host.setdefault(n, []).append((s, d))

    def traced_from(self, name):
        return 0


def _context(recorded, facts):
    import json

    from cellbench import manifest, readers, work
    from cellbench.tests import tiny

    cell = manifest.cell("gpt2m.serve-prefill", tiny.REPO)
    sz = {"L": 2, "h": 1024, "heads": 16, "V": 50304}
    base = {"sz": sz, "recorder": _Recorder(recorded.spans),
            "shapes": {"heads": 16, "dh": 64, "h": 1024, "n_pages": 128,
                       "page_size": 64, "slots": 8}}
    ctx = readers.Context(cell, {"facts": dict(base, **facts)}, recorded,
                          work.peaks("TPU v5 lite"))
    return cell, ctx


def test_every_reader_of_a_serving_cell_reads_the_recorded_trace(recorded):
    from cellbench import readers, work

    sz = {"L": 2, "h": 1024, "heads": 16, "V": 50304}
    prompts = [100, 130, 160, 190]
    cell, ctx = _context(recorded, {
        "max_slots": 8, "n_pages": 128, "occupancy": [4] * 14,
        "kv_pages": [14] * 14, "lag_s": [0.001, 0.002], "queue_s": [0.01],
        "prefill_tokens_traced": prompts,
        "serve_flops_traced": work.serve_flops(sz, 580 + 28, 4000.0),
        "paged_decode_work": (0.0, work.paged_decode_bytes(sz, 4000, 28)),
        "flash_prefill_work": work.flash_prefill_work(sz, prompts)})
    got = {m.name: readers.read(ctx, m) for m in cell.per_layer}
    assert all(v is not None for v in got.values()), got
    assert got["engine.occupancy_pct.prefill"] == pytest.approx(50.0)
    assert got["kv.mapped_pct.prefill"] == pytest.approx(100 * 14 / 128)
    assert 0 < got["device.idle_pct.prefill"] < 100
    assert 0 < got["step.decode_dev_ms.prefill"] < 5
    for name in ("kernel.paged_decode_roofline_pct.prefill",
                 "kernel.flash_roofline_pct.prefill", "step.mfu_pct.prefill"):
        assert 0 < got[name] < 100, (name, got[name])
    assert readers.device_busy_s(ctx) > 0


def test_a_pattern_that_matches_nothing_reads_nothing_not_zero(recorded):
    from cellbench import readers

    _, ctx = _context(recorded, {"paged_decode_work": (0.0, 1e6)})
    assert readers.roofline_pct(ctx, "paged_decode_work",
                                [r"bf16\[{n_pages},7,{h}\]"],
                                module="paged_decode_body") is None
    assert readers.module_dev_ms(ctx, "no_such_program") is None
