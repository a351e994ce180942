"""Chip smoke: the main path, once, on the real accelerator.

    python chip_smoke.py          # from the checkout root, ON THE CHIP

One process (it holds the chip; nothing is forked), one model at full
width — GPT-2 124M: 12 layers, hidden 768, 12 heads — through the entry
points a user calls, in this order:

1. device gate: no TPU => non-zero exit before anything is built;
2. trainer: amp-O2 bf16 params + fp32 masters, ``FusedAdam``, dynamic
   ``LossScaler``, ``make_resilient_train_step`` driven by
   ``run_training`` for a few steps at bs 8 x seq 1024;
3. kernel: ``fused_paged_decode_attention``'s Pallas path against its
   ``jnp`` reference at these widths (bf16, a w=3 verify window, int8);
4. server: default ``EngineConfig`` at 8 slots x 1024 — a bare
   ``InferenceEngine.serve()`` (errors propagate), then the same request
   set under ``EngineSupervisor`` (the ``python -m apex_tpu.loadtest``
   path), zero tick failures / restarts / decode retraces;
5. four chips when there are four: the same train step dp 2 x tp 2
   through ``make_train_step``'s ``shard_map`` branch, and
   ``ShardedEngine`` tp=2 on the same requests.

Weights are random from a seed, inputs are generated, nothing touches
the network. A failing phase raises: traceback, non-zero exit, and NO
result line. On success the last stdout line is the verdict, one JSON
object with exactly these keys: ``{"ok": true, "device": {"platform":
"tpu", "kind": "...", "count": N}}``; the line before it,
``chip_smoke: summary {...}``, carries the per-phase results. Set-up
(compile) seconds are reported per program as set-up time, never as a
metric; the script claims no performance number (``"claim": null``).

Tolerances. The Pallas decode kernel accumulates its flash recurrence in
f32 from bf16 operands while the reference rounds scores and
probabilities to bf16 between its two MXU GEMMs, so the two are not
bitwise: on unit-normal inputs every context element must satisfy
``|kernel - reference| <= KERNEL_ATOL + KERNEL_RTOL * |reference|``. For
the same reason greedy streams against ``generate()`` are REPORTED as a
match fraction, not gated.
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import defaultdict

#: Pallas kernel vs jnp reference on unit-normal bf16 inputs. bf16 keeps
#: 8 mantissa bits (one rounding is 2^-8 = 4e-3 relative); the reference
#: rounds scores, probabilities and output, the kernel only the
#: probabilities and output — a few roundings of an O(1) value apart
KERNEL_ATOL, KERNEL_RTOL = 2e-2, 2e-2
#: (query heads, kv_lora_rank, qk_rope_head_dim) of the latent kernel's
#: case: dots-vlm1-inst's published sizes
LATENT_SIZES = (128, 512, 64)
#: four-chip first-step loss vs the single-chip phase, same seeds: tp=2
#: splits every row-parallel reduction in two bf16 partial sums
MULTICHIP_LOSS_RTOL = 5e-3

TRAIN_STEPS = 8
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
SERVE_SLOTS, SERVE_MAX_LEN = 8, 1024
#: 12 requests: prompts spread over 16-700 tokens (buckets 16..1024, a
#: multi-page slot), #4 and #5 share a 256-token prefix (4 full pages =>
#: the suffix-prefill program), 16-64 new tokens, greedy/sampled mixed
SERVE_PROMPTS = (16, 40, 100, 200, 300, 290, 450, 700, 64, 130, 520, 48)
SERVE_NEW = (16, 24, 32, 64, 48, 40, 20, 16, 56, 28, 36, 44)
SERVE_SHARED_PREFIX, SERVE_SHARED = 256, (4, 5)


class _CompileLedger:
    """Backend-compile seconds per jitted program (jax's own monitoring
    events), and persistent-cache hits — the set-up time of a run."""

    def __init__(self):
        import jax

        self.seconds = defaultdict(float)
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[kw.get("fun_name", "?")] += duration
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> dict:
        big = {k: round(v, 1) for k, v in sorted(
            self.seconds.items(), key=lambda kv: -kv[1]) if v >= 1.0}
        return {"programs": self.count, "cache_hits": self.cache_hits,
                "total_s": round(sum(self.seconds.values()), 1),
                "by_program_s": big}


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def gpt2_124m():
    """GPT-2 124M (12 L, hidden 768, 12 x 64 heads, vocab 50304 = 50257
    padded to a multiple of 128, bf16 compute), no dropout."""
    import jax.numpy as jnp

    from apex_tpu.models import TransformerConfig

    return TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        recompute=False, scan_unroll=12, compute_dtype=jnp.bfloat16)


def seeded_model(cfg):
    """The model and its seeded random fp32 weights (one jitted init:
    eager init compiles one program per parameter shape)."""
    import jax

    from apex_tpu.models import GPTModel

    model = GPTModel(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _train_inputs(cfg, batch, seq):
    import jax

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    model, params = seeded_model(cfg)
    amp_state = amp.initialize("O2")          # bf16 params, dynamic scale
    params = amp_state.policy.cast_to_param(params)
    opt = FusedAdam(lr=1e-4, master_weights=True)   # fp32 masters
    data = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                     cfg.vocab_size)}

    def loss_fn(p, b, rng):
        return model.apply(p, b["tokens"], b["labels"])

    return model, params, opt, amp_state.scaler, data, loss_fn


def train_phase(cfg, *, batch, seq, steps) -> dict:
    """A few ``run_training`` steps on one repeated batch; returns the
    losses and the compiled step's ``tpu_custom_call`` count."""
    import math

    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.resilience import (
        ResilienceConfig,
        make_resilient_train_step,
        make_train_state,
        run_training,
    )

    model, params, opt, scaler, data, loss_fn = _train_inputs(cfg, batch, seq)
    step = make_resilient_train_step(loss_fn, opt, scaler)
    state = make_train_state(params, opt.init(params), scaler.init())
    hlo = step.lower(state, data, None).compile().as_text()
    t0 = time.perf_counter()
    result = run_training(
        step, state, lambda _step: data, steps,
        config=ResilienceConfig(metrics=MetricsRegistry(), retrace_budget=0,
                                tokens_per_step=batch * seq))
    run_s = time.perf_counter() - t0
    losses = [h["loss"] for h in result.history]
    _require(result.status == "completed" and len(losses) == steps,
             f"trainer stopped early: {result.status}, {len(losses)} steps")
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall on a repeated batch: {losses}")
    _require(result.telemetry["retraces"] == 0,
             f"train step retraced: {result.telemetry}")
    _require(result.telemetry["skips"] == 0,
             f"skipped steps: {result.telemetry}")
    return {"steps": steps, "first_loss": losses[0], "last_loss": losses[-1],
            "tpu_custom_calls": hlo.count("tpu_custom_call"),
            # run_training's wall, the executable's load included
            "run_s": round(run_s, 2)}


def kernel_phase(*, slots, heads, head_dim, page_size, pages_per_slot,
                 dtype) -> dict:
    """Pallas decode kernel vs the jnp reference on one random pool and
    page table with ragged positions; returns max-abs errors per variant
    (plain, w=3 verify window, int8 pool, and the latent kernel over its
    own two pools)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.decode_attention import (
        _latent_pallas,
        _latent_reference,
        _pallas,
        _reference,
        latent_rope_lanes,
        paged_quant_fill,
    )

    f = heads * head_dim
    n_pages = slots * pages_per_slot + 2
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    k_pages = jax.random.normal(keys[0], (n_pages, page_size, f), dtype)
    v_pages = jax.random.normal(keys[1], (n_pages, page_size, f), dtype)
    rng = np.random.RandomState(7)
    w_max = 3
    # ragged: slot 0 empty, slot 1 one row short of a page boundary, the
    # rest anywhere their table can hold (window rows included)
    cap = pages_per_slot * page_size - w_max
    positions = np.concatenate(
        [[0, page_size - 1], rng.randint(0, cap, slots - 2)])[:slots]
    table = np.full((slots, pages_per_slot), n_pages, np.int32)
    perm = rng.permutation(n_pages - 2)
    nxt = 0
    for r in range(slots):
        for j in range(-(-(int(positions[r]) + w_max) // page_size)):
            table[r, j] = perm[nxt]
            nxt += 1
    table, positions = jnp.asarray(table), jnp.asarray(positions, jnp.int32)
    zeros_q = jnp.zeros((n_pages, page_size, f), jnp.int8)
    zeros_s = jnp.zeros((n_pages, heads), jnp.float32)
    every = jnp.arange(n_pages, dtype=jnp.int32)
    k_q, k_s = paged_quant_fill(zeros_q, zeros_s, k_pages, every)
    v_q, v_s = paged_quant_fill(zeros_q, zeros_s, v_pages, every)

    # jitted like _pallas, so the appended pools compare bitwise
    reference = jax.jit(_reference,
                        static_argnames=("group", "sliding_window"))
    errors = {}
    for name, w, pools in (("bf16", 1, (k_pages, v_pages, None, None)),
                           ("bf16_w3", 3, (k_pages, v_pages, None, None)),
                           ("int8", 1, (k_q, v_q, k_s, v_s)),
                           ("int8_w3", 3, (k_q, v_q, k_s, v_s))):
        q = jax.random.normal(keys[2], (slots, w, heads, head_dim), dtype)
        k_new = jax.random.normal(keys[3], (slots, w, f), dtype)
        v_new = jax.random.normal(keys[4], (slots, w, f), dtype)
        args = (q, k_new, v_new, *pools, table, positions)
        got = _pallas(*args, group=1, sliding_window=None)
        want = reference(*args, group=1, sliding_window=None)
        ctx, ref = (x[0].astype(jnp.float32) for x in (got, want))
        err = jnp.abs(ctx - ref)
        _require(bool(jnp.all(jnp.isfinite(ctx))),
                 f"kernel {name}: non-finite context")
        _require(bool(jnp.all(err <= KERNEL_ATOL + KERNEL_RTOL
                              * jnp.abs(ref))),
                 f"kernel {name}: max-abs {float(jnp.max(err)):.3e} "
                 f"outside atol {KERNEL_ATOL} + rtol {KERNEL_RTOL}")
        # the append is the same XLA scatter on both paths
        _require(all(bool(jnp.array_equal(a, b))
                     for a, b in zip(got[1:3], want[1:3])),
                 f"kernel {name}: appended pools differ")
        errors[name] = float(jnp.max(err))

    # the latent (MLA) kernel at the published head sizes of
    # dots-vlm1-inst: 128 queries of 512 + 64 against one shared row a
    # position, over the same table and ragged positions
    heads_l, rank, rope = LATENT_SIZES
    lanes = latent_rope_lanes(rope)
    c_pages = jax.random.normal(keys[0], (n_pages, page_size, rank), dtype)
    kr_pages = jax.random.normal(
        keys[1], (n_pages, page_size, lanes), dtype).at[:, :, rope:].set(0)
    q = jax.random.normal(keys[2], (slots, heads_l, rank + lanes), dtype)
    q = q.at[:, :, rank + rope:].set(0)
    c_new = jax.random.normal(keys[3], (slots, rank), dtype)
    kr_new = jax.random.normal(keys[4], (slots, lanes), dtype)
    kr_new = kr_new.at[:, rope:].set(0)
    args = (q, c_new, kr_new, c_pages, kr_pages, table, positions)
    scale = float(rank + rope) ** -0.5
    got = _latent_pallas(*args, scale=scale)
    want = jax.jit(_latent_reference, static_argnames=("scale",))(
        *args, scale=scale)
    ctx, ref = (x[0].astype(jnp.float32) for x in (got, want))
    err = jnp.abs(ctx - ref)
    _require(bool(jnp.all(jnp.isfinite(ctx))),
             "kernel latent: non-finite context")
    _require(bool(jnp.all(err <= KERNEL_ATOL + KERNEL_RTOL * jnp.abs(ref))),
             f"kernel latent: max-abs {float(jnp.max(err)):.3e} outside "
             f"atol {KERNEL_ATOL} + rtol {KERNEL_RTOL}")
    _require(all(bool(jnp.array_equal(a, b))
                 for a, b in zip(got[1:], want[1:])),
             "kernel latent: appended pools differ")
    errors["latent"] = float(jnp.max(err))
    return {"max_abs_err": errors, "atol": KERNEL_ATOL,
            "rtol": KERNEL_RTOL}


def build_requests(vocab, prompt_lens, new_tokens, shared_prefix, shared):
    """The fixed-seed request set: greedy on even indices, sampled on
    odd; the ``shared`` indices start with one common prefix."""
    import numpy as np

    from apex_tpu.serving import Request, SamplingParams

    rng = np.random.RandomState(11)
    prefix = rng.randint(0, vocab, shared_prefix)
    requests = []
    for i, (n, new) in enumerate(zip(prompt_lens, new_tokens)):
        prompt = rng.randint(0, vocab, n)
        if i in shared:
            prompt[:shared_prefix] = prefix
        sampling = (SamplingParams() if i % 2 == 0 else
                    SamplingParams(temperature=0.8, top_k=40, seed=i))
        requests.append(Request(prompt=prompt.tolist(), max_new_tokens=new,
                                sampling=sampling))
    return requests


def _check_served(requests, results, counters, engine, label):
    _require(len(results) == len(requests),
             f"{label}: {len(results)} results for {len(requests)} requests")
    for req, res in zip(requests, results):
        _require(res.finish_reason in ("eos", "length")
                 and len(res.tokens) == req.max_new_tokens,
                 f"{label}: request {req.request_id} finished "
                 f"{res.finish_reason!r} with {len(res.tokens)} of "
                 f"{req.max_new_tokens} tokens")
    _require(engine.decode_retraces == 0,
             f"{label}: decode retraced {engine.decode_retraces}x")
    _require(counters["prefix_hits"] >= 1,
             f"{label}: no prefix-cache hit: {counters}")


def serve_phase(model, params, engine_cfg, make_requests, *,
                engine_factory=None, generate_checks=2) -> dict:
    """Serve the request set on a bare engine, then under the
    supervisor; report greedy agreement with ``generate()``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import generate
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import EngineSupervisor, InferenceEngine

    factory = engine_factory or InferenceEngine
    requests = make_requests()
    registry = MetricsRegistry()
    with factory(model, params, engine_cfg, metrics=registry) as engine:
        bare = engine.serve(requests)
        _check_served(requests, bare, registry.counters(), engine, "engine")
        custom_calls = engine.decode_program_text().count("tpu_custom_call")
        _require(engine.decode_retraces == 0,
                 "lowering the decode program for its text retraced it")
        ticks = registry.counters()["decode_steps"]

    again = make_requests()
    sup_registry = MetricsRegistry()
    with EngineSupervisor(model, params, engine_cfg, metrics=sup_registry,
                          engine_factory=engine_factory) as sup:
        supervised = sup.serve(again)
        counters = sup_registry.counters()
        _check_served(again, supervised, counters, sup.engine, "supervisor")
        _require(counters["tick_failures"] == 0
                 and counters["engine_restarts"] == 0,
                 f"supervisor swallowed a failure: {counters}")
    same = sum(a.tokens == b.tokens for a, b in zip(bare, supervised))

    # greedy vs generate(): reported, not gated (see module docstring)
    greedy = sorted((i for i, r in enumerate(requests)
                     if r.sampling.temperature == 0.0),
                    key=lambda i: requests[i].total_len)[:generate_checks]
    agree = total = 0
    for i in greedy:
        req = requests[i]
        ref = jax.jit(lambda p, x, n=req.max_new_tokens: generate(
            model, p, x, n, max_len=engine_cfg.max_len))(
                params, jnp.asarray([req.prompt], jnp.int32))
        ref = [int(t) for t in ref[0, req.prompt_len:]]
        agree += sum(a == b for a, b in zip(ref, bare[i].tokens))
        total += len(ref)
    return {"requests": len(requests), "decode_ticks": ticks,
            "tokens": sum(len(r.tokens) for r in bare),
            "tpu_custom_calls": custom_calls,
            "prefix_hits": registry.counters()["prefix_hits"],
            "bare_vs_supervised_identical": f"{same}/{len(requests)}",
            "greedy_vs_generate_match": (round(agree / total, 4)
                                         if total else None)}


def multichip_phase(cfg, engine_cfg, make_requests, single_first_loss, *,
                    batch, seq, steps=3) -> dict:
    """dp 2 x tp 2 on four devices: the train step through
    ``make_train_step``'s ``shard_map`` branch, then ``ShardedEngine``
    tp=2 on the serving request set."""
    import math

    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.serving.fleet import ShardedEngine
    from apex_tpu.training import make_train_step
    from apex_tpu.transformer import parallel_state
    from apex_tpu.utils.sharding import spec_axis_names

    devices = jax.devices()[:4]
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, devices=devices)
    try:
        model, params, opt, _scaler, data, loss_fn = _train_inputs(
            cfg, batch, seq)
        spec = model.spec()
        step = make_train_step(
            loss_fn, opt, mesh, spec, {"tokens": P("data"),
                                       "labels": P("data")},
            params_template=params)
        opt_state = opt.init(params)
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, data, None)
            losses.append(float(loss))
        _require(all(math.isfinite(x) for x in losses)
                 and losses[-1] < losses[0],
                 f"4-chip loss not finite and falling: {losses}")
        rel = abs(losses[0] - single_first_loss) / abs(single_first_loss)
        _require(rel <= MULTICHIP_LOSS_RTOL,
                 f"4-chip first loss {losses[0]} vs single-chip "
                 f"{single_first_loss}: rel {rel:.2e} > "
                 f"{MULTICHIP_LOSS_RTOL}")
        # the point: tensor-sharded leaves really live on four devices
        leaves = jax.tree_util.tree_leaves(params)
        specs = jax.tree_util.tree_leaves(
            spec, is_leaf=lambda s: isinstance(s, P))
        sharded = [leaf for leaf, s in zip(leaves, specs)
                   if "tensor" in spec_axis_names(s)]
        _require(len(leaves) == len(specs) and sharded,
                 "no tensor-sharded parameter leaf found")
        for leaf in sharded:
            on = {s.device for s in leaf.addressable_shards}
            _require(on == set(devices),
                     f"tensor-sharded leaf {leaf.shape} lives on {on}")
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        _require(min(in_use) >= 0.25 * max(in_use),
                 f"device memory is lopsided (parked on one chip?): "
                 f"{in_use}")
        del params, opt_state

        serve_model, serve_params = seeded_model(cfg)
        served = serve_phase(serve_model, serve_params, engine_cfg,
                             make_requests, engine_factory=ShardedEngine,
                             generate_checks=0)
    finally:
        parallel_state.destroy_model_parallel()
    return {"mesh": {k: int(v) for k, v in mesh.shape.items()},
            "train": {"steps": steps, "first_loss": losses[0],
                      "last_loss": losses[-1],
                      "first_loss_rel_vs_single_chip": rel,
                      "tensor_sharded_leaves_on_4_devices": len(sharded),
                      "bytes_in_use": in_use},
            "sharded_engine": served}


def main() -> int:
    # THE entry check of every chip program in this repo: place the
    # compile cache, and no TPU => SystemExit before anything is built
    from benchmarks._harness import start

    device = start()
    import jax
    import jaxlib

    from apex_tpu import native
    from apex_tpu.ops._support import pallas_mode
    from apex_tpu.serving import EngineConfig
    from apex_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_entries = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"versions={versions} native={native.available()} "
          f"cache={cache_dir} ({cache_entries} entries at start)",
          flush=True)
    _require(pallas_mode() == "tpu",
             f"pallas_mode() == {pallas_mode()!r} on a TPU backend "
             f"(APEX_TPU_FORCE_PALLAS set?)")

    ledger = _CompileLedger()
    cfg = gpt2_124m()
    engine_cfg = EngineConfig(max_slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN)

    def make_requests():
        return build_requests(cfg.vocab_size, SERVE_PROMPTS, SERVE_NEW,
                              SERVE_SHARED_PREFIX, SERVE_SHARED)

    phases = {}

    def run(name, fn):
        t0 = time.perf_counter()
        out = fn()
        out["wall_s"] = round(time.perf_counter() - t0, 1)
        phases[name] = out
        print(f"chip_smoke: {name} ok {json.dumps(out)}", flush=True)
        return out

    train = run("train", lambda: train_phase(
        cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS))
    _require(train["tpu_custom_calls"] > 0,
             "compiled train step has no tpu_custom_call: the Pallas "
             "kernels were not compiled by Mosaic")
    run("kernel", lambda: kernel_phase(
        slots=SERVE_SLOTS, heads=cfg.num_attention_heads,
        head_dim=cfg.head_dim, page_size=engine_cfg.page_size,
        pages_per_slot=engine_cfg.pages_per_slot, dtype=cfg.compute_dtype))
    serve = run("serve", lambda: serve_phase(
        *seeded_model(cfg), engine_cfg, make_requests))
    _require(serve["tpu_custom_calls"] > 0,
             "compiled decode program has no tpu_custom_call")
    multichip = f"not run: {device['count']} device(s)"
    if device["count"] >= 4:
        multi = run("multichip", lambda: multichip_phase(
            cfg, engine_cfg, make_requests, train["first_loss"],
            batch=TRAIN_BATCH, seq=TRAIN_SEQ))
        _require(multi["sharded_engine"]["tpu_custom_calls"] > 0,
                 "sharded decode program has no tpu_custom_call")
        multichip = "ran"

    print("chip_smoke: summary " + json.dumps({
        "device": device, "versions": versions,
        "native_host_runtime": native.available(),
        "phases": phases, "multichip": multichip,
        "setup": {"compile": ledger.summary(), "cache_dir": cache_dir,
                  "cache_entries_at_start": cache_entries},
        "claim": None}), flush=True)
    # the verdict, LAST and alone on its line: exactly these keys (the
    # driver parses it); the details are on the summary line above
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
