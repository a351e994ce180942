"""A serving cell: the supervised engine under closed- or open-loop load.

The window makes the two calls a server makes, ``submit`` and ``tick``,
from one thread. After every tick the harness looks at what each request
has produced and stamps new tokens with its own clock: time to first
token counts from the moment a request was *due*, gaps between tokens are
taken over every request, and what never ends is a miss.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from cellbench import check, spans, stats, traffic as T, weights as W, work

DONE = ("length", "eos")


class Ledger:
    """What the harness saw of each request, on its own clock."""

    def __init__(self):
        self.due, self.first, self.last, self.count = {}, {}, {}, {}
        self.gaps, self.plans, self.results = [], {}, {}
        self.prompt_len = {}

    def sent(self, rid, plan, due_t):
        self.due[rid], self.plans[rid] = due_t, plan
        self.count[rid] = 0
        self.prompt_len[rid] = len(plan.prompt)

    def tokens(self, rid, n, t):
        """Request ``rid`` now has ``n`` tokens, seen at ``t``."""
        have = self.count.get(rid)
        if have is None or n <= have:
            return 0
        # tokens that reached the host in one tick share its stamp: the
        # first waited since the last stamp, the rest came with it
        if have == 0:
            self.first[rid] = t
            self.gaps.extend([0.0] * (n - 1))
        else:
            self.gaps.append(t - self.last[rid])
            self.gaps.extend([0.0] * (n - have - 1))
        self.last[rid] = t
        self.count[rid] = n
        return n - have


def run(cell, seed: int, seconds: float, trace_dir, t_process: float,
        compiles, control: bool = False) -> dict:
    from cellbench.program import Server

    tr = cell.traffic
    vocab = cell.config["vocab_size"]
    server = Server(cell.config, seed)
    sz = server.sz
    max_len = cell.config["serving"]["max_len"]
    max_slots = cell.config["serving"]["max_slots"]
    led = Ledger()
    closed = tr["kind"] == "closed"

    # -- set-up: warm every program the window will use, and no other
    if closed:
        pool = T.closed_pool(tr, seed, vocab)
        rng = T.rng_for(seed, "midlife")
        first = [T.midlife(p, float(rng.random()), vocab, rng)
                 for p in pool[:tr["callers"]]]
        lengths = [len(p.prompt) for p in pool + first]
    else:
        plan = T.open_schedule(tr, seed, seconds, vocab)
        lengths = [len(p.prompt) for p in plan]
    wrng = T.rng_for(seed, "warm")
    for b in T.prompt_buckets(lengths, max_len):
        n = min(b, max_len - 2)
        server.submit(server.request(T.Planned(
            prompt=wrng.integers(0, vocab, n).tolist(), max_new_tokens=2,
            greedy=bool(b % 3), sample_seed=b)))
    while server.active() or server.queued():
        server.tick()

    next_in_pool = 0

    def resubmit(finished, rec=None):
        """A caller whose request ended sends its next one."""
        nonlocal next_in_pool
        lost = 0
        for _ in finished:
            p = pool[next_in_pool % len(pool)]
            if next_in_pool >= len(pool):
                # the pool came round: same lengths, fresh ids, so that a
                # repeated prompt never turns into a prefix-cache hit
                p = T.Planned(wrng.integers(0, vocab, len(p.prompt)).tolist(),
                              p.max_new_tokens, p.greedy, p.sample_seed)
            next_in_pool += 1
            req = server.request(p)
            if rec is None:
                took = server.submit(req)
            else:
                with rec.span("cb.submit"):
                    took = server.submit(req)
            if took:
                led.sent(req.request_id, p, time.perf_counter())
            else:
                lost += 1
        return lost

    if closed:
        # fill: every caller's first request is one in mid-life, so the
        # window opens on callers in steady state, not on a cold start
        waiting = list(first)
        next_in_pool = len(first)
        while waiting:
            while waiting and server.queued() < tr["max_waiting"]:
                p = waiting.pop(0)
                req = server.request(p)
                if server.submit(req):
                    led.sent(req.request_id, p, time.perf_counter())
            finished = server.tick()
            _observe(server, led, finished, time.perf_counter())
            resubmit([r for r in finished if r.request_id in led.count])
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process

    # -- the window
    rec = spans.Recorder(trace_dir, seconds, tr.get("trace_seconds", 4.0))
    ticks = []                      # (n_active, context_tokens, kv_pages)
    prefills = []                   # (tick index, prompt length)
    lags, failed_submits, attempted, depth = [], 0, 0, []
    in_window = set()
    tokens_in_window = 0
    compiles.reset()
    t0 = time.perf_counter()
    end = seconds + (0.0 if closed else tr["drain_s"])
    i_due = 0
    while True:
        now = time.perf_counter() - t0
        if now >= end:
            break
        rec.poll(now)
        if not closed:
            while i_due < len(plan) and plan[i_due].due_s <= now:
                p = plan[i_due]
                i_due += 1
                attempted += 1
                req = server.request(p)
                with rec.span("cb.submit"):
                    took = server.submit(req)
                lags.append(time.perf_counter() - t0 - p.due_s)
                if took:
                    led.sent(req.request_id, p, t0 + p.due_s)
                    in_window.add(req.request_id)
                else:
                    failed_submits += 1
            if not server.active() and not server.queued():
                if i_due >= len(plan):
                    break
                # nothing to serve: wait for the next arrival
                time.sleep(min(0.002, max(0.0, plan[i_due].due_s - now)))
                continue
        with rec.span("cb.tick"):
            finished = server.tick()
        t = time.perf_counter()
        n_new, n_active, ctx, started = _observe(server, led, finished, t)
        if t - t0 <= seconds:
            tokens_in_window += n_new
        ticks.append((n_active, ctx,
                      server.registry.gauges().get("kv_pages_in_use", 0)))
        depth.append(server.queued())
        prefills += [(len(ticks) - 1, n) for n in started]
        if closed:
            failed_submits += resubmit(finished, rec)
    window_s = min(time.perf_counter() - t0, seconds) if not closed \
        else time.perf_counter() - t0
    compiled = compiles.report()
    rec.stop()

    # -- what the window produced
    results = led.results
    if closed:
        attempted = len(led.count)
        bad = [r for r in results if results[r].finish_reason not in DONE]
        failed = len(bad) + failed_submits
        e2e = {"serve_tok_per_s": tokens_in_window / window_s}
    else:
        ttft = []
        for rid in in_window:
            ok = (rid in led.first and (rid not in results or
                  results[rid].finish_reason in DONE))
            ttft.append((led.first[rid] - led.due[rid]) * 1e3 if ok
                        else math.inf)
        ttft += [math.inf] * failed_submits
        unfinished = [r for r in in_window if r not in results
                      or results[r].finish_reason not in DONE]
        failed = len(unfinished) + failed_submits
        e2e = {"ttft_p90_ms": stats.percentile(ttft, 90),
               "tpot_p90_ms": stats.percentile(led.gaps, 90) * 1e3}
    peak = spans.memory_peak_bytes(cell.chips)

    # -- facts for the per-layer readers (the traced part of the window)
    k0 = rec.traced_from("cb.tick")
    traced = ticks[k0:]
    traced_prompts = [n for k, n in prefills if k >= k0]
    decode_rows = sum(a for a, _, _ in traced)
    context = sum(c for _, c, _ in traced)
    pairs = context + sum(work.causal_pairs(n) for n in traced_prompts)
    served = decode_rows + sum(traced_prompts)
    facts = {
        "sz": sz, "recorder": rec,
        "shapes": {"heads": sz["heads"], "dh": sz["h"] // sz["heads"],
                   "h": sz["h"], "n_pages": server.n_pages,
                   "page_size": cell.config["serving"]["page_size"],
                   "slots": max_slots},
        "max_slots": max_slots, "n_pages": server.n_pages,
        "occupancy": [a for a, _, _ in ticks],
        "kv_pages": [k for _, _, k in ticks],
        "lag_s": lags, "queue_depth": depth,
        "queue_s": [r.queue_s for r in results.values()
                    if r.request_id in in_window or closed],
        "prefill_tokens_traced": traced_prompts,
        "serve_flops_traced": work.serve_flops(sz, served, pairs),
        "paged_decode_work": (0.0, work.paged_decode_bytes(
            sz, context, decode_rows)),
        "flash_prefill_work": work.flash_prefill_work(sz, traced_prompts),
    }

    sample = _sample(led, results, seed, cell.limits.get("sample", 12))
    server.close()
    del server
    spans.free_device()
    readings = reference_readings(cell, seed, sample)
    if control:
        # calibration only: the reference one precision down, judged at
        # the same positions of the same prompts and tokens
        from cellbench.reference import gpt2

        readings["_control"] = reference_readings(cell, seed, sample,
                                                  quant=gpt2.fp8)
    readings["compiles_in_window"] = compiled
    return {"attempted": attempted, "failed": failed, "setup_s": setup_s,
            "window_s": window_s, "memory_peak_bytes": int(peak),
            "readings": readings, "end_to_end": e2e, "facts": facts}


def _observe(server, led, finished, t):
    """Stamp what this tick produced. Returns (new tokens, active slots,
    their context tokens, prompt lengths of requests first seen)."""
    n_new, ctx, n_active, started = 0, 0, 0, []
    for req, toks, _ in server.inflight():
        rid = req.request_id
        if rid not in led.count:
            continue
        n_active += 1
        ctx += led.prompt_len[rid] + len(toks)
        if led.count[rid] == 0 and toks:
            started.append(led.prompt_len[rid])
        n_new += led.tokens(rid, len(toks), t)
    for res in finished:
        rid = res.request_id
        if rid not in led.count:
            continue
        if led.count[rid] == 0 and res.tokens:
            started.append(led.prompt_len[rid])
        n_new += led.tokens(rid, len(res.tokens), t)
        led.results[rid] = res
    return n_new, n_active, ctx, started


def _sample(led, results, seed: int, n: int) -> list:
    """Greedy requests the window finished: the longest, and ``n - 1``
    more drawn from the seed. Each as (prompt, served tokens)."""
    done = sorted(r for r, res in results.items()
                  if led.plans[r].greedy and res.finish_reason in DONE
                  and res.tokens)
    if not done:
        return []
    longest = max(done, key=lambda r: led.prompt_len[r] + len(
        results[r].tokens))
    rest = [r for r in done if r != longest]
    rng = T.rng_for(seed, "sample")
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(list(led.plans[r].prompt), list(results[r].tokens))
            for r in picked]


def reference_readings(cell, seed: int, sample, quant=None) -> dict:
    """The reference once over each sampled prompt with its served tokens:
    the widest gap by which a served token's logit lies below the
    reference's best. With ``quant`` (the control) the token judged at
    each position is the one the lower precision puts first."""
    import jax.numpy as jnp

    from cellbench.reference import gpt2

    if not sample:
        return {"greedy_logit_gap": None, "_sampled_tokens": 0}
    sz = W.sizes(cell.config)
    w = jax.jit(lambda k: W.canonical(k, sz, round_to=jnp.bfloat16))(
        W.key_from_seed(seed))
    fwd = jax.jit(lambda w, t, q=None: gpt2.logits(
        w, t, heads=sz["heads"], eps=sz["eps"], quant=q),
        static_argnames=("q",))
    worst, count = 0.0, 0
    for prompt, served in sample:
        ids = np.asarray(prompt + served[:-1], np.int32)
        # one shape for every request; causal, so the padding is harmless
        pad = sz["pos"] - len(ids)
        ids = np.pad(ids, (0, pad))[None]
        lg = fwd(w, jnp.asarray(ids))[0]
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        rows = lg[at]
        if quant is None:
            judged = jnp.asarray(served, jnp.int32)
        else:
            judged = jnp.argmax(fwd(w, jnp.asarray(ids), quant)[0][at], -1)
        gap = jnp.max(rows, -1) - jnp.take_along_axis(
            rows, judged[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        count += len(served)
    return {"greedy_logit_gap": worst, "_sampled_tokens": count}
