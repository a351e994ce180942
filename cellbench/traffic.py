"""One general traffic generator; a mix is a data file of parameters.

Copied in idea from ``apex_tpu/loadtest/generator.py`` (seeded, open
loop, drawn up front) and changed where that generator made runs differ:
every window of a mix holds the *same count of requests and the same
multiset of lengths*; the seed only permutes their order, draws the token
ids and draws the gaps, which are then scaled to fill the window exactly.

Kinds (``traffic["kind"]``):

``train``   a stream of ``[batch, seq + 1]`` token rows, fresh every step.
``closed``  ``callers`` callers, each sending its next request when the
            last completes; requests come from one pool of fixed lengths.
``open``    a schedule of arrivals at a fixed ``rate_rps``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Planned:
    """One request as the generator plans it (the harness turns it into
    the program's request type)."""

    prompt: list
    max_new_tokens: int
    greedy: bool
    sample_seed: int
    due_s: float = 0.0


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


def quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of uniform ``[lo, hi]``, as whole
    numbers: the fixed multiset of lengths."""
    return np.rint(lo + (np.arange(n) + 0.5) * (hi - lo) / n).astype(int)


def train_stream(seed: int, batch: int, seq: int, vocab: int):
    """Endless ``[batch, seq + 1]`` int32 rows of uniform ids; tokens are
    ``[:, :-1]`` and labels ``[:, 1:]`` of each."""
    rng = rng_for(seed, "train")
    while True:
        yield rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)


def _pool(traffic: dict, n: int, seed: int, vocab: int) -> list:
    rng = rng_for(seed, "pool")
    prompts = rng.permutation(quantiles(*traffic["prompt_tokens"], n))
    outputs = rng.permutation(quantiles(*traffic["output_tokens"], n))
    n_greedy = int(round(traffic["greedy_share"] * n))
    greedy = rng.permutation(np.arange(n) < n_greedy)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    return [Planned(prompt=rng.integers(0, vocab, int(p)).tolist(),
                    max_new_tokens=int(o), greedy=bool(g),
                    sample_seed=int(s))
            for p, o, g, s in zip(prompts, outputs, greedy, seeds)]


def open_schedule(traffic: dict, seed: int, seconds: float,
                  vocab: int) -> list:
    """``round(rate * seconds)`` arrivals inside ``(0, seconds)``:
    exponential gaps, scaled so that they fill the window exactly."""
    n = int(round(traffic["rate_rps"] * seconds))
    plan = _pool(traffic, n, seed, vocab)
    gaps = rng_for(seed, "gaps").exponential(1.0, n + 1)
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    for p, t in zip(plan, due):
        p.due_s = float(t)
    return plan


def closed_pool(traffic: dict, seed: int, vocab: int) -> list:
    """The closed loop's pool, in the order the callers draw from it."""
    return _pool(traffic, int(traffic["pool"]), seed, vocab)


def midlife(plan: Planned, u: float, vocab: int,
            rng: np.random.Generator) -> Planned:
    """A request already ``u`` of the way through its output when the
    window opens: the part generated so far rides as prompt, so that the
    context it holds and the tokens it still owes are those of a caller
    in steady state."""
    done = int(u * plan.max_new_tokens)
    done = min(done, plan.max_new_tokens - 1)
    extra = rng.integers(0, vocab, done).tolist()
    return Planned(prompt=plan.prompt + extra,
                   max_new_tokens=plan.max_new_tokens - done,
                   greedy=plan.greedy, sample_seed=plan.sample_seed)


def prompt_buckets(lengths, max_len: int) -> list:
    """The padded prefill lengths (powers of two, then ``max_len``) that a
    set of prompt lengths uses: the shapes set-up has to warm."""
    out = set()
    for n in lengths:
        b = 1
        while b < n and b < max_len:
            b *= 2
        out.add(min(b, max_len))
    return sorted(out)
