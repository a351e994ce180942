"""The per-request reference of the serving tests: one request, alone.

``reference_stream`` runs a request the way ``generate()`` decodes a
batch of one (prefill on a per-layer 4D cache, then ``decode_step``
token by token on the flattened cache, scalar position) and picks each
token by the sampling rule written out below. It shares no code with
the engine: no slot or page pool, no batched step, no sampler of the
engine's. A stream the engine serves, greedy or sampled, must equal it
token for token.
"""

import re

import jax
import jax.numpy as jnp

from apex_tpu.models.generation import (
    _cached_forward,
    cast_decode_params,
    decode_step,
    flatten_decode_caches,
    init_kv_caches,
)


def pick_token(logits, sampling, position):
    """The token at absolute ``position`` from its ``[V]`` logits: the
    argmax at temperature 0; else a draw from the softmax of
    ``logits / temperature`` with everything below the ``top_k``-th
    largest masked out, under the key ``fold_in(PRNGKey(seed),
    position)`` (a stream depends on its own seed and positions only)."""
    if sampling.temperature == 0.0:
        return int(jnp.argmax(logits))
    scaled = logits / jnp.float32(sampling.temperature)
    if sampling.top_k is not None:
        kth = jnp.sort(scaled)[-sampling.top_k]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    key = jax.random.fold_in(jax.random.PRNGKey(sampling.seed), position)
    return int(jax.random.categorical(key, scaled))


def reference_stream(model, params, request, max_len):
    """The tokens ``request`` generates served alone on a cache of
    ``max_len`` rows (the engine's ``EngineConfig.max_len``: the same
    reduction length), up to and including ``eos_token``."""
    c = model.config
    if c.compute_dtype != jnp.float32:
        params = cast_decode_params(params, c.compute_dtype)

    @jax.jit
    def prefill(params, prompt):
        caches = init_kv_caches(model, 1, max_len, stacked=False)
        logits, caches = _cached_forward(model, params, caches, prompt, 0,
                                         last_only=True)
        return logits[0, 0], flatten_decode_caches(caches, c.num_layers)

    @jax.jit
    def step(params, caches, token, position):
        logits, caches = decode_step(model, params, caches, token[None],
                                     position)
        return logits[0], caches

    logits, caches = prefill(params,
                             jnp.asarray([request.prompt], jnp.int32))
    position = request.prompt_len
    tokens = [pick_token(logits, request.sampling, position)]
    while (tokens[-1] != request.eos_token
           and len(tokens) < request.max_new_tokens):
        logits, caches = step(params, caches, jnp.int32(tokens[-1]),
                              jnp.int32(position))
        position += 1
        tokens.append(pick_token(logits, request.sampling, position))
    return tokens


def serving_programs(engine) -> dict:
    """An engine's decode, bucketed-prefill and suffix/chunk-prefill
    programs as ``(jitted fn, *args)``, each with the arguments the
    engine builds for it (one 16-token bucket for the prefills)."""
    row = jnp.asarray(engine._page_table_h[0])
    prompt = jnp.zeros((1, 16), jnp.int32)
    sampling = (jnp.float32(0.0), jnp.int32(engine._vocab), jnp.int32(0))
    aix = jnp.zeros(1, jnp.int32)
    carry = engine._carry_args(0)
    return {
        "decode": (engine._decode_fn._fn, *engine._decode_args()),
        "prefill": (engine._prefill_fn._fn, engine._params, engine._caches,
                    row, prompt, jnp.int32(11), *sampling, aix, None,
                    *carry),
        "suffix": (engine._suffix_fn._fn, engine._params, engine._caches,
                   row, prompt, jnp.int32(8), jnp.int32(3), jnp.int32(11),
                   *sampling, jnp.bool_(False), aix, None, *carry),
    }


def serving_program_text(engine, name: str) -> str:
    """Lowered (StableHLO) text of one of :func:`serving_programs`."""
    fn, *args = serving_programs(engine)[name]
    return fn.lower(*args).as_text()


def lane_pair_reshapes(text: str, ffn: int) -> list:
    """The reshapes of a lowered program to ``[..., ffn, 2]``: what the
    interleaved gated activation slices, and what the chip's compiler
    turns into a copy of the whole gate/up weight."""
    return [line.strip() for line in text.splitlines() if re.search(
        rf"stablehlo\.reshape.*-> tensor<[0-9x]*x{ffn}x2x\w+>", line)]
