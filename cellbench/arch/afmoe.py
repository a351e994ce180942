"""The AFMoE adapter (``model_type`` ``afmoe``: Arcee's Trinity family).

Everything the harness knows of the architecture, under the names
``cellbench/README.md`` lists; ``arch/gpt2.py`` is the commented example.
What differs here:

- the layers are of more than one kind (window or full attention; a dense
  or a routed feed-forward), so the program's ``transformer.layers`` is a
  per-layer LIST and the canonical weights keep the expert layers' leaves
  as one entry per layer;
- float32 canonical weights would be 17 GB at the published widths, so
  ``canonical`` makes each large leaf a block at a time from the seed and
  rounds it to bfloat16 in the same program, whatever ``round_to`` says
  (the values are the same either way: both loops round to bfloat16);
  the other leaves are rounded to ``round_to`` and kept in it;
- the work counts are what the algorithm needs at the LEAST: a share of a
  roofline may never pass 100%, whatever the router and the context
  lengths of a run do (see each function).

The canonical layout is the one ``reference/afmoe.py``'s docstring lists.
Only ``model_for`` imports the program.
"""

from __future__ import annotations

import math

from cellbench.work import causal_pairs

#: share of the 128 experts a decode call touches, for the weight bytes
#: below. The program's ``moe_experts_touched`` histogram read a mean of
#: 126.54-126.64 of 128 = 98.9% (three 10 s windows with their fills, idle
#: slots included; my chip runs, PR 30; PERF.md section 6): above 97%, so
#: every expert counts once a call
TOUCHED_SHARE = 1.0


def sizes(config: dict) -> dict:
    """The shape numbers of a configuration file (published key names)."""
    for key, only in (("score_func", "sigmoid"), ("route_norm", True),
                      ("n_group", 1)):
        if config.get(key, only) != only:
            raise ValueError(f"afmoe: {key} = {config[key]!r}: the program's "
                             f"routed layer has {only!r} only")
    h = config["hidden_size"]
    serving = config.get("serving", {})
    return {
        "L": config["num_hidden_layers"], "D": config["num_dense_layers"],
        "h": h, "heads": config["num_attention_heads"],
        "kv": config["num_key_value_heads"], "dh": config["head_dim"],
        "V": config["vocab_size"], "ffn": config["intermediate_size"],
        "f": config["moe_intermediate_size"], "E": config["num_experts"],
        "k": config["num_experts_per_tok"],
        "shared": config["num_shared_experts"],
        "window": config["sliding_window"],
        "types": tuple("sliding" if t == "sliding_attention" else "full"
                       for t in config["layer_types"]),
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "route_scale": float(config["route_scale"]),
        "emb_mult": math.sqrt(h) if config.get("mup_enabled") else 1.0,
        "std": config["initializer_range"],
        "pos": config["max_position_embeddings"],
        "expert_spread": config["seeded_weights"]["expert_spread"],
        "slots": serving.get("max_slots", 1),
        "max_len": serving.get("max_len", config["max_position_embeddings"]),
    }


def vocab_ids(config: dict) -> int:
    return config["vocab_size"]


def reference_args(sz: dict) -> dict:
    """The shape arguments of ``reference.afmoe``'s ``logits``."""
    return {"heads": sz["heads"], "kv_heads": sz["kv"], "head_dim": sz["dh"],
            "eps": sz["eps"], "window": sz["window"],
            "rope_theta": sz["theta"], "layer_types": sz["types"],
            "dense_layers": sz["D"], "emb_mult": sz["emb_mult"],
            "top_k": sz["k"], "route_scale": sz["route_scale"]}


# -- seeded weights -------------------------------------------------------------

def canonical(key, sz: dict, round_to=None) -> dict:
    """Canonical weights from the seed (``reference/afmoe.py`` lists the
    leaves). Large leaves (embedding, head, the experts) are made a block
    at a time and come out in bfloat16 always; the others are float32, or
    rounded to ``round_to`` and KEPT in it when it is given (the reference
    casts a leaf up where it uses it: nothing is held twice)."""
    import jax
    import jax.numpy as jnp

    L, D, h, V, std = sz["L"], sz["D"], sz["h"], sz["V"], sz["std"]
    E, f, ffn, dh = sz["E"], sz["f"], sz["ffn"], sz["dh"]
    q_w, kv_w, fs = sz["heads"] * dh, sz["kv"] * dh, sz["f"] * sz["shared"]
    count = iter(range(1 << 20))

    def k():
        return jax.random.fold_in(key, next(count))

    def n(shape, mean=0.0):
        x = mean + std * jax.random.normal(k(), shape, jnp.float32)
        return x if round_to is None else x.astype(round_to)

    def big(lead, shape):
        """``[lead, *shape]`` bfloat16, one leading index at a time: the
        float32 draw of one block is all that ever exists."""
        return jax.lax.map(
            lambda kk: (std * jax.random.normal(kk, shape, jnp.float32)
                        ).astype(jnp.bfloat16),
            jax.random.split(k(), lead))

    def experts(shape):
        """One layer's ``[E, *shape]`` expert matrices, a block at a
        time like ``big``. With the configuration's
        ``seeded_weights.expert_spread`` = a, expert ``e`` is ``(base + a *
        own_e) / sqrt(1 + a^2)``: one seeded base for the layer and an
        independent seeded part each (every entry keeps the standard
        deviation ``std``; two experts correlate at ``1 / (1 + a^2)``)."""
        a = sz["expert_spread"]
        base = jax.random.normal(k(), shape, jnp.float32)
        return jax.lax.map(
            lambda kk: (std / (1.0 + a * a) ** 0.5 * (
                base + a * jax.random.normal(kk, shape, jnp.float32))
            ).astype(jnp.bfloat16), jax.random.split(k(), E))

    def rows(n_rows):
        blocks = 8 if n_rows % 8 == 0 else 1
        return big(blocks, (n_rows // blocks, h)).reshape(n_rows, h)

    M = L - D
    return {
        "embed": rows(V), "head": rows(V), "n_f": n((h,), 1.0),
        "n_in": n((L, h), 1.0), "n_post_attn": n((L, h), 1.0),
        "n_pre_mlp": n((L, h), 1.0), "n_post_mlp": n((L, h), 1.0),
        "wq": n((L, h, q_w)), "wk": n((L, h, kv_w)), "wv": n((L, h, kv_w)),
        "wg": n((L, h, q_w)), "wo": n((L, q_w, h)),
        "n_q": n((L, dh), 1.0), "n_k": n((L, dh), 1.0),
        "d_in": n((D, h, 2 * ffn)), "d_out": n((D, ffn, h)),
        "router": tuple(n((h, E)) for _ in range(M)),
        "router_bias": tuple(jnp.zeros((E,), jnp.float32) for _ in range(M)),
        "e_in": tuple(experts((h, 2 * f)) for _ in range(M)),
        "e_out": tuple(experts((f, h)) for _ in range(M)),
        "s_in": tuple(n((h, 2 * fs)) for _ in range(M)),
        "s_out": tuple(n((fs, h)) for _ in range(M)),
    }


def _grouped_qkv(wq, wk, wv, sz):
    """``[h, heads dh]``, ``[h, kv dh]`` x2 -> the program's fused
    projection ``[kv (group + 2) dh, h]``: per KV head its ``group`` query
    heads, then its key, then its value."""
    import jax.numpy as jnp

    h, kv, dh = sz["h"], sz["kv"], sz["dh"]
    group = sz["heads"] // kv
    q = wq.reshape(h, kv, group, dh)
    both = jnp.concatenate(
        [q, wk.reshape(h, kv, 1, dh), wv.reshape(h, kv, 1, dh)], axis=2)
    return both.reshape(h, kv * (group + 2) * dh).T


def _interleaved(w_in):
    """``[h, 2 ffn]`` columns ``[gate | up]`` -> the program's dense MLP
    layout ``[2 ffn, h]`` with rows ``gate_0, up_0, gate_1, ...``."""
    import jax.numpy as jnp

    h, two = w_in.shape
    return jnp.stack([w_in[:, :two // 2], w_in[:, two // 2:]],
                     axis=-1).reshape(h, two).T


def program_tree(w: dict, sz: dict) -> dict:
    """The canonical numbers in the program's parameter tree: a per-layer
    list under ``transformer.layers``; the experts, the embedding and the
    head pass through as they are."""
    layers = []
    for l in range(sz["L"]):
        def norm(name):
            return {"weight": w[name][l]}

        if l < sz["D"]:
            mlp = {"dense_h_to_4h": {"weight": _interleaved(w["d_in"][l])},
                   "dense_4h_to_h": {"weight": w["d_out"][l].T}}
        else:
            m = l - sz["D"]
            mlp = {"router": {"weight": w["router"][m],
                              "bias": w["router_bias"][m]},
                   "w_in": w["e_in"][m], "w_out": w["e_out"][m],
                   "shared": {"w_in": w["s_in"][m], "w_out": w["s_out"][m]}}
        layers.append({
            "input_layernorm": norm("n_in"),
            "self_attention": {
                "query_key_value": {"weight": _grouped_qkv(
                    w["wq"][l], w["wk"][l], w["wv"][l], sz)},
                "dense": {"weight": w["wo"][l].T},
                "gate": {"weight": w["wg"][l].T},
                "q_layernorm": {"weight": w["n_q"][l]},
                "k_layernorm": {"weight": w["n_k"][l]}},
            "post_attention_layernorm": norm("n_post_attn"),
            "pre_mlp_layernorm": norm("n_pre_mlp"),
            "post_mlp_layernorm": norm("n_post_mlp"),
            "mlp": mlp})
    return {"embedding": {"word_embeddings": {"weight": w["embed"]}},
            "output_layer": {"weight": w["head"]},
            "transformer": {"layers": layers,
                            "final_layernorm": {"weight": w["n_f"]}}}


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        "afmoe: training cells are not wired up (the program trains a "
        "mixed, routed model through no path yet: ROADMAP M1)")


#: what only a training cell calls
canonical_names = norms = loss = train_flops = train_kernel_work = \
    _no_training


# -- the program's model --------------------------------------------------------

def model_for(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel, TransformerConfig

    sz = sizes(config)
    return GPTModel(TransformerConfig(
        num_layers=sz["L"], hidden_size=sz["h"],
        num_attention_heads=sz["heads"], num_query_groups=sz["kv"],
        kv_channels=sz["dh"], ffn_hidden_size=sz["ffn"],
        vocab_size=sz["V"], max_position_embeddings=sz["pos"],
        hidden_dropout=0.0, attention_dropout=0.0,
        layernorm_epsilon=sz["eps"], position_embedding_type="rope",
        rope_theta=sz["theta"], activation="swiglu",
        normalization="rmsnorm", sliding_window=sz["window"],
        attention_layer_types=sz["types"], add_bias_linear=False,
        qk_layernorm=True, attention_output_gate=True, sandwich_norm=True,
        embedding_multiplier=sz["emb_mult"],
        untie_embeddings_and_output_weights=True,
        num_routed_experts=sz["E"], routed_top_k=sz["k"],
        routed_ffn_hidden_size=sz["f"], route_scale=sz["route_scale"],
        num_shared_experts=sz["shared"], num_dense_layers=sz["D"],
        init_method_std=sz["std"], params_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16))


# -- operations and bytes the algorithm needs, from shapes alone ----------------

def _kinds(sz: dict) -> tuple:
    """(window layers, full layers)."""
    n_window = sum(t == "sliding" for t in sz["types"])
    return n_window, sz["L"] - n_window


def _least_context(sz: dict, context_tokens: float) -> float:
    """Cached positions the layers must read for ``context_tokens`` of
    context, summed over layers: a full layer reads every one; a window
    layer at least ``window / max_len`` of them (the least
    ``sum_i min(c_i, window)`` can be when no slot passes ``max_len``)."""
    n_window, n_full = _kinds(sz)
    share = min(1.0, sz["window"] / sz["max_len"])
    return context_tokens * (n_full + n_window * share)


def matmul_params_per_token(sz: dict) -> float:
    """Weights that multiply each token: attention's four projections and
    the gate, the dense or the shared-plus-chosen experts' feed-forward,
    the router, the head."""
    h, dh = sz["h"], sz["dh"]
    attn = h * dh * (3 * sz["heads"] + 2 * sz["kv"])
    dense = 3 * h * sz["ffn"]
    routed = 3 * h * sz["f"] * (sz["k"] + sz["shared"]) + h * sz["E"]
    return (sz["L"] * attn + sz["D"] * dense
            + (sz["L"] - sz["D"]) * routed + sz["V"] * h)


def serve_flops(sz: dict, tokens: float, kv_pairs: float) -> float:
    """Model FLOPs of serving ``tokens`` positions that attended to
    ``kv_pairs`` cached positions: 2 per weight per token, and QK^T + PV
    (``4 heads dh`` a pair a layer) over the pairs the layers must at
    least see."""
    pairs = _least_context(sz, kv_pairs)
    return (2.0 * matmul_params_per_token(sz) * tokens
            + 4.0 * sz["heads"] * sz["dh"] * pairs)


def paged_decode_bytes(sz: dict, context_tokens: float, rows: float) -> float:
    """Bytes the decode steps must move for attention: K and V (bf16) of
    the cached positions each layer must read, plus the rows written."""
    width = 2 * sz["kv"] * sz["dh"] * 2
    return (_least_context(sz, context_tokens) + rows * sz["L"]) * width


def flash_prefill_work(sz: dict, prompt_lens) -> tuple:
    """(FLOPs, bytes) of prefill attention, unpadded: a window layer sees
    ``min(i + 1, window)`` keys at query ``i``."""
    n_window, n_full = _kinds(sz)
    w = sz["window"]

    def window_pairs(n):
        m = min(n, w)
        return causal_pairs(m) + (n - m) * w

    pairs = sum(n_full * causal_pairs(n) + n_window * window_pairs(n)
                for n in prompt_lens)
    tokens = sum(prompt_lens)
    width = (2 * sz["heads"] + 2 * sz["kv"]) * sz["dh"] * 2
    return 4.0 * sz["heads"] * sz["dh"] * pairs, tokens * width * sz["L"]


def moe_experts_decode_work(sz: dict, decode_rows: float) -> tuple:
    """(FLOPs, bytes) of the routed products of the decode steps. FLOPs
    are exact: every decoded row meets ``k`` experts of ``3 h f`` weights.
    Bytes: one read of each expert a call touches. The loop hands over
    neither the calls nor the routing, so count the fewest calls there can
    have been (every one full) touching :data:`TOUCHED_SHARE` of the
    experts."""
    expert_layers = sz["L"] - sz["D"]
    per_expert = 3 * sz["h"] * sz["f"]
    calls = math.ceil(decode_rows / sz["slots"])
    return (2.0 * decode_rows * sz["k"] * per_expert * expert_layers,
            calls * sz["E"] * TOUCHED_SHARE * per_expert * 2.0
            * expert_layers)


def serve_kernel_work(sz: dict, prompt_lens, context_tokens: float,
                      decode_rows: float) -> dict:
    return {
        "paged_decode_work": (0.0, paged_decode_bytes(
            sz, context_tokens, decode_rows)),
        "flash_prefill_work": flash_prefill_work(sz, prompt_lens),
        "moe_experts_decode_work": moe_experts_decode_work(sz, decode_rows)}


def shapes(sz: dict, traffic: dict) -> dict:
    """``{h}`` is the page pool's minor dim (what the accepted decode
    patterns mean by it: ``kv_heads * head_dim``); the routed products'
    patterns name the experts' three dims."""
    return {"heads": sz["heads"], "dh": sz["dh"], "h": sz["kv"] * sz["dh"],
            "hidden": sz["h"], "experts": sz["E"], "moe_in": 2 * sz["f"],
            "moe_f": sz["f"]}
