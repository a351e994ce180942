"""The dots.vlm1 adapter (``model_type`` ``dots_vlm``: rednote-hilab's
dots.vlm1, whose language model is the DeepSeek-V3 block).

Everything the harness knows of the architecture, under the names
``cellbench/README.md`` lists; ``arch/gpt2.py`` is the commented example
and ``arch/afmoe.py`` the one for layers of more than one kind. What
differs here:

- attention is LATENT (MLA): low-rank q and kv projections, a cache of one
  row of ``kv_lora_rank + qk_rope_head_dim`` values a token with no head
  axis, q/k head size ``nope + rope`` against v head size ``v_head_dim``;
- the router selects inside the best ``topk_group`` of ``n_group`` groups;
- the configuration is ONE CHIP'S SHARE of a deployment that spreads each
  layer over several: the router scores all ``router_width`` experts but
  only ``expert_range`` of them are held here, and the embedding and head
  are a slice of the vocabulary (``vocab_size`` rows: ids, logits and
  sampling over the slice);
- as in ``arch/afmoe.py``, every large leaf is made a block at a time from
  the seed and comes out in bfloat16 whatever ``round_to`` says (float32
  copies of 4.57 G parameters fit no chip); the small ones (norm weights,
  the router) are float32, or rounded to ``round_to`` and kept in it;
- the work counts are what the algorithm needs at the LEAST.

The canonical layout is the one ``reference/dots_vlm.py``'s docstring
lists. Only ``model_for`` imports the program.
"""

from __future__ import annotations

import math

from cellbench.work import causal_pairs


def sizes(config: dict) -> dict:
    """The shape numbers of a configuration file (published key names)."""
    for key, only in (("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("hidden_act", "silu"), ("attention_bias", False)):
        if config.get(key, only) != only:
            raise ValueError(f"dots_vlm: {key} = {config[key]!r}: the "
                             f"program's layer has {only!r} only")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("dots_vlm: latent attention has one key a head")
    rs = config.get("rope_scaling")
    if rs is not None and rs.get("type") != "yarn":
        raise ValueError(f"dots_vlm: rope_scaling type {rs.get('type')!r}")
    lo, hi = config.get("expert_range", (0, config["n_routed_experts"]))
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(
            f"dots_vlm: n_routed_experts ({config['n_routed_experts']}) is "
            f"the experts HELD: expert_range {[lo, hi]} must span it")
    serving = config.get("serving", {})
    return {
        "L": config["num_hidden_layers"],
        "D": config["first_k_dense_replace"],
        "h": config["hidden_size"], "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
        "V": config["vocab_size"], "ffn": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "held": config["n_routed_experts"], "lo": lo, "hi": hi,
        "E": config.get("router_width", config["n_routed_experts"]),
        "k": config["num_experts_per_tok"],
        "shared": config["n_shared_experts"],
        "n_group": config["n_group"], "topk_group": config["topk_group"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "yarn": None if rs is None else (
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale"]), float(rs["mscale_all_dim"])),
        "route_scale": float(config["routed_scaling_factor"]),
        "std": config["initializer_range"],
        "pos": config["max_position_embeddings"],
        "expert_spread": config["seeded_weights"]["expert_spread"],
        "slots": serving.get("max_slots", 1),
        "max_len": serving.get("max_len", config["max_position_embeddings"]),
    }


def vocab_ids(config: dict) -> int:
    """Traffic draws ids from the chip's slice of the vocabulary."""
    return config["vocab_size"]


def reference_args(sz: dict) -> dict:
    """The shape arguments of ``reference.dots_vlm``'s ``logits``."""
    return {"heads": sz["heads"], "nope": sz["nope"], "rope": sz["rope"],
            "v_dim": sz["dv"], "rank": sz["rank"], "eps": sz["eps"],
            "rope_theta": sz["theta"], "yarn": sz["yarn"],
            "dense_layers": sz["D"], "top_k": sz["k"],
            "n_group": sz["n_group"], "topk_group": sz["topk_group"],
            "route_scale": sz["route_scale"],
            "expert_range": (sz["lo"], sz["hi"])}


# -- seeded weights -------------------------------------------------------------

def canonical(key, sz: dict, round_to=None) -> dict:
    """Canonical weights from the seed (``reference/dots_vlm.py`` lists the
    leaves). Every matrix but the router is made a block at a time and
    comes out in bfloat16 always; norm weights and the router are float32,
    or rounded to ``round_to`` and KEPT in it when it is given."""
    import jax
    import jax.numpy as jnp

    L, D, h, V, std = sz["L"], sz["D"], sz["h"], sz["V"], sz["std"]
    heads, f, ffn = sz["heads"], sz["f"], sz["ffn"]
    fs = f * sz["shared"]
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
        """One layer's ``[held, *shape]`` expert matrices. With
        ``seeded_weights.expert_spread`` = a, expert ``e`` is ``(base + a *
        own_e) / sqrt(1 + a^2)``: one seeded base for the layer and an
        independent seeded part each (``arch/afmoe.py`` has the reason)."""
        a = sz["expert_spread"]
        base = jax.random.normal(k(), shape, jnp.float32)
        return jax.lax.map(
            lambda kk: (std / (1.0 + a * a) ** 0.5 * (
                base + a * jax.random.normal(kk, shape, jnp.float32))
            ).astype(jnp.bfloat16), jax.random.split(k(), sz["held"]))

    def rows(n_rows):
        blocks = 8 if n_rows % 8 == 0 else 1
        return big(blocks, (n_rows // blocks, h)).reshape(n_rows, h)

    M = L - D
    return {
        "embed": rows(V), "head": rows(V), "n_f": n((h,), 1.0),
        "n_in": n((L, h), 1.0), "n_post": n((L, h), 1.0),
        "w_dq": big(L, (h, sz["q_rank"])), "n_q": n((L, sz["q_rank"]), 1.0),
        "w_uq": big(L, (sz["q_rank"], heads * (sz["nope"] + sz["rope"]))),
        "w_dkv": big(L, (h, sz["rank"] + sz["rope"])),
        "n_kv": n((L, sz["rank"]), 1.0),
        "w_ukv": big(L, (sz["rank"], heads * (sz["nope"] + sz["dv"]))),
        "wo": big(L, (heads * sz["dv"], h)),
        "d_in": big(D, (h, 2 * ffn)), "d_out": big(D, (ffn, h)),
        "router": tuple(n((h, sz["E"])) for _ in range(M)),
        "router_bias": tuple(jnp.zeros((sz["E"],), jnp.float32)
                             for _ in range(M)),
        "e_in": tuple(experts((h, 2 * f)) for _ in range(M)),
        "e_out": tuple(experts((f, h)) for _ in range(M)),
        "s_in": tuple(big(1, (h, 2 * fs))[0] for _ in range(M)),
        "s_out": tuple(big(1, (fs, h))[0] for _ in range(M)),
    }


def _interleaved(w_in):
    """``[h, 2 ffn]`` columns ``[gate | up]`` -> the program's dense MLP
    layout ``[2 ffn, h]`` with rows ``gate_0, up_0, gate_1, ...``."""
    import jax.numpy as jnp

    h, two = w_in.shape
    return jnp.stack([w_in[:, :two // 2], w_in[:, two // 2:]],
                     axis=-1).reshape(h, two).T


def program_tree(w: dict, sz: dict) -> dict:
    """The canonical numbers in the program's parameter tree: a per-layer
    list under ``transformer.layers``; ``w_ukv``'s columns part into the
    per-head ``k_up [heads, nope, rank]`` and ``v_up [heads, v, rank]``
    the absorbed form reads; the experts, the embedding and the head pass
    through as they are."""
    heads, nope = sz["heads"], sz["nope"]
    layers = []
    for l in range(sz["L"]):
        if l < sz["D"]:
            mlp = {"dense_h_to_4h": {"weight": _interleaved(w["d_in"][l])},
                   "dense_4h_to_h": {"weight": w["d_out"][l].T}}
        else:
            m = l - sz["D"]
            mlp = {"router": {"weight": w["router"][m],
                              "bias": w["router_bias"][m]},
                   "w_in": w["e_in"][m], "w_out": w["e_out"][m],
                   "shared": {"w_in": w["s_in"][m], "w_out": w["s_out"][m]}}
        ukv = w["w_ukv"][l].reshape(sz["rank"], heads, nope + sz["dv"])
        layers.append({
            "input_layernorm": {"weight": w["n_in"][l]},
            "self_attention": {
                "q_down": {"weight": w["w_dq"][l].T},
                "q_layernorm": {"weight": w["n_q"][l]},
                "q_up": {"weight": w["w_uq"][l].T},
                "kv_down": {"weight": w["w_dkv"][l].T},
                "kv_layernorm": {"weight": w["n_kv"][l]},
                "k_up": {"weight": ukv[:, :, :nope].transpose(1, 2, 0)},
                "v_up": {"weight": ukv[:, :, nope:].transpose(1, 2, 0)},
                "dense": {"weight": w["wo"][l].T}},
            "post_attention_layernorm": {"weight": w["n_post"][l]},
            "mlp": mlp})
    return {"embedding": {"word_embeddings": {"weight": w["embed"]}},
            "output_layer": {"weight": w["head"]},
            "transformer": {"layers": layers,
                            "final_layernorm": {"weight": w["n_f"]}}}


def canonical_names(tree: dict) -> dict:
    """A tree of the program's structure back under canonical names (the
    inverse of :func:`program_tree`)."""
    import jax.numpy as jnp

    layers = tree["transformer"]["layers"]
    attn = [p["self_attention"] for p in layers]

    def stack(get, of=None):
        return jnp.stack([get(p) for p in (attn if of is None else of)])

    def un_interleave(w):                     # [2 ffn, h] -> [h, 2 ffn]
        two, h = w.shape
        pairs = w.T.reshape(h, two // 2, 2)
        return jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)

    def ukv(p):
        both = jnp.concatenate([p["k_up"]["weight"], p["v_up"]["weight"]], 1)
        return both.transpose(2, 0, 1).reshape(both.shape[2], -1)

    dense = [p["mlp"] for p in layers if "router" not in p["mlp"]]
    routed = [p["mlp"] for p in layers if "router" in p["mlp"]]
    return {
        "embed": tree["embedding"]["word_embeddings"]["weight"],
        "head": tree["output_layer"]["weight"],
        "n_f": tree["transformer"]["final_layernorm"]["weight"],
        "n_in": stack(lambda p: p["input_layernorm"]["weight"], layers),
        "n_post": stack(lambda p: p["post_attention_layernorm"]["weight"],
                        layers),
        "w_dq": stack(lambda p: p["q_down"]["weight"].T),
        "n_q": stack(lambda p: p["q_layernorm"]["weight"]),
        "w_uq": stack(lambda p: p["q_up"]["weight"].T),
        "w_dkv": stack(lambda p: p["kv_down"]["weight"].T),
        "n_kv": stack(lambda p: p["kv_layernorm"]["weight"]),
        "w_ukv": stack(ukv),
        "wo": stack(lambda p: p["dense"]["weight"].T),
        "d_in": stack(lambda p: un_interleave(p["dense_h_to_4h"]["weight"]),
                      dense),
        "d_out": stack(lambda p: p["dense_4h_to_h"]["weight"].T, dense),
        "router": tuple(p["router"]["weight"] for p in routed),
        "router_bias": tuple(p["router"]["bias"] for p in routed),
        "e_in": tuple(p["w_in"] for p in routed),
        "e_out": tuple(p["w_out"] for p in routed),
        "s_in": tuple(p["shared"]["w_in"] for p in routed),
        "s_out": tuple(p["shared"]["w_out"] for p in routed),
    }


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        "dots_vlm: training cells are not wired up (no training cut of "
        "this model fits the chips, and the program trains a mixed, routed "
        "model through no path yet: ROADMAP M1)")


#: what only a training cell calls
norms = loss = train_flops = train_kernel_work = _no_training


# -- the program's model --------------------------------------------------------

def model_for(config: dict):
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.ops.rope import YarnScaling

    sz = sizes(config)
    yarn = sz["yarn"]
    return GPTModel(TransformerConfig(
        num_layers=sz["L"], hidden_size=sz["h"],
        num_attention_heads=sz["heads"], ffn_hidden_size=sz["ffn"],
        vocab_size=sz["V"], max_position_embeddings=sz["pos"],
        hidden_dropout=0.0, attention_dropout=0.0,
        layernorm_epsilon=sz["eps"], position_embedding_type="rope",
        rope_theta=sz["theta"], activation="swiglu",
        normalization="rmsnorm", add_bias_linear=False,
        untie_embeddings_and_output_weights=True,
        kv_lora_rank=sz["rank"], q_lora_rank=sz["q_rank"],
        qk_nope_head_dim=sz["nope"], qk_rope_head_dim=sz["rope"],
        v_head_dim=sz["dv"],
        rope_yarn=None if yarn is None else YarnScaling(
            factor=yarn[0], original_max_position_embeddings=yarn[1],
            beta_fast=yarn[2], beta_slow=yarn[3], mscale=yarn[4],
            mscale_all_dim=yarn[5]),
        num_routed_experts=sz["E"], routed_top_k=sz["k"],
        routed_ffn_hidden_size=sz["f"], route_scale=sz["route_scale"],
        num_shared_experts=sz["shared"],
        routed_expert_range=(sz["lo"], sz["hi"]),
        routed_num_groups=sz["n_group"],
        routed_topk_groups=sz["topk_group"], num_dense_layers=sz["D"],
        init_method_std=sz["std"], params_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16))


# -- operations and bytes the algorithm needs, from shapes alone ----------------

def attention_params(sz: dict) -> float:
    """One layer's five attention matrices."""
    h, heads = sz["h"], sz["heads"]
    return (h * sz["q_rank"]
            + sz["q_rank"] * heads * (sz["nope"] + sz["rope"])
            + h * (sz["rank"] + sz["rope"])
            + sz["rank"] * heads * (sz["nope"] + sz["dv"])
            + heads * sz["dv"] * h)


def expected_assignments(sz: dict) -> float:
    """Assignments of one token that land on an expert held here, under
    the routing a seeded router makes: ``top_k * held / router width``."""
    return sz["k"] * sz["held"] / sz["E"]


def matmul_params_per_token(sz: dict) -> float:
    """Weights that multiply each token ON THIS CHIP: attention's five
    matrices, the dense or the shared-plus-expected-held experts'
    feed-forward, the router, the head's slice."""
    h = sz["h"]
    one = 3 * h * sz["f"]
    routed = (one * (sz["shared"] + expected_assignments(sz))
              + h * sz["E"])
    return (sz["L"] * attention_params(sz) + sz["D"] * 3 * h * sz["ffn"]
            + (sz["L"] - sz["D"]) * routed + sz["V"] * h)


def serve_flops(sz: dict, tokens: float, kv_pairs: float) -> float:
    """Model FLOPs of serving ``tokens`` positions that attended to
    ``kv_pairs`` cached positions: 2 per weight per token, and QK^T + PV
    at the EXPANDED head sizes (``2 heads (nope + rope + v)`` a pair a
    layer): what the attention is, whatever form computes it."""
    per_pair = 2.0 * sz["heads"] * (sz["nope"] + sz["rope"] + sz["dv"])
    return (2.0 * matmul_params_per_token(sz) * tokens
            + per_pair * kv_pairs * sz["L"])


def mla_decode_work(sz: dict, context_tokens: float, rows: float) -> tuple:
    """(FLOPs, bytes) of the absorbed decode attention: every cached
    position is scored by ``heads`` queries of ``rank + rope`` and weighs
    ``rank`` values; its ``rank + rope`` bf16 values are read once, and
    each step's row written."""
    width = sz["rank"] + sz["rope"]
    flops = context_tokens * sz["heads"] * 2.0 * (width + sz["rank"])
    return (flops * sz["L"],
            (context_tokens + rows) * width * 2.0 * sz["L"])


def flash_prefill_work(sz: dict, prompt_lens) -> tuple:
    """(FLOPs, bytes) of causal prefill attention, unpadded, at q/k head
    size ``nope + rope`` and v head size ``v``."""
    pairs = sum(causal_pairs(n) for n in prompt_lens)
    tokens = sum(prompt_lens)
    qk, dv = sz["nope"] + sz["rope"], sz["dv"]
    return (2.0 * sz["heads"] * (qk + dv) * pairs * sz["L"],
            tokens * sz["heads"] * 2 * (qk + dv) * 2.0 * sz["L"])


def touched_share(sz: dict, rows_a_call: float) -> float:
    """Share of the held experts that at least one of ``rows_a_call`` rows
    chooses, when each row chooses an expert with chance ``top_k / router
    width``: an expert no row chose is not read."""
    return 1.0 - (1.0 - sz["k"] / sz["E"]) ** rows_a_call


def moe_experts_decode_work(sz: dict, decode_rows: float) -> tuple:
    """(FLOPs, bytes) of the routed products of the decode steps. FLOPs:
    the expected assignments that land here, each ``3 h f`` weights.
    Bytes: one read of each held expert a call touches; the loop hands
    over neither the calls nor the routing, so count the fewest calls
    there can have been (every one full) touching the share of the held
    experts that uniform routing leaves none of its rows without."""
    expert_layers = sz["L"] - sz["D"]
    per_expert = 3 * sz["h"] * sz["f"]
    calls = math.ceil(decode_rows / sz["slots"])
    share = touched_share(sz, decode_rows / max(calls, 1))
    return (2.0 * decode_rows * expected_assignments(sz) * per_expert
            * expert_layers,
            calls * sz["held"] * share * per_expert * 2.0 * expert_layers)


def serve_kernel_work(sz: dict, prompt_lens, context_tokens: float,
                      decode_rows: float) -> dict:
    return {
        "mla_decode_work": mla_decode_work(sz, context_tokens, decode_rows),
        "flash_prefill_work": flash_prefill_work(sz, prompt_lens),
        "moe_experts_decode_work": moe_experts_decode_work(sz, decode_rows)}


def shapes(sz: dict, traffic: dict) -> dict:
    """``{latent}`` and ``{rope_lanes}`` are the minor dims of the ``c``
    and ``kR`` page pools (the latent kernel's call is found by them: the
    rotary part on whole 128-lane tiles); ``{experts}`` the experts HELD."""
    return {"heads": sz["heads"], "latent": sz["rank"],
            "rope_lanes": -(-sz["rope"] // 128) * 128,
            "hidden": sz["h"], "experts": sz["held"],
            "moe_in": 2 * sz["f"], "moe_f": sz["f"]}
