"""Seeded GPT-2 weights, made by the benchmark and not by the program.

One jitted call makes every leaf on the device from ``--seed``. The
*canonical* layout is the published GPT-2 one (``c_attn`` columns are
``[q | k | v]``, each head-major; matrices are ``[in, out]``; layers are
stacked on a leading axis). The reference reads the canonical layout; the
program is handed the same numbers re-laid into its own parameter tree by
:func:`program_tree` (``[out, in]`` matrices, the fused QKV rows grouped
per head as ``[q_h | k_h | v_h]``). Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def key_from_seed(seed: int):
    """A jax PRNG key from any non-negative whole number (the driver's
    seeds pass 2**31, more than a signed 32-bit word holds)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def sizes(config: dict) -> dict:
    """The shape numbers of a configuration file (published key names)."""
    return {"L": config["n_layer"], "h": config["n_embd"],
            "heads": config["n_head"], "pos": config["n_positions"],
            "V": config["padded_vocab_size"], "ffn": 4 * config["n_embd"],
            "std": config["initializer_range"],
            "eps": config["layer_norm_epsilon"]}


def canonical(key, sz: dict, round_to=None) -> dict:
    """Canonical float32 weights. ``round_to`` rounds every leaf to that
    dtype and back (bf16: what amp-O2 training and serving hold), so the
    reference starts from the very numbers the program starts from."""
    import jax
    import jax.numpy as jnp

    L, h, V, ffn, std = sz["L"], sz["h"], sz["V"], sz["ffn"], sz["std"]
    out_std = std / (2.0 * L) ** 0.5          # GPT-2's residual-output init
    k = iter(jax.random.split(key, 16))

    def n(shape, s):
        return s * jax.random.normal(next(k), shape, jnp.float32)

    w = {
        "wte": n((V, h), std), "wpe": n((sz["pos"], h), std),
        "ln1_g": 1.0 + n((L, h), std), "ln1_b": n((L, h), std),
        "w_qkv": n((L, h, 3 * h), std), "b_qkv": n((L, 3 * h), std),
        "w_o": n((L, h, h), out_std), "b_o": n((L, h), std),
        "ln2_g": 1.0 + n((L, h), std), "ln2_b": n((L, h), std),
        "w_fc": n((L, h, ffn), std), "b_fc": n((L, ffn), std),
        "w_proj": n((L, ffn, h), out_std), "b_proj": n((L, h), std),
        "lnf_g": 1.0 + n((h,), std), "lnf_b": n((h,), std),
    }
    if round_to is not None:
        w = {a: x.astype(round_to).astype(jnp.float32) for a, x in w.items()}
    return w


def program_tree(w: dict, sz: dict) -> dict:
    """The canonical numbers in the program's parameter tree (float32;
    the caller casts to the type it trains or serves in)."""
    import jax.numpy as jnp

    L, h, heads = sz["L"], sz["h"], sz["heads"]
    dh = h // heads

    def group(x):
        # [..., 3h] columns [q | k | v] -> per head [q_h | k_h | v_h]
        lead = x.shape[:-1]
        x = x.reshape(*lead, 3, heads, dh)
        return jnp.moveaxis(x, -3, -2).reshape(*lead, 3 * h)

    def t(x):
        return jnp.swapaxes(x, -1, -2)

    return {
        "embedding": {"word_embeddings": {"weight": w["wte"]},
                      "position_embeddings": w["wpe"]},
        "transformer": {
            "layers": {
                "input_layernorm": {"weight": w["ln1_g"], "bias": w["ln1_b"]},
                "self_attention": {
                    "query_key_value": {"weight": t(group(w["w_qkv"])),
                                        "bias": group(w["b_qkv"])},
                    "dense": {"weight": t(w["w_o"]), "bias": w["b_o"]}},
                "post_attention_layernorm": {"weight": w["ln2_g"],
                                             "bias": w["ln2_b"]},
                "mlp": {
                    "dense_h_to_4h": {"weight": t(w["w_fc"]),
                                      "bias": w["b_fc"]},
                    "dense_4h_to_h": {"weight": t(w["w_proj"]),
                                      "bias": w["b_proj"]}}},
            "final_layernorm": {"weight": w["lnf_g"], "bias": w["lnf_b"]}},
    }


def canonical_names(tree: dict) -> dict:
    """Per-leaf values of a program-layout tree (norms, say: any tree with
    the program's structure and one value per leaf) under canonical names."""
    tr = tree["transformer"]
    ly = tr["layers"]
    return {
        "wte": tree["embedding"]["word_embeddings"]["weight"],
        "wpe": tree["embedding"]["position_embeddings"],
        "ln1_g": ly["input_layernorm"]["weight"],
        "ln1_b": ly["input_layernorm"]["bias"],
        "w_qkv": ly["self_attention"]["query_key_value"]["weight"],
        "b_qkv": ly["self_attention"]["query_key_value"]["bias"],
        "w_o": ly["self_attention"]["dense"]["weight"],
        "b_o": ly["self_attention"]["dense"]["bias"],
        "ln2_g": ly["post_attention_layernorm"]["weight"],
        "ln2_b": ly["post_attention_layernorm"]["bias"],
        "w_fc": ly["mlp"]["dense_h_to_4h"]["weight"],
        "b_fc": ly["mlp"]["dense_h_to_4h"]["bias"],
        "w_proj": ly["mlp"]["dense_4h_to_h"]["weight"],
        "b_proj": ly["mlp"]["dense_4h_to_h"]["bias"],
        "lnf_g": tr["final_layernorm"]["weight"],
        "lnf_b": tr["final_layernorm"]["bias"],
    }
