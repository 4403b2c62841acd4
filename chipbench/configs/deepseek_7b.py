"""deepseek_7b as the program runs it: the program's ``ModelConfig`` and
``ServingConfig`` built from ``deepseek_7b.json``.

Every width comes from the JSON (the published ones); the program's own
``repro.configs.deepseek_7b`` is checked against it, and the depth and
``rms_eps`` are set from it (the program's default eps is 1e-5, the
published one 1e-6).
"""

from __future__ import annotations

import dataclasses


def program_config(spec: dict):
    from repro.configs import get_config

    base = get_config("deepseek_7b")
    heads = spec["num_attention_heads"]
    return dataclasses.replace(
        base,
        d_model=spec["hidden_size"],
        d_ff=spec["intermediate_size"],
        n_heads=heads,
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["hidden_size"] // heads,
        vocab=spec["vocab_size"],
        n_layers=spec["num_hidden_layers"],
        rms_eps=spec["rms_norm_eps"],
        rope_base=spec["rope_theta"],
        tie_embeddings=spec["tie_word_embeddings"],
    )


def serving_config(spec: dict):
    from repro.runtime.config import ServingConfig

    s = spec["serving"]
    return ServingConfig(
        n_slots=s["n_slots"], max_len=s["max_len"], cache=s["cache"],
        page_size=s["page_size"], prefill_chunk=s["prefill_chunk"],
        eos_id=spec["vocab_size"], temperature=0.0, default_level="f32", seed=0,
    )
