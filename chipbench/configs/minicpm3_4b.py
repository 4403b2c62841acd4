"""minicpm3_4b as the program runs it: the program's ``ModelConfig`` and
``ServingConfig`` built from ``minicpm3_4b.json``.

Every width comes from the JSON (the published ones); the depth is the
JSON's cut, while the residual scale keeps the published depth
(``scale_depth / sqrt(published num_hidden_layers)``), as the published
modelling code computes it from its own 62 layers.  The program applies
LongRoPE's short factors at every length; that is the published model
only where ``max_position_embeddings`` equals the original, which is
checked.
"""

from __future__ import annotations

import dataclasses
import math


def program_config(spec: dict):
    from repro.configs import get_config
    from repro.models.config import MLAConfig

    rs = spec["rope_scaling"]
    if spec["max_position_embeddings"] != rs["original_max_position_embeddings"]:
        raise ValueError("the program applies LongRoPE's short factors at every length: "
                         "max_position_embeddings must equal the original")
    base = get_config("minicpm3_4b")
    return dataclasses.replace(
        base,
        d_model=spec["hidden_size"],
        d_ff=spec["intermediate_size"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        vocab=spec["vocab_size"],
        n_layers=spec["num_hidden_layers"],
        mla=MLAConfig(
            q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
            qk_nope_head_dim=spec["qk_nope_head_dim"],
            qk_rope_head_dim=spec["qk_rope_head_dim"], v_head_dim=spec["v_head_dim"],
        ),
        rms_eps=spec["rms_norm_eps"],
        rope_base=spec["rope_theta"],
        max_seq=spec["max_position_embeddings"],
        tie_embeddings=spec["tie_word_embeddings"],
        scale_emb=float(spec["scale_emb"]),
        residual_scale=spec["scale_depth"] / math.sqrt(spec["published"]["num_hidden_layers"]),
        head_divisor=spec["hidden_size"] / spec["dim_model_base"],
        rope_factors=tuple(float(f) for f in rs["short_factor"]),
    )


def serving_config(spec: dict):
    from repro.runtime.config import ServingConfig

    s = spec["serving"]
    return ServingConfig(
        n_slots=s["n_slots"], max_len=s["max_len"], cache=s["cache"],
        page_size=s["page_size"], prefill_chunk=s["prefill_chunk"],
        eos_id=spec["vocab_size"], temperature=0.0, default_level="f32", seed=0,
    )
