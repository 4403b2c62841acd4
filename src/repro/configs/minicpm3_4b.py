"""minicpm3-4b [dense] — 62L d_model=2560 40H d_ff=6400 vocab=73448,
multi-head latent attention (MLA).  [hf:openbmb/MiniCPM3-4B; hf]

MLA ranks: q_lora 768, kv_lora 256, nope 64 / rope 32 / v 64 per head.
Decode caches only the latent: the 256-wide normalised ``c_kv`` and the
32-wide shared rotary key, 288 values a position a layer (1152 B in
f32, against 40 x 2 x 64 values for per-head K and V).

MiniCPM's three scalings, each as the published modelling code applies
it: the embedding rows x ``scale_emb`` (12); every residual branch x
``scale_depth / sqrt(num_hidden_layers)`` = 1.4 / sqrt(62), from the
published depth whatever depth is served; the head's input (after the
final norm) / (hidden_size / dim_model_base) = 2560 / 256 = 10.

LongRoPE: rotary frequency i is divided by ``rope_factors[i]`` (the
short factors; max_position_embeddings equals the original 32768, so
they apply at every length, and the cos/sin multiplier
sqrt(1 + ln(32768/32768) / ln(32768)) is exactly 1).  The 16 factors
are recalled from the published config.json, which is not in the
repository: unchecked, as is the untied head (``tie_word_embeddings``).
"""

import math

from repro.models.config import LayerSpec, MLAConfig, ModelConfig

#: LongRoPE short factors (= long factors) of the published config.json
ROPE_FACTORS = (
    1.0591234137867171, 1.1241891283591912, 1.2596935748670968, 1.5380380402321725,
    2.093982435514466, 3.1185471643977227, 4.818561980716869, 7.345722624208001,
    10.786193183883008, 14.7955098045099, 18.861440637062537, 22.58508568813099,
    25.838917290233976, 28.48698521072628, 30.42542231998018, 31.705853134961017,
)
SCALE_EMB = 12.0
SCALE_DEPTH = 1.4
DIM_MODEL_BASE = 256
PUBLISHED_LAYERS = 62

CONFIG = ModelConfig(
    name="minicpm3-4b",
    d_model=2560,
    n_layers=PUBLISHED_LAYERS,
    period=(LayerSpec(kind="mla", window=None, ffn="mlp"),),
    vocab=73448,
    n_heads=40,
    n_kv_heads=40,
    head_dim=0,
    d_ff=6400,
    mla=MLAConfig(
        q_lora_rank=768, kv_lora_rank=256,
        qk_nope_head_dim=64, qk_rope_head_dim=32, v_head_dim=64,
    ),
    rope_base=10000.0,
    max_seq=32768,
    scale_emb=SCALE_EMB,
    residual_scale=SCALE_DEPTH / math.sqrt(PUBLISHED_LAYERS),
    head_divisor=2560 / DIM_MODEL_BASE,
    rope_factors=ROPE_FACTORS,
)
