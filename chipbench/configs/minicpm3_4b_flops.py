"""Algorithmic operations and bytes of a minicpm3_4b decode pass, from
the configuration's shapes (multi-head latent attention in its absorbed
decode form, SwiGLU MLP, untied head).

The count is of the algorithm, not of its implementation (as
``deepseek_7b_flops.py``).  A decode pass of one rung over ``lanes``
(each a lane's attended context: its position + 1):

* operations: per lane and layer, 2 x the parameters of ``wq_a``,
  ``wq_b``, ``wkv_a``, ``wo`` and the MLP; the absorptions of ``wkv_b``,
  2 x heads x nope x kv_rank (query into latent space) and 2 x heads x
  kv_rank x v (latent output back to heads); attention over context c,
  2 x heads x (kv_rank + rope) x c for the scores and 2 x heads x
  kv_rank x c for the values; plus 2 x the head once a lane;
* bytes: the weights once, at the rung's declared width (f32: 4 bytes;
  q16_16: 1 byte for the int8 projections, 2 for the bfloat16 head), with
  ``wkv_b`` at 4 bytes on both rungs (read through the absorbed einsums,
  it has no int8 copy); plus every live latent row the lanes attend,
  (kv_rank + rope) x 4 bytes a position a layer.
"""

from __future__ import annotations

from typing import Iterable

WEIGHT_BYTES = {"f32": {"proj": 4, "head": 4}, "q16_16": {"proj": 1, "head": 2}}
WKV_B_BYTES = 4
CACHE_BYTES = 4


def shapes(spec: dict) -> dict:
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    ql, r = spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rope, v = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"], spec["v_head_dim"]
    ff = spec["intermediate_size"]
    proj = d * ql + ql * H * (nope + rope) + d * (r + rope) + H * v * d + 3 * d * ff
    return {"layers": spec["num_hidden_layers"], "proj_params": proj,
            "wkv_b_params": r * H * (nope + v), "head_params": d * spec["vocab_size"],
            "heads": H, "rank": r, "rope": rope, "nope": nope, "v": v,
            "latent_row_bytes": (r + rope) * CACHE_BYTES}


def decode_flops_per_token(spec: dict, context: int) -> float:
    s = shapes(spec)
    H, r = s["heads"], s["rank"]
    absorb = 2 * H * s["nope"] * r + 2 * H * r * s["v"]
    attn = 2 * H * (r + s["rope"]) * context + 2 * H * r * context
    return float(s["layers"] * (2 * s["proj_params"] + absorb + attn) + 2 * s["head_params"])


def decode_pass(spec: dict, rung: str, lanes: Iterable[int]) -> (float, float):
    """(operations, bytes) of one decode pass at ``rung`` over lanes
    with the given attended contexts."""
    lanes = list(lanes)
    s = shapes(spec)
    w = WEIGHT_BYTES[rung]
    flops = sum(decode_flops_per_token(spec, c) for c in lanes)
    nbytes = (s["layers"] * (s["proj_params"] * w["proj"] + s["wkv_b_params"] * WKV_B_BYTES)
              + s["head_params"] * w["head"] + sum(lanes) * s["layers"] * s["latent_row_bytes"])
    return flops, float(nbytes)
