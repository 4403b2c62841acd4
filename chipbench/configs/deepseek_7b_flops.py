"""Algorithmic operations and bytes of a deepseek_7b decode pass, from
the configuration's shapes (dense multi-head attention, SwiGLU MLP,
untied head).

The count is of the algorithm, not of its implementation: an f32 matrix
product that the TPU runs as six bfloat16 passes still counts 2*m*n*k,
so a roofline share reads the same work whatever implements it.

A decode pass of one rung over ``lanes`` (each a lane's attended
context: the keys its query reads, its position + 1):

* operations: per lane, 2 x every projection's parameters (q, k, v, o,
  gate, up, down, per layer) + 2 x the head; plus attention's
  2 x 2 x heads x head_dim x context per layer;
* bytes: the weights once, at the rung's declared width (f32: 4 bytes;
  q16_16: 1 byte for the int8 projections and 2 for the bfloat16 head),
  plus every live KV row the lanes attend, at the cache's 4 bytes.

The harness finds this file by the configuration's name
(``configs/<name>_flops.py``); another architecture brings its own.
"""

from __future__ import annotations

from typing import Iterable

WEIGHT_BYTES = {"f32": {"proj": 4, "head": 4}, "q16_16": {"proj": 1, "head": 2}}
CACHE_BYTES = 4


def shapes(spec: dict) -> dict:
    d = spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = d // H
    ff = spec["intermediate_size"]
    L = spec["num_hidden_layers"]
    proj = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return {"layers": L, "proj_params": proj, "head_params": d * spec["vocab_size"],
            "heads": H, "head_dim": hd, "kv_row_bytes": 2 * KV * hd * CACHE_BYTES}


def decode_flops_per_token(spec: dict, context: int) -> float:
    s = shapes(spec)
    attn = 4 * s["heads"] * s["head_dim"] * context
    return float(s["layers"] * (2 * s["proj_params"] + attn) + 2 * s["head_params"])


def decode_pass(spec: dict, rung: str, lanes: Iterable[int]) -> (float, float):
    """(operations, bytes) of one decode pass at ``rung`` over lanes
    with the given attended contexts."""
    lanes = list(lanes)
    s = shapes(spec)
    w = WEIGHT_BYTES[rung]
    flops = sum(decode_flops_per_token(spec, c) for c in lanes)
    nbytes = (s["layers"] * s["proj_params"] * w["proj"] + s["head_params"] * w["head"]
              + sum(lanes) * s["layers"] * s["kv_row_bytes"])
    return flops, float(nbytes)
