"""Period-level block composition.

A *period* is the repeating unit of layers (1 for homogeneous stacks,
2 for gemma2 local/global, 8 for jamba's 1-attention:7-mamba pattern).
The model scans over ``n_periods`` stacked parameter pytrees, keeping
HLO size independent of depth; inside the scanned body a static Python
loop walks the period's heterogeneous positions.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import mlp_specs, swiglu_mlp

__all__ = ["period_specs", "period_forward", "init_period_cache"]

Constrain = Callable[[jnp.ndarray, str], jnp.ndarray]
_id_constrain: Constrain = lambda x, kind: x


def _mixer_specs(cfg: ModelConfig, spec: LayerSpec) -> dict:
    if spec.kind == "attn":
        return attn.attn_specs(cfg)
    if spec.kind == "mla":
        return attn.mla_specs(cfg)
    if spec.kind == "mamba":
        return ssm_mod.ssm_specs(cfg)
    raise ValueError(spec.kind)


def _ffn_specs(cfg: ModelConfig, spec: LayerSpec) -> Optional[dict]:
    if spec.ffn == "mlp":
        return mlp_specs(cfg.d_model, cfg.d_ff)
    if spec.ffn == "moe":
        return moe_mod.moe_specs(cfg)
    return None


def period_specs(cfg: ModelConfig) -> dict:
    out = {}
    for i, spec in enumerate(cfg.period):
        entry = {"mixer": _mixer_specs(cfg, spec)}
        f = _ffn_specs(cfg, spec)
        if f is not None:
            entry["ffn"] = f
        out[f"pos{i}"] = entry
    return out


def init_period_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
    quantized: bool = False,
) -> dict:
    """Cache pytree for ONE period (stacked over periods by the caller).
    ``quantized``: Q-format int8 KV payloads (FAST serving mode)."""
    out = {}
    for i, spec in enumerate(cfg.period):
        if spec.kind == "attn":
            out[f"pos{i}"] = attn.init_attn_cache(
                cfg, spec, batch, max_len, dtype, quantized=quantized
            )
        elif spec.kind == "mla":
            out[f"pos{i}"] = attn.init_mla_cache(cfg, batch, max_len, dtype)
        elif spec.kind == "mamba":
            out[f"pos{i}"] = ssm_mod.init_ssm_cache(cfg, batch)
    return out


def _scaled_branch(h, cfg: ModelConfig):
    """A residual branch's output, times ``cfg.residual_scale`` where
    the model has one (MiniCPM: scale_depth / sqrt(published depth))."""
    return h if cfg.residual_scale is None else h * cfg.residual_scale


def period_forward(
    params: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    mode: str = "precise",
    caches: Optional[dict] = None,
    prefill: bool = False,
    constrain: Constrain = _id_constrain,
    seg_aux: Optional[dict] = None,
) -> Tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    """Apply one period. Returns (x, new_caches, aux_losses (2,)).
    Single-token decode (S == 1, not prefill): ``new_caches`` holds each
    layer's new rows (leaves (B, ...)), not caches.

    ``seg_aux``: mutable dict for segment-decode rollback state.  When
    given (speculative verify), each SSM layer records its per-position
    states under ``seg_aux[f"pos{i}"]`` so the caller can roll the
    cumulative cache back to any position in the segment."""
    aux = jnp.zeros((2,), jnp.float32)
    new_caches = {} if caches is not None else None

    for i, spec in enumerate(cfg.period):
        p = params[f"pos{i}"]
        cache_i = caches.get(f"pos{i}") if caches is not None else None

        if spec.kind == "attn":
            h, c = attn.attention_forward(
                p["mixer"], x, cfg, spec,
                positions=positions, mode=mode, cache=cache_i, prefill=prefill,
                constrain=constrain,
            )
        elif spec.kind == "mla":
            h, c = attn.mla_forward(
                p["mixer"], x, cfg,
                positions=positions, mode=mode, cache=cache_i, prefill=prefill,
                constrain=constrain,
            )
        else:  # mamba
            layer_aux = {} if seg_aux is not None else None
            h, c = ssm_mod.ssm_forward(
                p["mixer"], x, cfg, mode=mode, cache=cache_i, prefill=prefill,
                constrain=constrain, seg_aux=layer_aux,
            )
            if seg_aux is not None:
                seg_aux[f"pos{i}"] = layer_aux
        x = constrain(x + _scaled_branch(h, cfg), "residual")

        if "ffn" in p:
            if spec.ffn == "moe":
                h, a = moe_mod.moe_forward(p["ffn"], x, cfg, mode, constrain=constrain)
                aux = aux + a
            else:
                h = swiglu_mlp(p["ffn"], x, mode, cfg.rms_eps)
            x = constrain(x + _scaled_branch(h, cfg), "residual")

        if new_caches is not None:
            new_caches[f"pos{i}"] = c
    return x, new_caches, aux
