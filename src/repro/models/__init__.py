"""Pure-JAX decoder LM zoo: GQA / SWA / local-global / MLA attention,
capacity-dispatch MoE, Mamba-2 SSD, hybrid periods — all composed by
transformer.py and assembled by model.py, with the paper's precision
modes dispatched per-op (layers.pdot / layers.rope_tables)."""

from repro.models.config import (
    LayerSpec,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    smoke_config,
)
from repro.models.model import (
    cache_layout,
    commit_segment,
    decode_rows,
    decode_step,
    init_caches,
    init_params,
    param_specs,
    prefill_step,
    reset_cache_slot,
    segment_step,
    train_loss,
    truncate_cache_slot,
    write_cache_slot,
    write_rows,
)
