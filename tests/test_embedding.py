"""The serving steps gather embedding rows before rounding them to
bfloat16; training rounds the whole table first.  Rounding is per
element, so both give the same bits, also for a table that bfloat16
cannot represent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke
from repro.models.model import _embed, _embed_rows


@pytest.mark.parametrize("mode", ["exact", "fast"])
def test_serving_rows_equal_cast_then_gather(mode):
    cfg = smoke("deepseek_7b")
    table = jax.random.normal(jax.random.PRNGKey(3), (cfg.vocab, cfg.d_model), jnp.float32)
    rounded = table.astype(jnp.bfloat16).astype(jnp.float32)
    assert np.count_nonzero(np.asarray(rounded != table)) > table.size // 2
    params = {"embed": table}
    tokens = jnp.asarray([[0, 5, 5, cfg.vocab - 1], [7, 1, 2, 3]], jnp.int32)

    def as_rung(embed):
        # the steps' exact rung lifts the bf16 rows to f32; q16_16 keeps bf16
        def fn(p, t):
            x = embed(p, t, cfg)
            return x.astype(jnp.float32) if mode == "exact" else x
        return jax.jit(fn)

    served = np.asarray(as_rung(_embed_rows)(params, tokens))
    trained = np.asarray(as_rung(_embed)(params, tokens))
    assert served.dtype == trained.dtype
    assert served.dtype == (np.float32 if mode == "exact" else jnp.bfloat16)
    assert served.shape == (2, 4, cfg.d_model)
    np.testing.assert_array_equal(served.view(np.uint8), trained.view(np.uint8))
