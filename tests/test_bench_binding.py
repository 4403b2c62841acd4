"""The contract between the serving runtime and the chip benchmark
(``chipbench/``), which builds the server from each configuration's
binding and reads it by name:

* ``chipbench/rehearse.py:bare_server`` builds the jitted steps on an
  object that skips the server's own set-up;
* the device trace is read by jit module name (``jit_tick_p`` ... in
  ``chipbench/metrics``), so each served program must lower under the
  name its metric reads;
* ``chipbench/cell.py`` wraps and reads server attributes by name.

Each configuration runs at its published widths with the depth cut to
one layer.  Nothing is allocated at that size: the programs are lowered
from ``ShapeDtypeStruct`` inputs, never compiled or run.  The attribute
check builds a real server at the configuration's smoke widths.
"""

import importlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.cell import load_module  # noqa: E402
from chipbench.rehearse import bare_server  # noqa: E402
from repro.configs import smoke  # noqa: E402
from repro.core.quantization import QuantizedWeightCache  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.layers import attach_quantized_weights  # noqa: E402
from repro.runtime.serve import ContinuousBatchingServer  # noqa: E402

CONFIGS = ["deepseek_7b", "minicpm3_4b"]

#: the programs the metrics read, by the Python name of the jitted step
PROGRAMS = ("tick_p", "pool_pass_p", "chunk_admit", "finish", "step_update")


def _binding(config: str, layers: int = 1):
    spec = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json").read_text())
    spec["num_hidden_layers"] = layers
    binding = load_module(ROOT / "chipbench" / "configs" / f"{config}.py",
                          f"binding_{config}")
    return binding.program_config(spec), binding.serving_config(spec)


@pytest.fixture(scope="module")
def bare():
    """``bare(config) -> (server, mcfg, scfg)`` at published widths, one
    layer; built once per configuration."""
    built = {}

    def get(config):
        if config not in built:
            mcfg, scfg = _binding(config)
            built[config] = (bare_server(mcfg, scfg), mcfg, scfg)
        return built[config]

    return get


def _lowering_args(srv, mcfg, scfg):
    """Shape-only arguments of each served program, as the serving loop
    passes them."""
    sd = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    shapes = jax.eval_shape(lambda k: init_params(mcfg, k), jax.random.PRNGKey(0))
    params = jax.eval_shape(
        lambda p: attach_quantized_weights(p, QuantizedWeightCache(), level="q16_16"), shapes)
    pool = srv.cache_ops
    state = jax.eval_shape(pool.alloc)
    B, C, L = scfg.n_slots, scfg.resolved_chunk, scfg.max_len
    tables = {gk: sd((B, g["nb"]), i32) for gk, g in pool.groups.items()}
    slot_tables = {gk: sd((g["nb"],), i32) for gk, g in pool.groups.items()}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return {
        "tick_p": (sd((), i32), params, sd((B,), i32), sd((B,), i32), state, tables,
                   sd((B,), bool), key, sd((B, L), i32), sd((B,), i32), sd((B, 2), f32)),
        "pool_pass_p": (sd((), i32), params, sd((B, 1), i32), sd((B,), i32), state, tables,
                        sd((B,), bool), sd((B, mcfg.vocab), f32)),
        "chunk_admit": (sd((), i32), params, sd((1, C), i32), sd((1, C), i32), state,
                        slot_tables, slot_tables, sd((), i32), sd((1,), i32), sd((1,), i32)),
        "finish": (sd((B, mcfg.vocab), f32), key),
        "step_update": (sd((B, L), i32), sd((B,), i32), sd((B,), i32), sd((B,), i32),
                        sd((B, 2), f32), sd((B,), i32), sd((B, 3), f32), sd((B,), bool)),
    }


@pytest.mark.parametrize("config", CONFIGS)
def test_bare_server_builds_the_served_steps(config, bare):
    """``rehearse.bare_server`` sets only what ``_build`` reads; the
    steps it yields are the ones the serving loop dispatches."""
    srv, mcfg, scfg = bare(config)
    assert mcfg.n_layers == 1
    assert isinstance(scfg.resolved_chunk, int)
    for name in PROGRAMS:
        assert callable(getattr(srv, f"_{name}")), name
    assert srv.level_names == ("q16_16", "f32")
    assert srv.cache_ops.groups and all("alloc" in g for g in srv.cache_ops.groups.values())


@pytest.mark.parametrize("config", CONFIGS)
def test_programs_lower_under_the_module_names_metrics_read(config, bare):
    srv, mcfg, scfg = bare(config)
    read = set()
    for metric in ("decode_step_ms", "prefill_chunk_ms"):
        read |= set(importlib.import_module(f"chipbench.metrics.{metric}").MODULES)
    assert read == {f"jit_{name}" for name in PROGRAMS}
    args = _lowering_args(srv, mcfg, scfg)
    for name in PROGRAMS:
        text = getattr(srv, f"_{name}").lower(*args[name]).as_text()
        assert text.startswith(f"module @jit_{name} "), (name, text[:80])


@pytest.mark.parametrize("config", CONFIGS)
def test_server_has_the_attributes_the_cell_reads(config):
    """Every attribute ``chipbench/cell.py`` and ``chipbench/loadgen.py``
    wrap or read, on a server built from the binding's serving config
    (at the configuration's smoke widths, so that it allocates little)."""
    _, scfg = _binding(config)
    cfg = smoke(config)
    srv = ContinuousBatchingServer(cfg, init_params(cfg, jax.random.PRNGKey(0)), scfg)
    for obj, attr in ((srv, "_admit"), (srv, "_finish_slot"), (srv, "_tick_p"),
                      (srv, "_pool_pass_p"), (srv, "_finish"), (srv, "_step_update"),
                      (srv, "_chunk_admit"), (srv.cache_ops, "ensure_rows"),
                      (srv.cache_ops, "device_tables"), (srv, "next_rid"), (srv, "serve"),
                      (srv, "metrics_snapshot"), (srv.scheduler, "admit"),
                      (srv.scheduler, "has_work"), (srv.scheduler, "advance"),
                      (srv.arbiter, "observe")):
        assert callable(getattr(obj, attr)), attr
    assert srv._m_decode_ticks.value() == 0
    assert srv._gen_buf.shape == (scfg.n_slots, scfg.max_len)
    assert srv.level_names == ("q16_16", "f32")
    assert srv.scfg.resolved_chunk == scfg.prefill_chunk
    assert all(g["alloc"].n_pages > 1 for g in srv.cache_ops.groups.values())
    assert "retrace_total" in srv.metrics_snapshot()
