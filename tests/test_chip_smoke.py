"""chip_smoke.py's control flow at smoke size on the CPU: both serving
phases and the agreement check, end to end, through the same function
the chip run calls.  The platform guard is tested on its own: on a CPU
backend the script exits non-zero and prints no result."""

import importlib.util
from pathlib import Path

import pytest

from repro.configs import smoke

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_smoke_end_to_end(chip_smoke):
    cfg = smoke("deepseek_7b")
    lines = []
    rep = chip_smoke.run_smoke(cfg, slots=4, max_len=64, prompt_lens=[5, 12],
                               max_new=4, page_size=8, spec_k=2, seed=0,
                               log=lines.append)
    p1, p2, agree = rep["phase1"], rep["phase2"], rep["agreement"]
    # budgets 4 + 4i per slot, every token served
    assert p1["tokens_served"] == p2["tokens_served"] == 4 + 8 + 12 + 16
    # both rungs ran in phase 1, through the mixed pass and the fused
    # tick, after chunked prefill, without speculation
    assert set(p1["level_passes_total"]) == {"level=f32", "level=q16_16"}
    assert {"step=pool_pass", "step=tick"} <= set(p1["retrace_total"])
    assert p1["prefill_chunks_total"] > 0
    assert p1["spec_drafted_total"] == 0
    # phase 2: chunked prefill and one speculative request
    assert p2["prefill_chunks_total"] > 0
    assert p2["spec_drafted_total"] > 0
    # on the CPU the f32 rung is f32: decode and prefill re-derivation
    # pick the same token at every position
    assert agree["tokens"] == 4 and agree["argmax_equal"] == 4
    assert agree["max_gap_spreads"] <= chip_smoke.AGREE_TOL
    assert any(line.startswith("cut: layers=2 slots=4 max_len=64") for line in lines)


def test_agreement_check_rejects_a_wrong_token(chip_smoke):
    """The check bites: the reference's least likely token, served in
    place of its argmax, fails the run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_caches, init_params, prefill_step

    cfg = smoke("deepseek_7b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = [3, 1, 4, 1, 5]
    logits, _ = prefill_step(params, jnp.asarray([prompt], jnp.int32),
                             init_caches(cfg, 1, len(prompt), dtype=jnp.float32),
                             cfg, mode="exact")
    worst = int(np.argmin(np.asarray(logits[0])))
    with pytest.raises(chip_smoke.SmokeFailure, match="below the re-derived maximum"):
        chip_smoke.check_agreement(cfg, params, prompt, [worst], log=lambda _: None)


def test_guard_refuses_cpu_backend(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
