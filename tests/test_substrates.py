"""Substrate integration tests: data determinism, checkpoint
atomicity/restart, trainer e2e (loss decreases, failure injection,
arbiter-driven precision switching), serving consistency."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _reference import teacher_forced
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import smoke
from repro.core.precision import Mode
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import decode_step, init_caches, init_params, train_loss
from repro.runtime.serve import ContinuousBatchingServer, ServingConfig
from repro.runtime.train_loop import InjectedFailure, Trainer, TrainerConfig


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_in_step():
    cfg = DataConfig(vocab=100, seq_len=32, global_batch=8)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1, b2 = d1.batch(7), d2.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d1.batch(7)["tokens"], d1.batch(8)["tokens"])


def test_data_host_sharding_partitions():
    full = SyntheticLM(DataConfig(vocab=50, seq_len=16, global_batch=8)).batch(3)
    parts = [
        SyntheticLM(DataConfig(vocab=50, seq_len=16, global_batch=8, num_hosts=4, host_id=h)).batch(3)
        for h in range(4)
    ]
    np.testing.assert_array_equal(np.concatenate([p["tokens"] for p in parts]), full["tokens"])


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones((5,), jnp.int32)}}
    ck.save(10, tree, blocking=True)
    out = ck.restore(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert ck.latest_step() == 10


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = {"x": jnp.zeros((2,))}
    ck.save(1, tree, blocking=True)
    # a crashed half-save must be invisible
    (tmp_path / "step_2.tmp").mkdir()
    (tmp_path / "step_5").mkdir()  # committed dir without manifest = corrupt
    assert ck.latest_step() == 1


def test_checkpoint_gc_keeps_newest(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        ck.save(s, tree, blocking=True)
    assert ck.all_steps() == [3, 4]


# ---------------------------------------------------------------------------
# trainer e2e
# ---------------------------------------------------------------------------


def _trainer(tmp_path, **kw):
    cfg = smoke("deepseek_7b")
    defaults = dict(total_steps=16, ckpt_every=8, ckpt_dir=str(tmp_path), log_every=100)
    defaults.update(kw)
    return Trainer(cfg, TrainerConfig(**defaults))


def test_train_loss_decreases(tmp_path):
    out = _trainer(tmp_path, total_steps=30).run()
    first = np.mean([h["loss"] for h in out["history"][:5]])
    last = np.mean([h["loss"] for h in out["history"][-5:]])
    assert last < first - 0.1, (first, last)


def test_failure_injection_and_bitwise_resume(tmp_path):
    with pytest.raises(InjectedFailure):
        _trainer(tmp_path, total_steps=16, ckpt_every=4, crash_at_step=10).run()
    # restart picks up from the last committed checkpoint (step 7)
    t2 = _trainer(tmp_path, total_steps=16, ckpt_every=4)
    assert t2.start_step == 8
    out2 = t2.run()

    # reference: uninterrupted run with identical config/seed
    ref = _trainer(str(tmp_path) + "_ref", total_steps=16, ckpt_every=4).run()
    resumed = {h["step"]: h["loss"] for h in out2["history"]}
    reference = {h["step"]: h["loss"] for h in ref["history"]}
    for s in range(10, 16):
        assert resumed[s] == pytest.approx(reference[s], rel=1e-5), s


def test_arbiter_switches_on_injected_nan(tmp_path):
    t = _trainer(tmp_path, total_steps=12, use_arbiter=True, start_mode=Mode.FAST)
    # sabotage: force a NaN loss observation mid-run via arbiter API
    t.arbiter.observe(0, float("nan"), 1.0)
    assert t.arbiter.mode is Mode.PRECISE
    out = t.run()
    assert out["history"][-1]["mode"] in ("fast", "precise")


def test_trainer_mode_switch_preserves_training(tmp_path):
    t = _trainer(tmp_path, total_steps=20, start_mode=Mode.PRECISE)
    # manual mid-run switch: run 10 steps, switch, run 10 more
    t.tcfg.total_steps = 10
    t.run()
    latency_us = t.engine.set_mode(Mode.FAST)
    assert latency_us >= 0
    t.tcfg.total_steps = 20
    t.start_step = 10
    out = t.run()
    modes = {h["mode"] for h in out["history"]}
    assert "fast" in modes
    assert np.isfinite(out["final_loss"])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_serving_matches_teacher_forcing():
    """Greedy decode through the cache must equal argmax of the full
    forward at each position (prefill/decode correctness).  The
    reference runs mode="exact" — the serving mode the server's f32
    level maps to (SERVE_STEP_LEVELS)."""
    cfg = smoke("deepseek_7b")
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompt = list(range(1, 9))
    srv = ContinuousBatchingServer(cfg, params, ServingConfig(n_slots=1, max_len=64))
    out = srv.generate([prompt], max_new=6, level="f32")[0]

    # teacher-forced reference: repeatedly run prefill on the growing
    # sequence (no cache reuse) and take argmax
    seq = teacher_forced(cfg, params, prompt, 6, "exact")
    assert out == seq, (out, seq)


@pytest.mark.parametrize(
    "arch",
    [
        "gemma2_2b",
        "mixtral_8x22b",
        "mamba2_1_3b",
        # jamba un-xfailed: the hybrid divergence was bf16 rounding of
        # an O(1e3) residual stream amplifying shape-dependent gemm
        # noise (one bf16 ulp = 8 at that magnitude); serving now runs
        # the f32 "exact" mode + f32 caches, so decode agrees with
        # prefill re-derivation across all families.
        "jamba_v01_52b",
        "minicpm3_4b",
    ],
)
def test_serving_decode_consistency_all_families(arch):
    """Same check across attention variants (SWA rolling cache,
    local-global, MoE, SSD recurrence, hybrid, MLA absorbed decode).
    Page size 8 divides the smoke configs' 8-row sliding windows."""
    cfg = smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompt = list(range(2, 12))
    srv = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=1, max_len=64, page_size=8))
    out = srv.generate([prompt], max_new=4, level="f32")[0]

    seq = teacher_forced(cfg, params, prompt, 4, "exact")
    assert out == seq, (arch, out, seq)


@pytest.mark.parametrize("mode,highest", [("exact", True), ("fast", False), ("precise", False)])
def test_exact_rung_matmuls_traced_at_highest(mode, highest):
    """Every matmul of an exact-mode serving step carries ``highest``
    precision (a TPU otherwise runs an f32 matmul as one bf16 pass); no
    matmul of the other rungs does."""
    cfg = smoke("deepseek_7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok, pos = jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32)
    caches = init_caches(cfg, 2, 16, dtype=jnp.float32)
    text = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, cfg, mode=mode)).lower(
        params, tok, pos, caches).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots
    assert [("HIGHEST" in line) for line in dots] == [highest] * len(dots)
