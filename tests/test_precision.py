"""C4 validation: dispatch table, atomic O(1) switching, two-phase
barrier ordering, arbiter policy (paper §4; Table 1 'switch' row)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    ArbiterConfig,
    MathEngine,
    Mode,
    PrecisionArbiter,
    Q16_16,
    from_fixed,
    to_fixed,
)
from repro.core.barrier import TwoPhaseBarrier


def test_engine_default_opset():
    eng = MathEngine(Mode.PRECISE)
    ctx = eng.ctx()
    for op in ("mul", "add", "sub", "sin", "cos", "matmul"):
        assert op in ctx


def test_r1_api_stability_across_modes(rng):
    """R1: identical call sites in both modes; results agree within the
    Q16.16 error envelope."""
    eng = MathEngine(Mode.PRECISE)
    theta = np.float32(0.7)
    precise_sin = float(eng.call("sin", theta))
    eng.set_mode(Mode.FAST)
    fast_sin = float(eng.call("sin", theta))
    assert fast_sin == pytest.approx(precise_sin, abs=8e-4)


def test_r3_switch_is_o1_no_recompile():
    """R3: after the first build, set_mode must not trace/compile.
    We verify by checking the switch latency is microseconds-scale and
    constant-ish across repeats (a retrace would be milliseconds)."""
    eng = MathEngine(Mode.PRECISE)
    # warm both contexts
    eng.set_mode(Mode.FAST)
    eng.set_mode(Mode.PRECISE)
    lat = []
    for _ in range(20):
        lat.append(eng.set_mode(Mode.FAST))
        lat.append(eng.set_mode(Mode.PRECISE))
    med = sorted(lat)[len(lat) // 2]
    assert med < 5e3, f"switch median {med:.1f}us — not O(1)"  # generous CPU bound
    assert eng.switch_stats.count == 42


def test_no_mixed_precision_state():
    """A context captured before the switch keeps its mode (immutability);
    the active context after the switch is uniformly the new mode."""
    eng = MathEngine(Mode.PRECISE)
    before = eng.ctx()
    eng.set_mode(Mode.FAST)
    after = eng.ctx()
    assert before.mode is Mode.PRECISE and after.mode is Mode.FAST
    with pytest.raises(AttributeError):
        before.mode = Mode.FAST  # frozen


def test_set_mode_same_mode_is_noop():
    eng = MathEngine(Mode.FAST)
    assert eng.set_mode(Mode.FAST) == 0.0
    assert eng.switch_stats.count == 0


def test_barrier_ordering():
    events = []

    def fake_sync():
        events.append("sync")

    b = TwoPhaseBarrier(sync_fn=fake_sync)
    x = jnp.ones((8,)) * 3  # in-flight device value

    def swap():
        events.append("swap")

    ev = b.transition(inflight=x, swap_fn=swap)
    assert events == ["sync", "swap"], "phase 1 (quiesce+agree) must precede phase 2"
    assert ev.total_s >= ev.swap_s >= 0


def test_barrier_host_only_inflight_completes():
    """Host values (ints, floats, None leaves, mixed pytrees) have
    nothing to block on: the transition runs both phases."""
    events = []
    b = TwoPhaseBarrier(sync_fn=lambda: events.append("sync"))
    inflight = {"step": 3, "loss": 1.5, "aux": None, "mix": [np.ones(2), jnp.ones(2)]}
    b.transition(inflight=inflight, swap_fn=lambda: events.append("swap"))
    assert events == ["sync", "swap"]
    assert len(b.events) == 1


def test_barrier_propagates_error_raised_while_blocking():
    """A device error surfacing at the quiesce point must reach the
    caller, and the swap must not happen."""

    class FailingValue:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    swapped = []
    b = TwoPhaseBarrier(sync_fn=lambda: None)
    with pytest.raises(RuntimeError, match="device lost"):
        b.transition(inflight=[FailingValue()], swap_fn=lambda: swapped.append(1))
    assert swapped == [] and b.events == []


def test_compile_op_aot_paths():
    """AOT-compiled executables dispatch correctly in both modes."""
    eng = MathEngine(Mode.PRECISE)
    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)

    def precise_fn(x):
        return jnp.matmul(x, x)

    def fast_fn(x):
        from repro.core.linalg import qmatmul_deferred
        from repro.core.qformat import from_fixed, to_fixed

        q = to_fixed(x)
        return from_fixed(qmatmul_deferred(q, q))

    eng.compile_op("square", {Mode.PRECISE: precise_fn, Mode.FAST: fast_fn}, spec)
    x = np.random.default_rng(0).uniform(-1, 1, (16, 16)).astype(np.float32)
    precise = np.asarray(eng.call("square", x))
    eng.set_mode(Mode.FAST)
    fast = np.asarray(eng.call("square", x))
    np.testing.assert_allclose(fast, precise, atol=1e-2)
    # executables, not traced fns: calling with a wrong shape must fail
    with pytest.raises(Exception):
        eng.call("square", np.zeros((8, 8), np.float32))


# ---------------------------------------------------------------------------
# arbiter policy
# ---------------------------------------------------------------------------


def test_arbiter_nan_fallback():
    arb = PrecisionArbiter(ArbiterConfig(cooldown_steps=0))
    assert arb.mode is Mode.FAST
    for s in range(10):
        assert arb.observe(s, loss=2.0, grad_norm=1.0) is None
    assert arb.observe(10, loss=float("nan"), grad_norm=1.0) is Mode.PRECISE
    assert arb.mode is Mode.PRECISE


def test_arbiter_spike_fallback_and_promotion():
    cfg = ArbiterConfig(spike_factor=4.0, stable_steps=8, cooldown_steps=2)
    arb = PrecisionArbiter(cfg)
    step = 0
    for _ in range(16):
        arb.observe(step, loss=1.0, grad_norm=1.0)
        step += 1
    assert arb.observe(step, loss=1.0, grad_norm=100.0) is Mode.PRECISE
    step += 1
    # healthy steps -> promotion back to FAST after stable_steps
    promoted_at = None
    for _ in range(32):
        out = arb.observe(step, loss=0.9, grad_norm=1.0)
        if out is Mode.FAST:
            promoted_at = step
            break
        step += 1
    assert promoted_at is not None


def test_arbiter_cooldown_prevents_flapping():
    cfg = ArbiterConfig(spike_factor=2.0, stable_steps=1, cooldown_steps=50)
    arb = PrecisionArbiter(cfg)
    for s in range(16):
        arb.observe(s, loss=1.0, grad_norm=1.0)
    assert arb.observe(16, loss=1.0, grad_norm=50.0) is Mode.PRECISE
    # immediate stability must NOT promote within the cooldown window
    for s in range(17, 40):
        assert arb.observe(s, loss=1.0, grad_norm=1.0) is None
