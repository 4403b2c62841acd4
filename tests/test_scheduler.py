"""Continuous-batching serving engine tests: scheduler admission/
eviction invariants, per-slot arbiter hysteresis, slot isolation (reuse
never leaks KV/SSM state across requests), and the mixed-precision
contract (per-slot levels behave identically to running each request
alone at its level)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _pbt import given, settings, strategies as st
from _reference import teacher_forced
from repro.configs import smoke
from repro.core.arbiter import SlotArbiter, SlotArbiterConfig
from repro.runtime.scheduler import ContinuousScheduler, Request
from repro.models import init_params
from repro.runtime.serve import (
    ContinuousBatchingServer,
    ContinuousServerConfig,
    SERVE_STEP_LEVELS,
)


# ---------------------------------------------------------------------------
# scheduler (pure host logic)
# ---------------------------------------------------------------------------


def _req(rid, plen=4, max_new=4, level=None):
    return Request(rid=rid, prompt=list(range(1, plen + 1)), max_new=max_new, level=level)


def test_scheduler_fifo_admission_and_slot_binding():
    s = ContinuousScheduler(n_slots=2, max_len=32)
    for i in range(5):
        s.submit(_req(i))
    admitted = s.admit()
    assert [(slot, r.rid) for slot, r in admitted] == [(0, 0), (1, 1)]
    assert s.admit() == []                      # no free slots
    assert s.active_slots() == [0, 1]
    # finish slot 1 -> rid 2 (not 3) takes its place: FIFO
    assert s.advance(1) is None
    s.advance(1); s.advance(1)
    assert s.advance(1) == "max_new"
    s.finish(1, [9, 9, 9, 9], "max_new")
    admitted = s.admit()
    assert [(slot, r.rid) for slot, r in admitted] == [(1, 2)]


def test_scheduler_every_request_finishes_exactly_once():
    s = ContinuousScheduler(n_slots=3, max_len=64)
    for i in range(7):
        s.submit(_req(i, max_new=2 + i % 3))
    while s.has_work():
        s.admit()
        for slot in s.active_slots():
            reason = s.advance(slot)
            if reason is not None:
                n = s.n_generated(slot)
                s.finish(slot, [0] * n, reason)
    assert sorted(s.finished) == list(range(7))
    for i in range(7):
        assert s.finished[i].n_generated == 2 + i % 3


def test_scheduler_termination_reasons():
    s = ContinuousScheduler(n_slots=1, max_len=8, eos_id=99)
    s.submit(_req(0, plen=4, max_new=10))
    s.admit()
    assert s.advance(0, eos=False) is None
    assert s.advance(0, eos=True) == "eos"      # EOS beats budget
    s.finish(0, [1, 99], "eos")
    # max_len: prompt 4 + generated hits the window
    s.submit(_req(1, plen=6, max_new=10))
    s.admit()
    assert s.advance(0) is None                 # pos 7
    assert s.advance(0) == "max_len"            # pos 8 == max_len
    s.finish(0, [1, 2], "max_len")


def test_scheduler_rejects_bad_requests():
    s = ContinuousScheduler(n_slots=1, max_len=8)
    s.submit(_req(0))
    with pytest.raises(ValueError):
        s.submit(_req(0))                       # duplicate rid
    with pytest.raises(ValueError):
        s.submit(_req(1, plen=8))               # prompt fills the window
    with pytest.raises(ValueError):
        Request(rid=2, prompt=[], max_new=4)    # empty prompt
    with pytest.raises(ValueError):
        Request(rid=3, prompt=[1], max_new=0)   # no budget


# ---------------------------------------------------------------------------
# scheduler under random churn (property-based)
# ---------------------------------------------------------------------------
#
# A seeded driver throws random admission/eviction/escalation traffic at
# the scheduler and checks the invariants its docstring promises hold at
# EVERY step, not just on the happy path the unit tests walk.


def _run_churn(n_slots: int, n_requests: int, seed: int, max_len: int = 16):
    """Drive one random serving episode; assert step-level invariants;
    return (scheduler, requests, admission_order)."""
    rng = np.random.default_rng(seed)
    levels = ("q16_16", "f32")
    s = ContinuousScheduler(n_slots=n_slots, max_len=max_len, eos_id=99,
                            levels=levels)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(1, max_len - 1))
        reqs.append(Request(
            rid=i, prompt=[int(t) for t in rng.integers(0, 50, plen)],
            max_new=int(rng.integers(1, 6)),
            level=[None, *levels][int(rng.integers(0, 3))],
        ))
        s.submit(reqs[-1])

    admit_order = []
    live = {}                                     # slot -> rid (our shadow table)
    steps = 0
    while s.has_work():
        steps += 1
        assert steps < 10_000, "scheduler livelock"
        for slot, r in s.admit():
            assert slot not in live, "slot double-booked"   # no cache-row leak
            live[slot] = r.rid
            admit_order.append(r.rid)
        for slot in list(s.active_slots()):
            assert live[slot] == s.request_at(slot).rid     # binding is stable
            if rng.random() < 0.7:                # decode progress is ragged
                reason = s.advance(slot, eos=bool(rng.random() < 0.1))
                assert s.position(slot) <= max_len
                if reason is not None:            # eviction frees the row
                    n = s.n_generated(slot)
                    s.finish(slot, [0] * n, reason)
                    del live[slot]
    return s, reqs, admit_order


@settings(max_examples=20)
@given(st.integers(1, 4), st.integers(1, 12), st.integers(0, 10**6))
def test_scheduler_churn_invariants(n_slots, n_requests, seed):
    """Under arbitrary churn: FIFO admission, every request finished
    exactly once with a sane token count, and every slot freed."""
    s, reqs, admit_order = _run_churn(n_slots, n_requests, seed)
    assert admit_order == sorted(admit_order)     # FIFO fairness
    assert len(admit_order) == len(reqs)          # nobody starved
    assert sorted(s.finished) == list(range(len(reqs)))
    assert s.slots == [None] * n_slots            # all rows released
    for req in reqs:
        f = s.finished[req.rid]
        assert 1 <= f.n_generated <= req.max_new
        assert len(f.tokens) == len(req.prompt) + f.n_generated
        assert f.reason in ("eos", "max_new", "max_len")
        if f.reason == "max_len":
            assert len(f.tokens) == s.max_len
        if f.reason == "max_new":
            assert f.n_generated == req.max_new


@settings(max_examples=10)
@given(st.integers(1, 3), st.integers(0, 10**6))
def test_scheduler_rid_reuse_after_pop(n_slots, seed):
    """pop_finished releases the rid: the same id can be resubmitted
    and the second life is bookkept independently of the first."""
    s, reqs, _ = _run_churn(n_slots, 5, seed)
    for req in reqs:
        fin = s.pop_finished(req.rid)
        assert fin.rid == req.rid
    assert s.finished == {} and s._submitted == set()   # state fully drained
    s.submit(Request(rid=reqs[0].rid, prompt=[1, 2], max_new=1))
    s.admit()
    assert s.advance(0) == "max_new"
    assert s.finish(0, [7], "max_new").n_generated == 1


# ---------------------------------------------------------------------------
# per-slot arbiter
# ---------------------------------------------------------------------------


def test_slot_arbiter_nan_jumps_to_top_and_demotes_to_floor():
    cfg = SlotArbiterConfig(n_levels=3, start_idx=0, stable_steps=2, cooldown_steps=2)
    arb = SlotArbiter(4, cfg)
    arb.reset_slot(1, start_idx=1)              # slot 1's floor is rung 1
    nonf = np.array([True, True, False, False])
    idx = arb.observe(0, nonfinite=nonf, amplitude=np.zeros(4))
    assert list(idx) == [2, 2, 0, 0]            # NaN slots rescue to top, no cooldown
    # healthy steps demote one rung at a time — but never below floor
    step = 1
    for _ in range(20):
        idx = arb.observe(step, nonfinite=np.zeros(4, bool), amplitude=np.zeros(4))
        step += 1
    assert list(idx) == [0, 1, 0, 0]            # slot 1 stops at its floor


def test_slot_arbiter_amplitude_escalates_with_cooldown():
    cfg = SlotArbiterConfig(n_levels=3, start_idx=0, amp_threshold=10.0,
                            stable_steps=100, cooldown_steps=4)
    arb = SlotArbiter(2, cfg)
    amp = np.array([100.0, 0.0])
    idx = arb.observe(0, nonfinite=np.zeros(2, bool), amplitude=amp)
    assert list(idx) == [1, 0]                  # one rung, not a jump
    idx = arb.observe(1, nonfinite=np.zeros(2, bool), amplitude=amp)
    assert list(idx) == [1, 0]                  # cooldown blocks the next rung
    idx = arb.observe(5, nonfinite=np.zeros(2, bool), amplitude=amp)
    assert list(idx) == [2, 0]                  # cooled: next rung


def _acc_cfg(**kw):
    base = dict(n_levels=3, start_idx=0, accept_threshold=0.5,
                accept_patience=3, cooldown_steps=1, stable_steps=10**6)
    base.update(kw)
    return SlotArbiterConfig(**base)


def _quiet(n):
    return dict(nonfinite=np.zeros(n, bool), amplitude=np.zeros(n))


def test_slot_arbiter_acceptance_escalates_after_patience():
    """Sustained low draft acceptance steps the rung up — but only
    after accept_patience consecutive low measurements, and one healthy
    measurement resets the counter (no single-round flapping)."""
    arb = SlotArbiter(2, _acc_cfg())
    low = np.array([0.2, 0.9])
    for step in range(2):
        assert list(arb.observe(step, **_quiet(2), acceptance=low)) == [0, 0]
    # third consecutive low measurement trips the escalation
    assert list(arb.observe(2, **_quiet(2), acceptance=low)) == [1, 0]
    assert arb.switches[-1][-1] == "acceptance"
    # counter was reset by the switch: two lows don't re-trip...
    assert list(arb.observe(3, **_quiet(2), acceptance=low)) == [1, 0]
    assert list(arb.observe(4, **_quiet(2), acceptance=low)) == [1, 0]
    # ...and a good round mid-run resets the count entirely
    arb.observe(5, **_quiet(2), acceptance=np.array([0.8, 0.9]))
    assert list(arb.observe(6, **_quiet(2), acceptance=low)) == [1, 0]
    assert list(arb.observe(7, **_quiet(2), acceptance=low)) == [1, 0]
    assert list(arb.observe(8, **_quiet(2), acceptance=low)) == [2, 0]


def test_slot_arbiter_acceptance_cooldown_hysteresis():
    """With a long cooldown, a slot that just escalated must sit out
    the window even when low measurements keep accumulating."""
    arb = SlotArbiter(1, _acc_cfg(accept_patience=1, cooldown_steps=5))
    low = np.array([0.0])
    assert list(arb.observe(0, **_quiet(1), acceptance=low)) == [1]
    for step in range(1, 5):                     # inside the cooldown window
        assert list(arb.observe(step, **_quiet(1), acceptance=low)) == [1], step
    assert list(arb.observe(5, **_quiet(1), acceptance=low)) == [2]  # cooled


def test_slot_arbiter_acceptance_never_demotes_below_floor():
    """Acceptance is an ESCALATION-only signal: perfect acceptance never
    drops a slot below the rung its request asked for, and demotion (on
    stability) still stops at the floor."""
    arb = SlotArbiter(1, _acc_cfg(stable_steps=2, cooldown_steps=1))
    arb.reset_slot(0, start_idx=1)               # requested floor: rung 1
    perfect = np.array([1.0])
    for step in range(12):
        idx = arb.observe(step, **_quiet(1), acceptance=perfect)
        assert idx[0] >= 1, step                 # never below the floor
    assert arb.idx[0] == 1


def test_slot_arbiter_nan_rescue_takes_precedence_over_acceptance():
    """A non-finite logit on the same step as a tripped acceptance
    counter: the NaN rescue wins (correctness beats throughput) — jump
    to the TOP rung, reason 'non-finite', no one-rung step."""
    arb = SlotArbiter(1, _acc_cfg(accept_patience=1))
    idx = arb.observe(0, nonfinite=np.array([True]), amplitude=np.zeros(1),
                      acceptance=np.array([0.0]))
    assert list(idx) == [2]                      # top, not start+1
    assert arb.switches[-1][-1] == "non-finite"


def test_slot_arbiter_unmeasured_acceptance_leaves_counter_untouched():
    """NaN / negative acceptance marks 'no measurement this step'
    (vanilla lanes, inactive slots): the low-counter neither grows nor
    resets, so patience accumulates only over REAL measurements."""
    arb = SlotArbiter(1, _acc_cfg())
    low, nomeas = np.array([0.1]), np.array([np.nan])
    arb.observe(0, **_quiet(1), acceptance=low)
    arb.observe(1, **_quiet(1), acceptance=low)          # counter: 2
    for step in range(2, 6):                             # gaps don't reset it
        assert list(arb.observe(step, **_quiet(1), acceptance=nomeas)) == [0]
        assert list(arb.observe(step, **_quiet(1), acceptance=np.array([-1.0]))) == [0]
    assert list(arb.observe(6, **_quiet(1), acceptance=low)) == [1]  # 3rd real low
    assert arb.switches[-1][-1] == "acceptance"


def test_slot_arbiter_reset_clears_acceptance_counter():
    """A new request admitted into the slot must not inherit the
    previous request's low-acceptance streak."""
    arb = SlotArbiter(1, _acc_cfg())
    low = np.array([0.0])
    arb.observe(0, **_quiet(1), acceptance=low)
    arb.observe(1, **_quiet(1), acceptance=low)
    arb.reset_slot(0)
    for step in range(2, 4):                     # two lows: still under patience
        assert list(arb.observe(step, **_quiet(1), acceptance=low)) == [0], step
    assert list(arb.observe(4, **_quiet(1), acceptance=low)) == [1]


def test_slot_arbiter_reset_slot_isolates_state():
    arb = SlotArbiter(2, SlotArbiterConfig(n_levels=2, start_idx=0))
    arb.observe(0, nonfinite=np.array([True, False]), amplitude=np.zeros(2))
    assert list(arb.idx) == [1, 0]
    arb.reset_slot(0)                           # new request takes the slot
    assert list(arb.idx) == [0, 0]
    with pytest.raises(ValueError):
        arb.reset_slot(0, start_idx=5)


# ---------------------------------------------------------------------------
# serving engine (device integration)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke("deepseek_7b")
    params = init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


@pytest.fixture(scope="module")
def small_server(small_model):
    """One compiled 2-slot server on ``small_model``, shared by the tests
    that only need some server of that shape.  Every request they send
    finishes, and they assert on their own requests and on counter
    differences."""
    cfg, params = small_model
    return ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=2, max_len=64)
    )


def test_continuous_matches_teacher_forcing_under_churn(small_model, small_server):
    """More requests than slots, mixed lengths and budgets: every
    request's greedy output must equal its teacher-forced reference —
    admission order, slot reuse and lock-step-free eviction must be
    invisible to each request."""
    cfg, params = small_model
    srv = small_server
    prefills = srv.stats["prefills"]
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [4, 5, 6], [9, 8, 7, 6, 5], [2, 2, 2, 2, 2, 2]]
    budgets = [3, 6, 2, 5]
    reqs = [Request(rid=srv.next_rid(), prompt=p, max_new=n)
            for p, n in zip(prompts, budgets)]
    fins = srv.serve(reqs)
    assert srv.stats["prefills"] - prefills == 4
    for r, p, n in zip(reqs, prompts, budgets):
        assert fins[r.rid].tokens == teacher_forced(
            cfg, params, p, n, dict(SERVE_STEP_LEVELS)["f32"]), r.rid
        assert fins[r.rid].reason == "max_new"


def test_slot_reuse_never_leaks_state(small_model):
    """A request admitted into a RECYCLED slot (after another request
    lived and died there) must produce exactly what it produces in a
    fresh server — KV rows, pos sentinels, SSM state must not leak."""
    cfg, params = small_model
    late = [7, 3, 7, 3, 7]
    # churned server: one slot, three requests through it; 'late' last
    srv = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=1, max_len=64)
    )
    churned = srv.generate([[5, 5, 5, 5, 5, 5], [11, 12, 13], late], max_new=5)[-1]
    fresh = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=1, max_len=64)
    ).generate([late], max_new=5)[0]
    assert churned == fresh


@pytest.mark.parametrize("arch", ["deepseek_7b", "jamba_v01_52b"])
def test_mixed_levels_identical_to_alone(arch):
    """THE per-request-precision contract: a batch mixing q16_16 and
    f32 slots gives every request exactly the tokens it gets when
    served alone at its level (row-independent lanes + traced-index
    dispatch; includes the hybrid SSM+attention family)."""
    cfg = smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(4))
    pa, pb = [1, 2, 3, 4, 5, 6], [9, 8, 7, 6]

    srv = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=2, max_len=64)
    )
    fins = srv.serve([
        Request(rid=0, prompt=pa, max_new=4, level="f32"),
        Request(rid=1, prompt=pb, max_new=4, level="q16_16"),
    ])
    assert srv.stats["level_passes"] == 2 * srv.stats["decode_steps"]  # mixed batch

    # served alone: one request at a time through the same compiled
    # server, the other slot empty
    alone_a = srv.serve([Request(rid=0, prompt=pa, max_new=4, level="f32")])[0]
    alone_b = srv.serve([Request(rid=1, prompt=pb, max_new=4, level="q16_16")])[1]
    assert fins[0].tokens == alone_a.tokens
    assert fins[1].tokens == alone_b.tokens
    assert alone_a.tokens != alone_b.tokens  # distinct requests, sanity


def test_masked_lane_cache_magnitude_cannot_perturb_members(small_model):
    """Regression (review finding, confirmed): a non-member lane's LIVE
    cache must not perturb a member's logits.  Before the pristine
    cache view, a masked lane attended to its own cache (q=0 still
    averages the cached V rows), re-acquired nonzero activations, and
    leaked into the FAST path's per-tensor activation exponents — the
    isolation contract silently depended on neighbor magnitudes."""
    cfg, params = small_model
    srv = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=2, max_len=64)
    )
    srv.scheduler.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new=8, level="q16_16"))
    for slot, req in srv.scheduler.admit():
        srv._admit(slot, req)

    def plant(node, value):
        """Fill slot 1's cache rows with large live-looking content
        (valid slot positions, huge payloads)."""
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "pos":  # (n_periods, B, L) -> valid positions 0..L-1
                    out[k] = v.at[:, 1].set(jnp.arange(v.shape[2], dtype=v.dtype)[None, :])
                else:
                    out[k] = plant(v, value)
            return out
        return node.at[:, 1].set(jnp.full(node.shape[2:], value, node.dtype))

    mask = jnp.asarray(np.array([True, False]))
    li = jnp.int32(srv.level_names.index("q16_16"))

    def run(pool):
        logits, _ = srv._pool_pass(
            li, srv.params, srv._tok[:, None], srv._pos, pool, mask,
            srv._zero_logits,
        )
        return np.asarray(logits[0])

    base = jax.tree.map(jnp.copy, srv.pool)
    l_clean = run(jax.tree.map(jnp.copy, base))
    l_dirty = run(plant(jax.tree.map(jnp.copy, base), 5000.0))
    np.testing.assert_array_equal(l_clean, l_dirty)


def test_unknown_level_rejected_before_slot_binding(small_server):
    """Regression (review finding): an invalid Request.level must fail
    at submission — before a slot is bound — and leave the server fully
    usable (no zombie slot entries, no stranded predecessors)."""
    srv = small_server
    good = Request(rid=0, prompt=[1, 2, 3], max_new=2)
    bad = Request(rid=1, prompt=[4, 5], max_new=2, level="q8_8")  # not a serve level
    with pytest.raises(ValueError, match="unknown level"):
        srv.serve([good, bad])
    assert not srv.scheduler.has_work()          # nothing stranded
    with pytest.raises(ValueError, match="duplicate request id"):
        srv.serve([good, Request(rid=0, prompt=[9], max_new=1)])
    outs = srv.generate([[1, 2, 3]], max_new=2)  # server still healthy
    assert len(outs[0]) == 5


def test_server_lifetime_state_is_bounded(small_server):
    """serve() hands results out and drops them from the scheduler — a
    long-lived server must not accumulate per-request state forever."""
    srv = small_server
    for _ in range(3):
        srv.generate([[1, 2, 3], [4, 5]], max_new=2)
    assert srv.scheduler.finished == {}
    assert srv.scheduler._submitted == set()


def test_arbiter_escalates_slot_mid_request(small_model):
    """Per-request precision is ADAPTIVE: with an impossible amplitude
    threshold every health sync escalates the slot one rung, so a
    q16_16 request finishes at f32 — switched via the traced index
    with zero retraces (the same compiled tick serves both levels)."""
    cfg, params = small_model
    srv = ContinuousBatchingServer(
        cfg, params,
        ContinuousServerConfig(
            n_slots=1, max_len=64, health_sync_every=2,
            default_level="q16_16",
            arbiter=SlotArbiterConfig(
                n_levels=len(SERVE_STEP_LEVELS), amp_threshold=-1.0,
                cooldown_steps=1, stable_steps=10**6,
            ),
        ),
    )
    fins = srv.serve([Request(rid=0, prompt=[1, 2, 3, 4], max_new=10)])
    assert fins[0].n_generated == 10
    assert srv.arbiter.idx[0] == len(SERVE_STEP_LEVELS) - 1   # escalated to top
    assert any(reason == "amplitude" for *_, reason in srv.arbiter.switches)
    # both levels ran within one request's decode
    assert srv.stats["level_passes"] == srv.stats["decode_steps"]


def test_eos_mode_budgets_and_eviction(small_model):
    """EOS mode (per-step token pull): unlikely EOS id -> budgets still
    bound every request; an EOS id that CAN be sampled terminates early
    with reason 'eos' and the slot is refilled."""
    cfg, params = small_model
    srv = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=2, max_len=64, eos_id=127)
    )
    reqs = [Request(rid=srv.next_rid(), prompt=[1, 2, 3], max_new=4),
            Request(rid=srv.next_rid(), prompt=[7, 7], max_new=3)]
    fins = srv.serve(reqs)
    for r in reqs:
        f = fins[r.rid]
        assert f.reason in ("eos", "max_new")
        assert f.n_generated <= r.max_new
        if f.reason == "eos":
            assert f.tokens[-1] == 127
