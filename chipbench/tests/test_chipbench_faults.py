"""The comparison that decides ``correct`` catches what it must.

A whole run of the chat cell (the harness's look for a chip skipped) at a
tiny size on the CPU: widths cut, everything else -- traffic generator,
open loop, paged server at both rungs, reference, limits -- as on the
chip, the cell's own limits file included.  A sound run comes out
correct; with the served path broken underneath it does not:

* a decode step that returns its cache state unchanged;
* a token altered where it is sampled;
* the control: each rung's tokens replaced by what the reference ranks
  first one step below the rung's precision (``high`` for f32, ``int4``
  for q16_16).

Of the other faults a cell can have, a batch mean and an exchange
between chips do not exist in a one-chip serving cell.  The server is
built once for the module: building it compiles every step.
"""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cell_mod
from chipbench import compare

ROOT = Path(__file__).resolve().parents[1]
WORKLOAD = "ds7b-chat-mixed"
SEED = 2**31 + 99
SECONDS = 1.0


def tiny_spec():
    spec = json.loads((ROOT / "configs" / "deepseek_7b.json").read_text())
    spec.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)
    spec["serving"] = dict(spec["serving"], n_slots=4, max_len=128)
    return spec


def tiny_traffic():
    t = json.loads((ROOT / "traffic" / f"{WORKLOAD}.json").read_text())
    t.update(rate_rps=16.0, prompt=dict(t["prompt"], median=24, min=8, max=48),
             output=dict(t["output"], median=8, min=4, max=16))
    return t


@pytest.fixture(scope="module")
def built():
    c = cell_mod.Cell(WORKLOAD, json.loads((ROOT.parent / "BENCHMARK.json").read_text()),
                      tiny_spec(), tiny_traffic())
    from chipbench.loadgen import output_lengths

    return c, cell_mod.build_server(c, SEED, output_lengths(c.traffic, SECONDS),
                                    time.perf_counter(), lambda m: None)


def run(built, monkeypatch, fault=None):
    """One run of the harness on the module's server; ``fault(srv)``
    breaks its served path first (undone afterwards)."""
    c, (weights, srv) = built
    monkeypatch.setattr(cell_mod, "build_server", lambda *a, **k: (weights, srv))
    saved = {k: getattr(srv, k) for k in ("_tick_p", "_pool_pass_p", "_finish")}
    try:
        if fault is not None:
            fault(srv)
        return cell_mod.run_cell(WORKLOAD, SEED, SECONDS, False, t_start=time.perf_counter(),
                                 spec_override=c.spec, traffic_override=c.traffic,
                                 log=lambda m: None)
    finally:
        for k, v in saved.items():
            setattr(srv, k, v)


def test_sound_run_is_correct(built, monkeypatch):
    r = run(built, monkeypatch)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def test_state_left_unchanged_is_caught(built, monkeypatch):
    def fault(srv):
        def frozen(fn, at):
            def step(*args):
                before = jax.tree.map(jnp.copy, args[4])   # the paged state (donated)
                out = list(fn(*args))
                out[at] = before
                return tuple(out)
            return step
        srv._tick_p = frozen(srv._tick_p, 0)
        srv._pool_pass_p = frozen(srv._pool_pass_p, 1)

    r = run(built, monkeypatch, fault)
    assert not r["correct"], r["checks"]


def test_altered_token_is_caught(built, monkeypatch):
    def fault(srv):
        finish, vocab = srv._finish, srv.cfg.vocab

        def altered(logits, key):
            tok, hv = finish(logits, key)
            tok = (tok + 1) % vocab
            return tok, hv.at[:, 0].set(tok.astype(hv.dtype))
        srv._finish = altered

    r = run(built, monkeypatch, fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["q16_gap"]["value"] > r["checks"]["q16_gap"]["limit"]


def test_control_is_not_correct(built):
    """The control in the program's place, on the tokens of a sound run:
    each rung's control is judged against that rung's own limits only,
    and reads past at least one of them."""
    from chipbench.control import CONTROL, rung_limits
    from chipbench.loadgen import OpenLoop, schedule

    c, (weights, srv) = built
    plan = schedule(c.traffic, SEED + 1, SECONDS, c.mcfg.vocab)
    served = OpenLoop(srv, plan, SECONDS, "all").run()
    picks = compare.sample(served, srv.level_names, SEED, cell_mod.SAMPLE_TOKENS,
                           cell_mod.SAMPLE_REQUESTS)
    rows = c.traffic["output"]["max"]
    sound = compare.readings(c.reference, c.spec, weights, picks, c.scfg.max_len, rows)
    assert compare.judge(sound, c.limits, 0)[0], sound
    for lv, reqs in picks.items():
        ctl = compare.readings(c.reference, c.spec, weights, {lv: reqs}, c.scfg.max_len, rows,
                               control=CONTROL[lv])
        own = rung_limits(c.limits, lv)
        assert own, lv
        ok, checks = compare.judge(ctl, own, 0)
        assert not ok, (lv, checks)
        assert any(checks[k]["value"] is not None and checks[k]["value"] > own[k]["limit"]
                   for k in own), (lv, checks)
