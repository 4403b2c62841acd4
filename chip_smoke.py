#!/usr/bin/env python3
"""Bring-up run: serve deepseek_7b at its published widths on one TPU chip.

Every width is as published (d_model 4096, 32 x 128 heads with 32 KV
heads, d_ff 11008, vocab 102400); only the depth is cut (:data:`LAYERS`).
Weights are random from a fixed seed.  The run goes through the serving
entry points a user calls -- ``ContinuousBatchingServer``, its
scheduler and its ``PagedCachePool`` with chunked prefill -- in one
process, in two phases:

1. one request per slot, prompts of two lengths, ``f32`` and
   ``q16_16`` requests mixed in one batch;
2. the same mix plus one speculative request.

Checks, any of which fails the run: every served token lies in
``[0, vocab)``; every health signal the per-slot arbiters were fed was
finite; and one f32 request agrees, token by token, with
``prefill_step(mode="exact")`` re-run over the grown sequence (logits
compared, see :data:`AGREE_TOL`).

    python chip_smoke.py            # full width, one TPU chip, no arguments

Without a TPU it exits non-zero before any work.  The last line of its
standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "deepseek_7b"

#: The depth cut, from a memory reckoning at the cut below (described-
#: topology compiles of every serving step): weights are f32 (4 B/param)
#: plus int8 copies for the q16_16 rung, 1.01 GB a layer; embedding and
#: head 3.36 GB; the KV pool 0.40 GB a layer, of which the speculative
#: verify step holds about 3.5 copies as temporaries.  That step peaks at
#: 13.8 GB of 16 at 4 layers and would need about 16.4 at 5.
LAYERS = 4

#: Everything else the chip run uses: 8 slots x 1536 positions of f32 KV
#: (32 KB per token per layer), prompts of 256 and 1024 tokens, budgets
#: of 24 + 4i tokens for request i, 16-row pages and chunks, 4 draft
#: tokens per speculative round.
FULL_WIDTH_RUN = dict(slots=8, max_len=1536, prompt_lens=(256, 1024),
                      max_new=24, page_size=16, spec_k=4, seed=0)

#: Agreement tolerance, in units of the spread (standard deviation) of the
#: reference logits at that position.  The served token is the argmax of
#: the decode path's logits (cache read, one position per step); the
#: reference re-derives the logits through the prefill path (chunked
#: attention over the whole sequence, no cache read).  Both run the f32
#: rung, whose matmuls are traced at ``highest`` precision, so they differ
#: by f32 accumulation-order noise, far below 1e-3 of the spread.  Where
#: that noise ranks two near-tied tokens differently, the served token's
#: reference logit is within twice the noise of the reference maximum.
#: A token served from another rung (q16_16: int8 activations, about 1%
#: error per matmul), through a misplaced cache row, or by single-pass
#: bf16 matmuls (up to 1.09 spreads below on a v5e) differs from the
#: reference argmax by a sizeable fraction of the spread.  One hundredth
#: of the spread lies between the two.
AGREE_TOL = 0.01

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class SmokeFailure(RuntimeError):
    """A check of the bring-up run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's own compile events while open: tracing, lowering, and
    backend compilation (or loading from the persistent cache), plus
    persistent-cache hits."""

    def __init__(self):
        self.totals = {name: 0.0 for name in _COMPILE_EVENTS.values()}
        self.cache_hits = 0

    def _on_duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.totals[_COMPILE_EVENTS[event]] += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False


def watch_health(arbiter, seen: list) -> None:
    """Record every health signal the server feeds ``arbiter`` (finite
    flag and max |logit| of each active lane) before it acts on them."""
    import numpy as np

    observe = arbiter.observe

    def recording(step, nonfinite, amplitude, active=None, acceptance=None):
        act = (np.ones(np.shape(nonfinite), bool) if active is None
               else np.asarray(active, bool))
        seen.append((bool(np.asarray(nonfinite)[act].any()),
                     float(np.max(np.asarray(amplitude)[act], initial=0.0))))
        return observe(step, nonfinite, amplitude, active, acceptance)

    arbiter.observe = recording


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_bytes(key: str):
    """``memory_stats()[key]`` of device 0, or None where the backend
    reports none (the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get(key)


def live_bytes() -> int:
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


def serve_phase(name, cfg, params, scfg, specs, log):
    """Build a server, serve ``specs`` (prompt, max_new, level,
    speculative) and check its outputs.  Returns ({index: generated
    tokens}, report).  The server is dropped before returning."""
    from repro.runtime.scheduler import Request
    from repro.runtime.serve import ContinuousBatchingServer

    t0 = time.perf_counter()
    with CompileClock() as clock:
        srv = ContinuousBatchingServer(cfg, params, scfg)
        health: list = []
        watch_health(srv.arbiter, health)
        if srv.draft_arbiter is not None:
            watch_health(srv.draft_arbiter, health)
        reqs = [Request(rid=srv.next_rid(), prompt=p, max_new=n, level=lv,
                        speculative=spec) for p, n, lv, spec in specs]
        t1 = time.perf_counter()
        fins = srv.serve(reqs)
        t2 = time.perf_counter()
    snap = srv.metrics_snapshot()
    out = {}
    for i, (r, (p, n, _, _)) in enumerate(zip(reqs, specs)):
        f = fins[r.rid]
        gen = f.tokens[len(p):]
        check(f.n_generated == n and len(gen) == n,
              f"{name}: request {i} generated {f.n_generated} of {n} ({f.reason})")
        check(all(0 <= t < cfg.vocab for t in gen),
              f"{name}: request {i} served a token outside [0, {cfg.vocab})")
        out[i] = gen
    check(len(health) > 0, f"{name}: no health signal reached the arbiters")
    check(not any(bad for bad, _ in health),
          f"{name}: a non-finite logit reached the arbiter")
    report = {
        "phase": name,
        "requests": len(reqs),
        "tokens_served": int(sum(len(g) for g in out.values())),
        "build_s": t1 - t0,
        "serve_s": t2 - t1,
        **clock.totals,
        "persistent_cache_hits": clock.cache_hits,
        "retrace_total": snap["retrace_total"],
        "level_passes_total": snap["level_passes_total"],
        "prefill_chunks_total": snap["prefill_chunks_total"],
        "spec_accepted_total": snap["spec_accepted_total"],
        "spec_drafted_total": snap["spec_drafted_total"],
        "health_syncs": len(health),
        "max_abs_logit": max(a for _, a in health),
        "peak_bytes_in_use": device_bytes("peak_bytes_in_use"),
    }
    log(f"{name}: " + json.dumps(report))
    del srv, fins, reqs
    gc.collect()
    return out, report


def check_agreement(cfg, params, prompt, served, log) -> dict:
    """Re-derive each served token with an exact-mode prefill over the
    grown sequence (one compile per length) and require the served
    token's reference logit within ``AGREE_TOL`` spreads of the
    reference maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_caches, prefill_step
    from repro.runtime.config import SERVE_CACHE_DTYPE

    ref = jax.jit(lambda p, t, c: prefill_step(p, t, c, cfg, mode="exact"))
    seq = list(prompt)
    gaps, argmax_equal = [], 0
    t0 = time.perf_counter()
    with CompileClock() as clock:
        for tok in served:
            caches = init_caches(cfg, 1, len(seq), dtype=SERVE_CACHE_DTYPE)
            logits, _ = ref(params, jnp.asarray([seq], jnp.int32), caches)
            row = np.asarray(logits[0], np.float64)
            check(bool(np.isfinite(row).all()),
                  f"reference logits not finite at length {len(seq)}")
            gaps.append((row.max() - row[tok]) / row.std())
            argmax_equal += int(row.argmax() == tok)
            seq.append(tok)
    report = {
        "tokens": len(served),
        "max_gap_spreads": float(max(gaps)),
        "mean_gap_spreads": float(np.mean(gaps)),
        "argmax_equal": argmax_equal,
        "tolerance_spreads": AGREE_TOL,
        "wall_s": time.perf_counter() - t0,
        **clock.totals,
    }
    log("agreement: " + json.dumps(report))
    check(report["max_gap_spreads"] <= AGREE_TOL,
          f"served f32 token {int(np.argmax(gaps))} is "
          f"{report['max_gap_spreads']:.4g} logit spreads below the "
          f"re-derived maximum (tolerance {AGREE_TOL})")
    return report


def run_smoke(cfg, *, slots: int, max_len: int, prompt_lens, max_new: int,
              page_size: int, spec_k: int, seed: int, log=print) -> dict:
    """Both serving phases plus the agreement check on ``cfg``; raises
    :class:`SmokeFailure` (or whatever the serving path raised) on any
    failure.  Returns the per-phase reports."""
    import jax

    from repro.launch.serve import random_prompts
    from repro.models import init_params
    from repro.runtime.config import ServingConfig
    from repro.runtime.speculative import SpeculativeConfig

    log(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim} kv_heads={cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab}")
    log(f"cut: layers={cfg.n_layers} slots={slots} max_len={max_len} "
        f"prompt_lens={list(prompt_lens)} page_size={page_size} spec_k={spec_k}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(seed)))
    log(f"init: {time.perf_counter() - t0:.3f}s live_bytes={live_bytes()}")

    prompts = random_prompts(prompt_lens, cfg.vocab, seed)
    levels = ("f32", "q16_16")
    # one request per slot: lengths alternate, levels alternate in pairs,
    # budgets grow so the last request decodes alone (single-level tick).
    # Request 0 (f32, first prompt length) is the agreement request.
    mix = [(prompts[i % len(prompts)], max_new + 4 * i, levels[(i // 2) % 2], False)
           for i in range(slots)]

    plain = ServingConfig(n_slots=slots, max_len=max_len, seed=seed,
                          page_size=page_size)
    served, rep1 = serve_phase("phase1", cfg, params, plain, mix, log)
    log(f"after phase1: live_bytes={live_bytes()} "
        f"bytes_in_use={device_bytes('bytes_in_use')}")
    agree = check_agreement(cfg, params, mix[0][0], served[0], log)

    speculative = ServingConfig(
        n_slots=slots, max_len=max_len, seed=seed, page_size=page_size,
        speculative=SpeculativeConfig(k=spec_k, max_len=max_len),
    )
    spec_req = (mix[0][0], mix[0][1], None, True)
    served2, rep2 = serve_phase("phase2", cfg, params, speculative,
                                mix[1:] + [spec_req], log)
    spec_tokens = served2[len(mix) - 1]
    # observed, not checked: speculative output is specified to equal
    # vanilla f32 greedy, which request 0 of phase 1 served
    same = sum(a == b for a, b in zip(spec_tokens, served[0]))
    log(f"speculative vs phase1 f32 tokens: {same}/{len(spec_tokens)} equal")
    return {"phase1": rep1, "agreement": agree, "phase2": rep2,
            "spec_equal_tokens": same}


def main() -> int:
    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']}", file=sys.stderr)
        return 1
    from repro.kernels import default_interpret
    from repro.launch.serve import enable_compile_cache

    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    t0 = time.perf_counter()
    run_smoke(cfg, **FULL_WIDTH_RUN, log=lambda line: print(line, flush=True))
    print(f"total: {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
