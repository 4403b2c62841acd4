"""Benchmarks mirroring the paper's Table 1 + §6 experiments, CPU-host
edition.  Wall-clock numbers are CPU proxies (the target is TPU v5e and
cycle-exact MCU numbers do not transfer); the *relationships* the paper
claims — error bounds, determinism, crossover structure, O(1) switch —
are what each benchmark checks and reports.

Emits ``name,us_per_call,derived`` CSV rows like every other bench.
"""

from __future__ import annotations

import math
import time

import numpy as np
import jax
import jax.numpy as jnp


def _bench(fn, *args, warmup=3, iters=20, repeats=5):
    """median of `repeats` timing blocks — single-core wall clock on a
    shared host is noisy; medians keep the paper-table relations stable."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters * 1e6)
    return sorted(times)[len(times) // 2]


def bench_trig():
    """Paper Table 1 rows sin/cos: CORDIC vs libm, plus max abs error
    and bit-determinism (the TPU analogue of Determinism Score)."""
    from repro.core.cordic import cordic_sincos, cordic_sincos_q16
    from repro.core.qformat import Q16_16, to_fixed

    theta = np.linspace(-math.pi, math.pi, 65536).astype(np.float32)
    t_fast = _bench(lambda x: cordic_sincos(x)[0], theta)
    t_std = _bench(lambda x: jnp.sin(x), theta)
    s, _ = cordic_sincos(theta)
    err = float(np.max(np.abs(np.asarray(s) - np.sin(theta))))

    tq = to_fixed(theta, Q16_16)
    s1, c1 = cordic_sincos_q16(tq)
    s2, c2 = cordic_sincos_q16(tq)
    det = float(np.mean(np.asarray(s1) == np.asarray(s2)))
    rows = [
        ("trig.cordic_sin_64k", t_fast, f"max_err={err:.2e}"),
        ("trig.libm_sin_64k", t_std, f"speed_ratio={t_std / t_fast:.3f}"),
        ("trig.determinism", 0.0, f"bitwise_det={det:.4f} (paper: 0.994 timing-det)"),
    ]
    return rows


def bench_universal_family(n: int = 65536):
    """Beyond the paper's Table 1: the universal-CORDIC transcendental
    family (Walther modes) vs the jnp float path — wall clock plus the
    documented error-bound check for each op (core/cordic.py docstring)."""
    from repro.core import cordic as cd
    from repro.core.qformat import Q16_16, to_fixed

    rng = np.random.default_rng(42)
    rows = []

    y = rng.uniform(-100, 100, n).astype(np.float32)
    x = rng.uniform(-100, 100, n).astype(np.float32)
    yq, xq = to_fixed(y, Q16_16), to_fixed(x, Q16_16)
    t_q = _bench(lambda a, b: cd.atan2_q16(a, b), yq, xq)
    t_f = _bench(lambda a, b: jnp.arctan2(a, b), jnp.asarray(y), jnp.asarray(x))
    err = float(np.max(np.abs(
        np.asarray(cd.atan2_q16(yq, xq), np.int64) / 65536.0
        - np.arctan2(np.asarray(yq, np.int64) / 65536.0, np.asarray(xq, np.int64) / 65536.0)
    )))
    rows.append((f"univ.atan2_{n//1024}k", t_q, f"jnp_us={t_f:.1f},max_err={err:.2e} (bound 1e-4)"))

    # linear-vectoring division (ROADMAP div_q16): normalized error vs
    # the documented 2^-15 * (1 + |q|) bound
    den = np.where(np.abs(x) < 1e-3, np.float32(1.0), x)
    yq2, dq = to_fixed(y, Q16_16), to_fixed(den, Q16_16)
    t_q = _bench(lambda a, b: cd.div_q16(a, b), yq2, dq)
    t_f = _bench(lambda a, b: a / b, jnp.asarray(y), jnp.asarray(den))
    got = np.asarray(cd.div_q16(yq2, dq), np.int64) / 65536.0
    want = (np.asarray(yq2, np.int64) / 65536.0) / (np.asarray(dq, np.int64) / 65536.0)
    ok = np.abs(want) < 32767
    err = float(np.max(np.abs(got - want)[ok] / (2.0 ** -15 * (1.0 + np.abs(want[ok])))))
    rows.append((f"univ.div_{n//1024}k", t_q,
                 f"jnp_us={t_f:.1f},err_vs_bound={err:.2f} (must be <= 1)"))

    # (op, fast, precise, inputs, relative?, documented bound) — sqrt and
    # exp have RELATIVE bounds, so their reported error is normalized by
    # the oracle; the rest report max absolute error.
    unary = [
        ("sqrt", cd.sqrt_q16, jnp.sqrt, rng.uniform(0.01, 30000.0, n), True, "rel 3e-5"),
        ("exp", cd.exp_q16, jnp.exp, rng.uniform(-10.0, 10.0, n), True, "rel 6e-5"),
        ("log", cd.log_q16, jnp.log, rng.uniform(0.01, 30000.0, n), False, "abs 8e-5"),
        ("tanh", cd.tanh_q16, jnp.tanh, rng.uniform(-8.0, 8.0, n), False, "abs 6e-5"),
        ("sigmoid", cd.sigmoid_q16, jax.nn.sigmoid, rng.uniform(-8.0, 8.0, n), False, "abs 5e-5"),
    ]
    for name, q_fn, f_fn, vals, relative, bound in unary:
        vals = vals.astype(np.float32)
        vq = to_fixed(vals, Q16_16)
        t_q = _bench(q_fn, vq)
        t_f = _bench(f_fn, jnp.asarray(vals))
        exact = {"sqrt": np.sqrt, "exp": np.exp, "log": np.log, "tanh": np.tanh,
                 "sigmoid": lambda v: 1 / (1 + np.exp(-v))}[name](
            np.asarray(vq, np.int64) / 65536.0)
        err = np.abs(np.asarray(q_fn(vq), np.int64) / 65536.0 - exact)
        if relative:
            # subtract the 1-ulp output-quantization floor before
            # normalizing (the documented bound is 1 ulp + rel * value)
            err = float(np.max(np.maximum(err - 2.0 ** -16, 0.0) / np.abs(exact)))
        else:
            err = float(np.max(err))
        kind = "max_rel_err" if relative else "max_err"
        rows.append((f"univ.{name}_{n//1024}k", t_q, f"jnp_us={t_f:.1f},{kind}={err:.2e} (bound {bound})"))
    return rows


def bench_scalar_mul():
    """Paper Table 1 row mul: Q16.16 vs f32 multiply on vectors, plus
    the Eq. 6 error bound check."""
    from repro.core.qformat import Q16_16, from_fixed, q_mul, to_fixed

    rng = np.random.default_rng(42)
    x = rng.uniform(-100, 100, (1 << 20,)).astype(np.float32)
    y = rng.uniform(-100, 100, (1 << 20,)).astype(np.float32)
    xq, yq = to_fixed(x, Q16_16), to_fixed(y, Q16_16)
    t_q = _bench(lambda a, b: q_mul(a, b), xq, yq)
    t_f = _bench(lambda a, b: a * b, jnp.asarray(x), jnp.asarray(y))
    zq = q_mul(xq, yq)
    err = np.max(
        np.abs(np.asarray(zq, np.int64) / 65536.0
               - (np.asarray(xq, np.int64) / 65536.0) * (np.asarray(yq, np.int64) / 65536.0))
    )
    return [
        ("mul.q16_1M", t_q, f"max_err={err:.3e} (bound 2^-17={2**-17:.3e})"),
        ("mul.f32_1M", t_f, f"note=paper 1.5x is MCU-specific; int8 MXU gives 2x on TPU"),
    ]


def bench_matmul_crossover():
    """Paper §6.4 + §8.1 (the open question): sweep n and find where the
    tiled Q-format kernel crosses naive float.  The paper predicted
    n >= 64 on the MCU and never measured it; we resolve the analogue
    here (CPU host, int8-dot fast path vs f32 matmul)."""
    from repro.models.layers import dot_fast_int8

    rng = np.random.default_rng(42)
    rows = []
    crossover = None
    for n in (4, 8, 16, 32, 64, 128, 256, 512):
        a = rng.uniform(-1, 1, (n, n)).astype(np.float32)
        b = rng.uniform(-1, 1, (n, n)).astype(np.float32)
        aj, bj = jnp.asarray(a), jnp.asarray(b)
        t_q = _bench(jax.jit(dot_fast_int8), aj, bj)
        t_f = _bench(jax.jit(jnp.matmul), aj, bj)
        speedup = t_f / t_q
        if crossover is None and speedup >= 1.0 and n >= 32:
            crossover = n
        rows.append((f"matmul.n{n}", t_q, f"float_us={t_f:.1f},speedup={speedup:.2f}"))
    rows.append(
        ("matmul.crossover", 0.0,
         f"first_n_with_speedup>=1: {crossover} (paper predicted n>=64 on LX6, untested)")
    )
    return rows


def bench_switch():
    """Paper Table 1 row switch: two-phase barrier latency, steady state
    (both executables warm), vs the paper's 8.09 us at 240 MHz."""
    from repro.core.precision import MathEngine, Mode

    eng = MathEngine(Mode.PRECISE)
    eng.set_mode(Mode.FAST)
    eng.set_mode(Mode.PRECISE)  # both contexts warm
    lat = []
    for _ in range(50):
        lat.append(eng.set_mode(Mode.FAST))
        lat.append(eng.set_mode(Mode.PRECISE))
    med = sorted(lat)[len(lat) // 2]
    return [
        ("switch.two_phase_barrier", med, f"median_us={med:.2f} (paper: 8.09us @240MHz)"),
        ("switch.count", 0.0, f"n={len(lat)},max_us={max(lat):.1f}"),
    ]


def bench_ladder_switch():
    """Ladder generalization of the switch row: cycling every registered
    level, scoped ``engine.at`` entry/exit, and a per-op policy swap —
    each must stay an O(1) cached-context reference swap."""
    from repro.core.precision import MathEngine, Mode, PrecisionPolicy, ladder_names

    eng = MathEngine(Mode.PRECISE)
    names = ladder_names()
    for nm in names:            # warm every context
        eng.set_level(nm)
    eng.set_level("f32")

    lat = []
    for _ in range(25):
        for nm in names:
            lat.append(eng.set_level(nm))
    lat = [v for v in lat if v > 0.0]
    med = sorted(lat)[len(lat) // 2]

    at_lat = []
    for _ in range(50):
        c0 = eng.switch_stats.total_latency_us
        with eng.at("q8_24"):
            pass
        at_lat.append(eng.switch_stats.total_latency_us - c0)
    at_med = sorted(at_lat)[len(at_lat) // 2]

    pol = PrecisionPolicy(per_op={"sin": "q8_24", "matmul": "f32"})
    eng.set_policy(pol)
    eng.set_policy(None)        # warm both policy contexts
    pol_lat = []
    for _ in range(50):
        pol_lat.append(eng.set_policy(pol))
        pol_lat.append(eng.set_policy(None))
    pol_med = sorted(pol_lat)[len(pol_lat) // 2]

    return [
        ("ladder.cycle_levels", med,
         f"median_us={med:.2f},levels={len(names)} (O(1) per rung)"),
        ("ladder.scoped_at", at_med, f"median_us={at_med:.2f} (enter+exit)"),
        ("ladder.policy_swap", pol_med, f"median_us={pol_med:.2f}"),
    ]


#: (section name, M tokens, config module) — the fused-MLP bench runs at
#: REAL MLP shapes from configs/ (decode-sized M), per the roadmap item.
FUSED_MLP_SHAPES = (
    ("gemma2_2b", 8, "repro.configs.gemma2_2b"),
    ("mixtral_expert", 8, "repro.configs.mixtral_8x22b"),
)


def _fused_mlp_cases(iters: int, repeats: int):
    """Measure fused / unfused / precise SwiGLU medians per config shape."""
    import importlib

    from repro.core.quantization import QuantizedWeightCache
    from repro.models.layers import attach_quantized_weights, swiglu_mlp

    rng = np.random.default_rng(42)
    out = {}
    for name, M, modname in FUSED_MLP_SHAPES:
        cfgmod = importlib.import_module(modname)
        d, f = cfgmod.CONFIG.d_model, cfgmod.CONFIG.d_ff
        params = {
            "norm": jnp.zeros((d,)),
            "w_gate": jnp.asarray(rng.standard_normal((d, f)), jnp.float32) * 0.02,
            "w_up": jnp.asarray(rng.standard_normal((d, f)), jnp.float32) * 0.02,
            "w_down": jnp.asarray(rng.standard_normal((f, d)), jnp.float32) * 0.02,
        }
        x = jnp.asarray(rng.standard_normal((M, d)), jnp.float32)
        qparams = attach_quantized_weights(params, QuantizedWeightCache())
        step = jax.jit(lambda p, x, m: swiglu_mlp(p, x, m), static_argnums=(2,))
        kw = dict(warmup=1, iters=iters, repeats=repeats)
        out[name] = {
            "M": M, "d_model": d, "d_ff": f,
            "unfused_us": _bench(step, params, x, "fast", **kw),
            "fused_us": _bench(step, qparams, x, "fast", **kw),
            "precise_us": _bench(step, params, x, "precise", **kw),
        }
    return out


def _decode_tokens_per_s(max_new: int = 12):
    """Smoke-model decode throughput, FAST (fused + cached weights) vs
    PRECISE — the end-to-end number the fusion and the sampling/host-sync
    satellites move."""
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import init_params
    from repro.models.config import smoke_config
    from repro.runtime.serve import ContinuousBatchingServer, ServingConfig

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    out = {}
    for label, level in (("fast", "q16_16"), ("precise", "f32")):
        # page size 8 divides the smoke config's 8-row sliding window
        srv = ContinuousBatchingServer(
            cfg, params,
            ServingConfig(n_slots=2, max_len=64, page_size=8, default_level=level),
        )
        srv.generate(prompts, max_new=max_new)  # warm (compile every step)
        t0 = time.perf_counter()
        outs = srv.generate(prompts, max_new=max_new)
        dt = time.perf_counter() - t0
        new_toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        out[label] = new_toks / dt
    return out


def bench_fused_mlp(iters: int = 2, repeats: int = 3, decode: bool = True):
    """Fused FAST SwiGLU (single deferred correction + quantize-once
    weights) vs the unfused three-dispatch path vs precise, at MLP
    shapes from configs/.  CPU-host proxies: the *relation* that must
    hold (and that the CI smoke gates on) is fused <= unfused — the
    fused path removes two activation quantizations, three per-call
    weight quantizations, and the bf16 HBM round-trip of the gate."""
    cases = _fused_mlp_cases(iters=iters, repeats=repeats)
    rows = []
    for name, c in cases.items():
        rows.append((
            f"fused_mlp.{name}.fused", c["fused_us"],
            f"unfused_us={c['unfused_us']:.0f},precise_us={c['precise_us']:.0f},"
            f"speedup_vs_unfused={c['unfused_us'] / c['fused_us']:.2f},"
            f"M={c['M']},d={c['d_model']},f={c['d_ff']}",
        ))
    if decode:
        tok = _decode_tokens_per_s()
        rows.append((
            "fused_mlp.decode_tok_s", 0.0,
            f"fast={tok['fast']:.1f},precise={tok['precise']:.1f} (smoke model)",
        ))
    return rows


def fused_mlp_json(iters: int = 2, repeats: int = 3) -> dict:
    """The BENCH_fused_mlp.json payload: per-shape medians + decode
    tokens/s, so the perf trajectory records across PRs."""
    return {
        "bench": "fused_mlp",
        "shapes": _fused_mlp_cases(iters=iters, repeats=repeats),
        "decode_tokens_per_s": _decode_tokens_per_s(),
    }


def bench_footprint():
    """Paper §4.3.2: 88-byte static footprint decomposition."""
    from repro.core.qformat import static_footprint_bytes

    fp = static_footprint_bytes()
    return [("footprint.static", 0.0,
             f"dispatch={fp['dispatch_table_bytes']}B,cordic={fp['cordic_table_bytes']}B,"
             f"total={fp['total_bytes']}B (paper: 24+64=88)")]


def bench_deferred_error():
    """Paper Eq. 18: error of deferred-shift vs per-element rounding."""
    from repro.core.linalg import qmatmul_deferred, qmatmul_per_element
    from repro.core.qformat import Q16_16, from_fixed, to_fixed

    rng = np.random.default_rng(42)
    K = 256
    a = to_fixed(rng.uniform(-0.9, 0.9, (32, K)).astype(np.float32), Q16_16)
    b = to_fixed(rng.uniform(-0.9, 0.9, (K, 32)).astype(np.float32), Q16_16)
    want = (np.asarray(a, np.float64) / 65536) @ (np.asarray(b, np.float64) / 65536)
    e_def = np.abs(np.asarray(from_fixed(qmatmul_deferred(a, b, tile_k=K))) - want).mean()
    e_per = np.abs(np.asarray(from_fixed(qmatmul_per_element(a, b, rounding=False))) - want).mean()
    return [("deferred.error_reduction", 0.0,
             f"per_element={e_per:.3e},deferred={e_def:.3e},ratio={e_per / max(e_def, 1e-12):.1f}x")]


ALL = [bench_trig, bench_universal_family, bench_scalar_mul,
       bench_matmul_crossover, bench_switch, bench_ladder_switch,
       bench_fused_mlp, bench_footprint, bench_deferred_error]

#: the CI smoke set: the O(1)-switch claim (binary + ladder), the
#: universal-family error bounds at a reduced batch, and the fused-MLP
#: latency relation (fused <= unfused) — minutes, not hours.
SMOKE = ["switch", "ladder", "universal", "fused_mlp"]

#: generous CPU-host ceiling for the smoke gate: a retrace/rebuild on a
#: switch shows up as milliseconds; shared-runner noise does not.
SMOKE_SWITCH_BUDGET_US = 5e4


def run():
    rows = []
    for fn in ALL:
        rows.extend(fn())
    return rows


def main(argv=None):
    """CLI: ``python benchmarks/bench_paper_tables.py [--smoke] [--out f.csv]``.

    ``--smoke`` runs the switch-latency + ladder + universal-family
    sections only and FAILS (exit 1) if any switch median exceeds the
    O(1) budget — this is the per-PR regression gate in CI, with the
    CSV uploaded as an artifact.
    """
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None, help="write CSV here (default: stdout)")
    args = ap.parse_args(argv)

    if args.smoke:
        rows = []
        rows.extend(bench_switch())
        rows.extend(bench_ladder_switch())
        rows.extend(bench_universal_family(n=8192))
        rows.extend(bench_fused_mlp(iters=1, repeats=3, decode=False))
    else:
        rows = run()

    lines = ["name,us_per_call,derived"]
    lines += [f"{name},{us:.2f},{derived}" for name, us, derived in rows]
    csv = "\n".join(lines) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(csv)
    print(csv, end="")

    if args.smoke:
        switch_rows = [
            (name, us) for name, us, _ in rows
            if name in ("switch.two_phase_barrier", "ladder.cycle_levels",
                        "ladder.scoped_at", "ladder.policy_swap")
        ]
        bad = [(n, u) for n, u in switch_rows if u > SMOKE_SWITCH_BUDGET_US]
        if bad:
            print(f"SMOKE FAIL: switch medians over {SMOKE_SWITCH_BUDGET_US}us: {bad}",
                  file=sys.stderr)
            return 1
        # the fused-MLP perf relation: the single-correction fused path
        # must not lose to the three-dispatch unfused path it replaces.
        slow = []
        for name, us, derived in rows:
            if name.startswith("fused_mlp.") and "unfused_us=" in derived:
                unfused = float(derived.split("unfused_us=")[1].split(",")[0])
                if us > unfused:
                    slow.append((name, us, unfused))
        if slow:
            print(f"SMOKE FAIL: fused SwiGLU median above unfused: {slow}",
                  file=sys.stderr)
            return 1
        print(f"smoke ok: {len(switch_rows)} switch medians under "
              f"{SMOKE_SWITCH_BUDGET_US:.0f}us; fused<=unfused at "
              f"{len(FUSED_MLP_SHAPES)} shapes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
    raise SystemExit(main())
