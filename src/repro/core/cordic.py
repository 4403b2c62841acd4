"""CORDIC math module (paper §3.2, §5.2; Listing 2) — universal edition.

Rotation-mode CORDIC computes ``sin``/``cos`` with adds and arithmetic
shifts only — no multipliers (Volder 1959; Walther 1971).  The paper
runs 16 iterations in Q16.16, giving an angular error bound of
``|eps_theta| <= 2**-16 rad ~= 1.526e-5`` (Eq. 14) from a 64-byte
arctangent table.

Universal CORDIC (beyond the paper's Listing 2)
-----------------------------------------------
The paper exercises only circular *rotation* mode, but Walther's
unified formulation — the very iteration the paper cites — covers three
coordinate systems x two directions on the same shift-add datapath:

====== ============ ======================= ==============================
 m      mode         rotation (drive z->0)   vectoring (drive y->0)
====== ============ ======================= ==============================
 +1     circular     sin, cos                atan2(y,x), K*sqrt(x^2+y^2)
 -1     hyperbolic   sinh, cosh -> exp,tanh  atanh(y/x) -> log; sqrt
  0     linear       multiply                divide
====== ============ ======================= ==============================

Gain constants: circular K = prod sqrt(1+2^-2i) -> 1.64676 (paper
Eq. 13); hyperbolic K_h = prod sqrt(1-2^-2i) over the iteration
schedule ~= 0.82816 (1/K_h ~= 1.20750).  Hyperbolic convergence
requires repeating iterations i = 4, 13, 40, ... (r_{j+1} = 3 r_j + 1);
with the repeats the convergence domain is |z| <= ~1.1182.

Derived Q16.16 operations and their range reductions:

* ``atan2_q16``   — circular vectoring in the right half-plane (x<0 is
  folded by point reflection, +/-pi restored from the sign of y);
  operands are pre-normalized so max(|x|,|y|) sits at bit 28, keeping
  the K-amplified magnitude inside int32.
* ``sqrt_q16``    — hyperbolic vectoring of (w+1/4, w-1/4): sqrt(w) =
  K_h^-1 * sqrt((w+1/4)^2 - (w-1/4)^2).  w is normalized to
  u in [0.5, 2) by an even power-of-two shift; the half-shift is
  reapplied to the result.  Internal datapath is Q3.29.
* ``exp_q16``     — hyperbolic rotation: e^r = cosh r + sinh r for
  r = t - k*ln2, |r| <= ln2/2; the 2^k is a final shift.  Saturates to
  Q16.16 max above ln(32768) and flushes to 0 below ln(2^-17).
* ``log_q16``     — hyperbolic vectoring: ln u = 2*atanh((u-1)/(u+1))
  for u in [1, 2) from an MSB normalization; ln w = ln u + k*ln2.
* ``tanh_q16``    — |t| <= 1: sinh/cosh from one hyperbolic rotation,
  divided in linear-vectoring mode; |t| > 1: (1 - e^-2|t|)/(1 + e^-2|t|)
  via ``exp_q16``, so the far tail needs no hyperbolic range extension.
* ``sigmoid_q16`` — (1 + tanh(t/2)) / 2.

Error bounds (Eq. 14 analogues; asserted in tests/test_universal_cordic.py,
measured against float64 oracles over each op's full input range):

* atan2:   |eps| <= 1e-4 rad
* sqrt:    |eps| <= 2^-16 + 3e-5 * sqrt(w)
* exp:     |eps| <= 2^-16 + 6e-5 * e^t   (below saturation)
* log:     |eps| <= 8e-5
* tanh:    |eps| <= 6e-5
* sigmoid: |eps| <= 5e-5

All six are dispatchable through ``MathEngine`` (FAST = these kernels,
PRECISE = the IEEE-754 jnp path); the Pallas TPU kernels in
``kernels/cordic/universal.py`` run the same bodies blockwise.

Differences from the paper's Listing 2 (documented in DESIGN.md):

* The listing's comment "sin is always in y; no negation needed" is
  wrong: after the fold ``theta -> theta -+ pi`` both ``cos`` *and*
  ``sin`` change sign (``sin(t - pi) = -sin t``).  We implement the
  corrected fold.
* The quadrant normalization here is **branchless** (`jnp.where`),
  which is the paper's own §8.2 future-work item — on a vector unit it
  is the natural formulation, eliminating the sin-jitter asymmetry the
  paper measured (coefficient 2.449).
* A full ``mod 2*pi`` range reduction precedes the fold, so any int32
  Q16.16 angle is accepted (the paper's listing assumes
  ``theta in [-pi, pi]``).

Beyond the paper: **exact fixed-point RoPE phase accumulation**.
``pos * inv_freq mod 2*pi`` is computed in Q0.64 *turns* with paired
uint32 limbs, so the phase error at position 524 288 is ~1e-9 rad
before CORDIC — versus ~3e-2 rad for the float32 product used by
typical RoPE implementations.  This is what makes the Q path *more*
accurate than fp32 for long-context rotary embeddings, not just
faster.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qformat import Q8_24, Q16_16, from_fixed, to_fixed

__all__ = [
    "ATAN_TABLE_Q16",
    "CORDIC_K_INV_Q16",
    "PI_Q16",
    "HALF_PI_Q16",
    "TWO_PI_Q16",
    "LN2_Q16",
    "INV_LN2_Q16",
    "EXP_SAT_HI_Q16",
    "EXP_FLUSH_LO_Q16",
    "HYPER_STAGES",
    "ITER_Q24",
    "angle_consts",
    "atan_table",
    "gain_inverse",
    "hyperbolic_schedule",
    "atanh_table",
    "hyper_gain_inverse",
    "cordic_sincos_q16",
    "cordic_sincos",
    "cordic_sincos24",
    "cordic_rotate_q16",
    "atan2_q16",
    "atan2_q24",
    "div_q16",
    "sqrt_q16",
    "exp_q16",
    "log_q16",
    "tanh_q16",
    "sigmoid_q16",
    "cordic_atan2",
    "cordic_atan2_24",
    "cordic_div",
    "cordic_sqrt",
    "cordic_exp",
    "cordic_log",
    "cordic_tanh",
    "cordic_sigmoid",
    "rope_inv_freq_q64",
    "exact_rope_phase_q16",
    "rope_tables_cordic",
]

_U16 = 1 << 16


def atan_table(iterations: int, frac_bits: int = 16) -> np.ndarray:
    """``round(atan(2**-i) * 2**frac_bits)`` for i in [0, iterations)."""
    scale = float(1 << frac_bits)
    return np.array(
        [int(round(math.atan(2.0 ** -i) * scale)) for i in range(iterations)],
        dtype=np.int32,
    )


def gain_inverse(iterations: int, frac_bits: int = 16) -> int:
    """``round(K_n**-1 * 2**frac_bits)`` (paper Eq. 13: K_inf = 1.64676...)."""
    k = 1.0
    for i in range(iterations):
        k *= math.sqrt(1.0 + 2.0 ** (-2 * i))
    return int(round((1.0 / k) * (1 << frac_bits)))


def angle_consts(frac_bits: int = 16) -> Tuple[int, int, int]:
    """(pi, pi/2, 2*pi) as raw Q(m.n) integers for any fraction width.

    2*pi in Q8.24 is ~1.05e8 — every format up to Q4.28 holds a full
    turn in int32, which is what bounds the ladder's angle formats.
    """
    scale = 1 << frac_bits
    return (
        int(round(math.pi * scale)),
        int(round(math.pi / 2 * scale)),
        int(round(2 * math.pi * scale)),
    )


# Paper's constants (verified identical to our generators):
ATAN_TABLE_Q16 = atan_table(16)                 # [51472, 30386, 16055, 8150, ...]
CORDIC_K_INV_Q16 = gain_inverse(16)             # 39797
PI_Q16, HALF_PI_Q16, TWO_PI_Q16 = angle_consts(16)   # 205887, 102944, 411775

#: default iteration count for the Q8.24 high-precision datapath: the
#: residual rotation atan(2**-23) ~= 1.2e-7 rad sits at one Q8.24 ulp.
ITER_Q24 = 24

assert CORDIC_K_INV_Q16 == 39797, "paper §5.2 constant mismatch"
assert PI_Q16 == 205887 and HALF_PI_Q16 == 102944, "paper §5.2 constants"
assert int(ATAN_TABLE_Q16[0]) == 51472, "paper Listing 2 atan(1) entry"


def _range_reduce_q(theta_q, frac_bits: int = 16):
    """Branchless reduction of any int32 Q(m.n) angle to [-pi/2, pi/2].

    Returns (reduced_angle, negate_flag).  negate applies to BOTH sin
    and cos (paper Listing 2's sin comment is incorrect — see module
    docstring).
    """
    pi_q, half_pi_q, two_pi_q = angle_consts(frac_bits)
    theta_q = jnp.asarray(theta_q, jnp.int32)
    two_pi = jnp.int32(two_pi_q)
    pi = jnp.int32(pi_q)
    half_pi = jnp.int32(half_pi_q)
    # floor-mod brings theta into [-pi, pi)
    r = jnp.remainder(theta_q + pi, two_pi) - pi
    hi = r > half_pi
    lo = r < -half_pi
    r = jnp.where(hi, r - pi, r)
    r = jnp.where(lo, r + pi, r)
    return r, hi | lo


def _range_reduce_q16(theta_q):
    return _range_reduce_q(theta_q, 16)


@partial(jax.jit, static_argnames=("iterations", "frac_bits"))
def cordic_sincos_q16(theta_q, iterations: int = 16, frac_bits: int = 16):
    """16-iteration rotation-mode CORDIC (paper Listing 2, corrected).

    Input/output are raw Q16.16 int32.  Vectorized over any shape; the
    iteration count is static so the loop fully unrolls (the paper
    relies on ``-O2`` unrolling; XLA does the same here).
    """
    table = atan_table(iterations, frac_bits)
    k_inv = gain_inverse(iterations, frac_bits)

    z, negate = _range_reduce_q(theta_q, frac_bits)
    x = jnp.full_like(z, k_inv)
    y = jnp.zeros_like(z)

    for i in range(iterations):
        d_pos = z >= 0
        x_shift = x >> i  # arithmetic shift: int32 >> is sign-preserving
        y_shift = y >> i
        x_new = jnp.where(d_pos, x - y_shift, x + y_shift)
        y_new = jnp.where(d_pos, y + x_shift, y - x_shift)
        z = jnp.where(d_pos, z - table[i], z + table[i])
        x, y = x_new, y_new

    cos_q = jnp.where(negate, -x, x)
    sin_q = jnp.where(negate, -y, y)
    return sin_q, cos_q


@partial(jax.jit, static_argnames=("iterations",))
def cordic_sincos(theta, iterations: int = 16):
    """Float in / float out convenience wrapper (pipeline boundary)."""
    theta_q = to_fixed(theta, Q16_16)
    sin_q, cos_q = cordic_sincos_q16(theta_q, iterations=iterations)
    return from_fixed(sin_q, Q16_16), from_fixed(cos_q, Q16_16)


@partial(jax.jit, static_argnames=("iterations",))
def cordic_sincos24(theta, iterations: int = ITER_Q24):
    """Q8.24 high-precision sincos (pipeline boundary).

    24 iterations on the Q8.24 datapath: angular error ~2e-6 rad
    (measured; asserted in tests/test_precision_ladder.py) vs the
    Q16.16 path's 8e-4-level output error — the angle-sensitive
    sensor-fusion rung of the ladder.  Input angles must satisfy
    |theta| < 128 - pi (the Q8.24 dynamic range); the sensor-fusion
    and RoPE callers reduce mod 2*pi upstream.
    """
    theta_q = to_fixed(theta, Q8_24)
    sin_q, cos_q = cordic_sincos_q16(theta_q, iterations=iterations, frac_bits=24)
    return from_fixed(sin_q, Q8_24), from_fixed(cos_q, Q8_24)


@partial(jax.jit, static_argnames=("iterations", "frac_bits"))
def cordic_rotate_q16(x_q, y_q, theta_q, iterations: int = 16, frac_bits: int = 16):
    """Rotate fixed-point vectors (x, y) by theta — multiplier-free.

    This is the CORDIC applied directly to data (e.g. RoPE pair
    rotation) rather than to the unit vector.  The K gain is folded in
    by pre-scaling with K^-1 via shift-add since K^-1 is a constant.
    """
    table = atan_table(iterations, frac_bits)
    k_inv = jnp.int32(gain_inverse(iterations, frac_bits))

    from repro.core.qformat import q_mul  # local import to avoid cycle at module load

    z, negate = _range_reduce_q16(theta_q)
    x = q_mul(jnp.asarray(x_q, jnp.int32), k_inv, frac_bits=frac_bits)
    y = q_mul(jnp.asarray(y_q, jnp.int32), k_inv, frac_bits=frac_bits)

    for i in range(iterations):
        d_pos = z >= 0
        x_shift = x >> i
        y_shift = y >> i
        x_new = jnp.where(d_pos, x - y_shift, x + y_shift)
        y_new = jnp.where(d_pos, y + x_shift, y - x_shift)
        z = jnp.where(d_pos, z - table[i], z + table[i])
        x, y = x_new, y_new

    x = jnp.where(negate, -x, x)
    y = jnp.where(negate, -y, y)
    return x, y


# ---------------------------------------------------------------------------
# Universal CORDIC (Walther): hyperbolic + linear modes, vectoring direction
# ---------------------------------------------------------------------------

#: Default hyperbolic stage count.  20 stages reach shift index 18
#: (with the 4/13 repeats), so the residual rotation angle is
#: atanh(2^-18) ~= 3.8e-6 — below one Q16.16 ulp.
HYPER_STAGES = 20

#: Internal fraction bits of the hyperbolic datapath (Q3.29): rotation
#: intermediates are bounded by cosh(1.55)/K_h < 3, so 3 integer bits
#: (incl. sign) suffice and 29 fraction bits keep the iteration noise
#: far below the Q16.16 output resolution.
_HFRAC = 29

LN2_Q16 = int(round(math.log(2.0) * _U16))          # 45426
INV_LN2_Q16 = int(round((1.0 / math.log(2.0)) * _U16))
EXP_SAT_HI_Q16 = int(round(math.log(32768.0) * _U16))   # exp saturates above
EXP_FLUSH_LO_Q16 = int(round(math.log(2.0 ** -17) * _U16))  # exp -> 0 below
_RAW_MAX = (1 << 31) - 1
_RAW_MIN = -(1 << 31)


def hyperbolic_schedule(stages: int) -> Tuple[int, ...]:
    """Shift indices 1, 2, 3, 4, 4, 5, ... with repeats at 4, 13, 40, ...

    The repeats (r_{j+1} = 3 r_j + 1) are required for hyperbolic
    convergence (Walther 1971); with them sum atanh(2^-i) ~= 1.1182.
    """
    idx, i, rep = [], 1, 4
    while len(idx) < stages:
        idx.append(i)
        if i == rep and len(idx) < stages:
            idx.append(i)
            rep = 3 * rep + 1
        i += 1
    return tuple(idx[:stages])


def atanh_table(schedule: Tuple[int, ...], frac_bits: int = _HFRAC) -> np.ndarray:
    """``round(atanh(2**-i) * 2**frac_bits)`` for each scheduled shift."""
    scale = float(1 << frac_bits)
    return np.array(
        [int(round(math.atanh(2.0 ** -i) * scale)) for i in schedule], dtype=np.int64
    )


def hyper_gain_inverse(schedule: Tuple[int, ...], frac_bits: int = _HFRAC) -> int:
    """``round(K_h**-1 * 2**frac_bits)``; K_h = prod sqrt(1-2^-2i) ~= 0.82816."""
    k = 1.0
    for i in schedule:
        k *= math.sqrt(1.0 - 2.0 ** (-2 * i))
    return int(round((1.0 / k) * (1 << frac_bits)))


def _i32(v: int):
    return jnp.int32(v)


def _clamp_raw(v):
    """Clamp INT32_MIN to INT32_MIN+1 so |v| and -v never wrap."""
    return jnp.maximum(jnp.asarray(v, jnp.int32), _i32(_RAW_MIN + 1))


def _ilog2(v):
    """Branchless floor(log2(v)) for v >= 1 (5-step binary cascade)."""
    v = jnp.asarray(v, jnp.int32)
    n = jnp.zeros_like(v)
    for s in (16, 8, 4, 2, 1):
        gt = v >= _i32(1 << s)
        n = n + jnp.where(gt, _i32(s), _i32(0))
        v = jnp.where(gt, v >> s, v)
    return n


def _shift_signed(v, s):
    """``v * 2**-s`` with a per-element signed shift count (s<0 => left)."""
    sr = jnp.maximum(s, 0)
    sl = jnp.maximum(-s, 0)
    return (v >> sr) << sl


def _round_shift_right(v, s):
    """Round-to-nearest arithmetic right shift by a per-element count >= 0."""
    half = jnp.where(s > 0, _i32(1) << jnp.maximum(s - 1, 0), _i32(0))
    return (v + half) >> s


def _hyper_vectoring(x, y, z, stages: int):
    """Drive y -> 0 (requires x > 0).  On exit x = K_h * sqrt(x0^2-y0^2)
    and z = z0 + atanh(y0/x0), both in the caller's fixed-point format
    (the atanh table is Q3.29 — callers keep z in Q3.29).

    x is non-increasing (each step subtracts |y|>>i), so the Q3.29
    intermediates never exceed their initial magnitude.
    """
    sched = hyperbolic_schedule(stages)
    table = atanh_table(sched, _HFRAC)
    for j, i in enumerate(sched):
        neg = y < 0
        xs = x >> i
        ys = y >> i
        t = _i32(int(table[j]))
        x, y, z = (
            jnp.where(neg, x + ys, x - ys),
            jnp.where(neg, y + xs, y - xs),
            jnp.where(neg, z - t, z + t),
        )
    return x, y, z


def _hyper_rotation(x, y, z, stages: int):
    """Drive z -> 0.  On exit (x, y) = K_h^-1-pre-scaled (cosh z0, sinh z0)
    when started from (K_h^-1, 0, z0); z is the Q3.29 residual angle."""
    sched = hyperbolic_schedule(stages)
    table = atanh_table(sched, _HFRAC)
    for j, i in enumerate(sched):
        pos = z >= 0
        xs = x >> i
        ys = y >> i
        t = _i32(int(table[j]))
        x, y, z = (
            jnp.where(pos, x + ys, x - ys),
            jnp.where(pos, y + xs, y - xs),
            jnp.where(pos, z - t, z + t),
        )
    return x, y, z


def _linear_div_q16(num, den, iterations: int = 17):
    """Linear-vectoring division: num/den in Q16.16, for den > 0 and
    |num| <= den (quotient in [-1, 1]).

    The denominator is normalized up to bit 29 first (the quotient is
    shift-invariant), so the y-update floor noise is ~2^-29 relative —
    the result is accurate to ~1 ulp.  Shift indices start at 0, giving
    a convergence range of sum 2^-i ~= 2.
    """
    num = jnp.asarray(num, jnp.int32)
    den = jnp.asarray(den, jnp.int32)
    b = _ilog2(jnp.maximum(den, 1))
    s = _i32(_HFRAC) - b  # normalize den into [2^29, 2^30)
    x = _shift_signed(den, -s)
    y = _shift_signed(num, -s)
    z = jnp.zeros_like(x)
    for i in range(iterations):
        pos = y >= 0
        xs = x >> i
        t = _i32(_U16 >> i)
        y = jnp.where(pos, y - xs, y + xs)
        z = jnp.where(pos, z + t, z - t)
    return z


def div_q16_body(num_q, den_q, iterations: int = 17):
    """Full-range linear-vectoring division num/den on Q16.16 (ROADMAP
    ``div_q16``).

    Normalization story: ``_linear_div_q16`` converges for quotients in
    (-2, 2) (shift schedule starting at 0, sum 2^-i = 2).  BOTH
    operands are pre-normalized to bit 29 — numerator left-shifts are
    exact, so no significand bits are ever discarded (a numerator
    right-shift would cost 2^-msb(den) relative error on small
    denominators) — and the quotient's net exponent
    ``e = msb(|num|) - msb(|den|)`` is applied to the result: rounded
    right-shift for e < 0, saturating left-shift for e > 0.  Error:
    |eps| <= 2**-15 * (1 + |num/den|) — one Q16.16 quantization step
    for sub-unit quotients, ~2**-15 relative above 1 (measured with 2x
    margin over the full operand range; asserted in
    tests/test_precision_ladder.py and gated in the benchmark smoke).

    Edge cases: den == 0 saturates to sign(num) * Q16.16 max (0/0 = 0);
    INT32_MIN operands are clamped one ulp up so |.| never wraps.
    """
    num = _clamp_raw(num_q)
    den = _clamp_raw(den_q)
    an = jnp.abs(num)
    ad = jnp.abs(den)
    bn = _ilog2(jnp.maximum(an, 1))
    bd = _ilog2(jnp.maximum(ad, 1))
    # normalize both significands to [2^29, 2^30): exact for the
    # numerator (left shift), <= 2^-28 relative for a denominator
    # above bit 29 (bd in {30}, right shift by <= 1)
    nn = _shift_signed(an, bn - _i32(_HFRAC))
    dd = _shift_signed(ad, bd - _i32(_HFRAC))
    z = _linear_div_q16(nn, jnp.maximum(dd, 1), iterations)  # in (0.5, 2) Q16.16
    e = bn - bd
    zr = _round_shift_right(z, jnp.maximum(-e, 0))
    sl = jnp.maximum(e, 0)
    fits = zr <= (_i32(_RAW_MAX) >> sl)
    mag = jnp.where(fits, zr << sl, _i32(_RAW_MAX))
    out = jnp.where((num < 0) != (den < 0), -mag, mag)
    sat = jnp.where(num > 0, _i32(_RAW_MAX), _i32(_RAW_MIN + 1))
    return jnp.where(
        jnp.asarray(den_q, jnp.int32) == 0,
        jnp.where(num == 0, _i32(0), sat),
        out,
    )


def atan2_q16_body(y_q, x_q, iterations: int = 16, frac_bits: int = 16):
    """Circular-vectoring atan2 on Q(m.n) operands; pure jnp, unjitted
    (shared with the Pallas kernel body).

    The operand normalization is scale-invariant, so ``frac_bits``
    only selects the *output* angle format (the atan accumulator
    table); ``frac_bits=24`` is the Q8.24 ladder rung.
    """
    y0 = _clamp_raw(y_q)
    x0 = _clamp_raw(x_q)
    table = atan_table(iterations, frac_bits)
    pi_q = angle_consts(frac_bits)[0]

    # fold x<0 to the right half-plane by point reflection; the +/-pi
    # restoration direction comes from the sign of the original y
    neg_x = x0 < 0
    x1 = jnp.where(neg_x, -x0, x0)
    y1 = jnp.where(neg_x, -y0, y0)

    # scale so max(|x|,|y|) lands in [2^28, 2^29): the circular gain
    # K ~= 1.647 then keeps the magnitude below 2^31 (atan2 is
    # scale-invariant, so both up- and down-shifts are free)
    m = jnp.maximum(jnp.abs(x1), jnp.abs(y1))
    s = _i32(28) - _ilog2(jnp.maximum(m, 1))
    x1 = _shift_signed(x1, -s)
    y1 = _shift_signed(y1, -s)

    z = jnp.zeros_like(x1)
    for i in range(iterations):
        neg = y1 < 0
        xs = x1 >> i
        ys = y1 >> i
        t = _i32(int(table[i]))
        x1, y1, z = (
            jnp.where(neg, x1 - ys, x1 + ys),
            jnp.where(neg, y1 + xs, y1 - xs),
            jnp.where(neg, z - t, z + t),
        )

    half_turn = jnp.where(y0 < 0, _i32(-pi_q), _i32(pi_q))
    out = jnp.where(neg_x, z + half_turn, z)
    return jnp.where((x0 == 0) & (y0 == 0), _i32(0), out)


def sqrt_q16_body(w_q, stages: int = HYPER_STAGES):
    """Hyperbolic-vectoring square root on Q16.16; w <= 0 returns 0."""
    w = _clamp_raw(w_q)
    k_h_inv = hyper_gain_inverse(hyperbolic_schedule(stages), _HFRAC)

    # even-shift normalization: w = u * 2^s, s even, u in [0.5, 2)
    b = _ilog2(jnp.maximum(w, 1))
    s0 = b - _i32(16)
    s = jnp.where((s0 & 1) == 0, s0, s0 + 1)
    u = _shift_signed(w, s)                      # Q16.16 in [0.5, 2)
    u29 = u << (_HFRAC - 16)
    quarter = _i32(1 << (_HFRAC - 2))

    x, _, _ = _hyper_vectoring(u29 + quarter, u29 - quarter, jnp.zeros_like(u29), stages)
    from repro.core.qformat import q_mul

    r29 = q_mul(x, _i32(k_h_inv), frac_bits=_HFRAC)  # sqrt(u), Q3.29
    # back to Q16.16 with the half-shift folded in: s in [-16, 14] even,
    # so the net shift (29-16) - s/2 is always a right shift in [6, 21]
    out = _round_shift_right(r29, _i32(_HFRAC - 16) - (s >> 1))
    return jnp.where(w <= 0, _i32(0), out)


def exp_q16_body(t_q, stages: int = HYPER_STAGES):
    """Hyperbolic-rotation exponential on Q16.16 with ln2 argument
    reduction; saturates above ln(32768), flushes to 0 below ln(2^-17)."""
    from repro.core.qformat import q_mul

    t = jnp.asarray(t_q, jnp.int32)
    k_h_inv = hyper_gain_inverse(hyperbolic_schedule(stages), _HFRAC)

    tc = jnp.clip(t, _i32(EXP_FLUSH_LO_Q16 - _U16), _i32(EXP_SAT_HI_Q16 + _U16))
    k = (q_mul(tc, _i32(INV_LN2_Q16)) + _i32(1 << 15)) >> 16  # round(t/ln2)
    r = tc - k * _i32(LN2_Q16)                                # |r| <= ~ln2/2

    x, y, _ = _hyper_rotation(
        jnp.full_like(t, k_h_inv), jnp.zeros_like(t), r << (_HFRAC - 16), stages
    )
    er = x + y                                  # e^r in Q3.29, in [0.70, 1.42]

    # e^t = e^r * 2^k: net right shift (29-16) - k, with saturation on
    # the left-shift (k > 13) side
    sh = _i32(_HFRAC - 16) - k
    rs = _round_shift_right(er, jnp.maximum(sh, 0))
    sl = jnp.maximum(-sh, 0)
    fits = rs <= (_i32(_RAW_MAX) >> sl)
    out = jnp.where(fits, rs << sl, _i32(_RAW_MAX))
    out = jnp.where(t >= _i32(EXP_SAT_HI_Q16), _i32(_RAW_MAX), out)
    return jnp.where(t <= _i32(EXP_FLUSH_LO_Q16), _i32(0), out)


def log_q16_body(w_q, stages: int = HYPER_STAGES):
    """Hyperbolic-vectoring natural log on Q16.16: ln w = 2*atanh((u-1)/(u+1))
    + k*ln2 for u = w*2^-k in [1, 2) ((u-1)/(u+1) in [0, 1/3), within
    the atanh convergence domain).  w <= 0 returns Q16.16 min."""
    w = _clamp_raw(w_q)
    b = _ilog2(jnp.maximum(w, 1))
    k = b - _i32(16)
    u = _shift_signed(w, k)                     # Q16.16 in [1, 2)
    u29 = u << (_HFRAC - 16)
    one29 = _i32(1 << _HFRAC)

    _, _, z = _hyper_vectoring(u29 + one29, u29 - one29, jnp.zeros_like(u29), stages)
    # ln u = 2*z: Q3.29 -> Q16.16 is >> (29-16-1) with rounding
    lnu = (z + _i32(1 << (_HFRAC - 18))) >> (_HFRAC - 17)
    return jnp.where(w <= 0, _i32(_RAW_MIN), lnu + k * _i32(LN2_Q16))


def tanh_q16_body(t_q, stages: int = HYPER_STAGES):
    """tanh on Q16.16: sinh/cosh + linear-vectoring divide for |t| <= 1,
    (1 - e^-2|t|)/(1 + e^-2|t|) via ``exp_q16_body`` for the tail."""
    t = _clamp_raw(t_q)
    at = jnp.abs(t)
    k_h_inv = hyper_gain_inverse(hyperbolic_schedule(stages), _HFRAC)

    # near path: one hyperbolic rotation at the clamped angle
    ts = jnp.minimum(at, _i32(_U16))
    x, y, _ = _hyper_rotation(
        jnp.full_like(t, k_h_inv), jnp.zeros_like(t), ts << (_HFRAC - 16), stages
    )
    near = _linear_div_q16(y >> (_HFRAC - 16), jnp.maximum(x >> (_HFRAC - 16), 1))

    # far path: e = e^-2|t| in (0, 0.135]; tanh = (1-e)/(1+e).  |t| is
    # clamped before the doubling shift so -2|t| cannot wrap int32.
    a2 = jnp.minimum(at, _i32(-EXP_FLUSH_LO_Q16))
    e = exp_q16_body(-(a2 << 1), stages)
    far = _linear_div_q16(_i32(_U16) - e, _i32(_U16) + e)

    # the q=1 division corner can overshoot by 1 ulp; |tanh| <= 1 exactly
    mag = jnp.minimum(jnp.where(at <= _i32(_U16), near, far), _i32(_U16))
    return jnp.where(t < 0, -mag, mag)


def sigmoid_q16_body(t_q, stages: int = HYPER_STAGES):
    """sigmoid(t) = (1 + tanh(t/2)) / 2 on Q16.16."""
    t = _clamp_raw(t_q)
    th = tanh_q16_body(t >> 1, stages)
    return (th + _i32(_U16 + 1)) >> 1


def _jit_q(body, static=("iterations",)):
    return partial(jax.jit, static_argnames=static)(body)


atan2_q16 = _jit_q(atan2_q16_body, static=("iterations", "frac_bits"))
div_q16 = _jit_q(div_q16_body)
sqrt_q16 = _jit_q(sqrt_q16_body, static=("stages",))
exp_q16 = _jit_q(exp_q16_body, static=("stages",))
log_q16 = _jit_q(log_q16_body, static=("stages",))
tanh_q16 = _jit_q(tanh_q16_body, static=("stages",))
sigmoid_q16 = _jit_q(sigmoid_q16_body, static=("stages",))


def atan2_q24(y_q, x_q, iterations: int = ITER_Q24):
    """Circular-vectoring atan2 with a Q8.24 output angle (ladder rung
    ``q8_24``); operands are Q8.24 raws (any common scale works —
    atan2 is scale-invariant)."""
    return atan2_q16(y_q, x_q, iterations=iterations, frac_bits=24)


# float-boundary convenience wrappers (pipeline boundary, like cordic_sincos)


@jax.jit
def cordic_atan2(y, x):
    return from_fixed(atan2_q16(to_fixed(y, Q16_16), to_fixed(x, Q16_16)), Q16_16)


@jax.jit
def cordic_atan2_24(y, x):
    """Q8.24 atan2 at the float boundary.  Operands are pre-normalized
    by max(|y|, |x|) so any float magnitude fits the Q8.24 word —
    atan2 is scale-invariant, so this costs accuracy nothing and keeps
    the high-precision rung total over the f32 range."""
    y = jnp.asarray(y, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    m = jnp.maximum(jnp.abs(y), jnp.abs(x))
    s = jnp.where(m > 0, m, jnp.float32(1.0))
    return from_fixed(atan2_q24(to_fixed(y / s, Q8_24), to_fixed(x / s, Q8_24)), Q8_24)


@jax.jit
def cordic_div(num, den):
    """Linear-vectoring division at the float boundary (engine op
    ``div``): saturates at the Q16.16 envelope like every FAST op."""
    return from_fixed(div_q16(to_fixed(num, Q16_16), to_fixed(den, Q16_16)), Q16_16)


@jax.jit
def cordic_sqrt(x):
    return from_fixed(sqrt_q16(to_fixed(x, Q16_16)), Q16_16)


@jax.jit
def cordic_exp(x):
    return from_fixed(exp_q16(to_fixed(x, Q16_16)), Q16_16)


@jax.jit
def cordic_log(x):
    return from_fixed(log_q16(to_fixed(x, Q16_16)), Q16_16)


@jax.jit
def cordic_tanh(x):
    return from_fixed(tanh_q16(to_fixed(x, Q16_16)), Q16_16)


@jax.jit
def cordic_sigmoid(x):
    return from_fixed(sigmoid_q16(to_fixed(x, Q16_16)), Q16_16)


# ---------------------------------------------------------------------------
# Exact long-context RoPE phase (beyond paper; uses paper §8.5 multi-limb)
# ---------------------------------------------------------------------------


def rope_inv_freq_q64(head_dim: int, base: float = 10000.0,
                      factors: Optional[Sequence[float]] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Per-pair rotary frequency as an exact Q0.64 fraction of a *turn*.

    ``f_j = base**(-2j/d) / factors[j] / (2*pi)`` (``factors``: LongRoPE's
    per-frequency divisors, none by default) encoded as (hi, lo) uint32
    limbs of ``round(f_j * 2**64)``.  Computed host-side with Python
    integers, so a frequency that is not a power of the base costs the
    phase nothing: it stays one exact integer product per position.
    """
    half = head_dim // 2
    if factors is not None and len(factors) != half:
        raise ValueError(f"{len(factors)} rope factors for {half} frequency pairs")
    hi = np.zeros((half,), np.uint32)
    lo = np.zeros((half,), np.uint32)
    for j in range(half):
        freq = base ** (-2.0 * j / head_dim)
        if factors is not None:
            freq = freq / float(factors[j])
        turns = freq / (2.0 * math.pi)
        q = int(round(turns * float(1 << 64)))
        q = min(q, (1 << 64) - 1)
        hi[j] = (q >> 32) & 0xFFFFFFFF
        lo[j] = q & 0xFFFFFFFF
    return hi, lo


@jax.jit
def exact_rope_phase_q16(positions, f_hi, f_lo):
    """``(pos * f) mod 1`` turn, exactly, then scaled to Q16.16 radians.

    positions: integer array (any shape), values < 2**32.
    f_hi/f_lo: uint32 Q0.64 turn fractions, shape broadcastable against
    positions (typically positions[..., None] x f[None, :]).

    Exactness: ``pos * f mod 2**64`` keeps only the fractional turn —
    integer turns wrap away for free.  One widening u32 multiply plus a
    wrapping u32 multiply; the result is the top 32 fractional bits
    (Q0.32 turns), then one more widening multiply by 2*pi in Q16.16.
    Total phase error <= 2**-33 turns + Q16.16 quantization.
    """
    pos = jnp.asarray(positions).astype(jnp.uint32)
    f_hi = jnp.asarray(f_hi, jnp.uint32)
    f_lo = jnp.asarray(f_lo, jnp.uint32)

    # 64-bit fraction: frac = (pos * (f_hi*2^32 + f_lo)) mod 2^64
    #   hi word = (pos*f_hi mod 2^32) + carry_hi(pos*f_lo)
    lo_prod_hi, _lo_prod_lo = _widening_mul_u32(pos, f_lo)
    frac_hi = pos * f_hi + lo_prod_hi  # wrapping u32: mod 2^32 is what we want
    # theta = frac (Q0.32 turns) * 2*pi (Q16.16) -> Q16.48; round to Q16.16
    t_hi, t_lo = _widening_mul_u32(frac_hi, jnp.uint32(TWO_PI_Q16))
    round_bit = (t_lo >> 31) & jnp.uint32(1)
    theta = (t_hi + round_bit).astype(jnp.int32)  # in [0, 2*pi) Q16.16, fits easily
    return theta


def _widening_mul_u32(a, b) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unsigned 32x32 -> 64 product as (hi, lo) uint32 limbs."""
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    mask = jnp.uint32(0xFFFF)
    a_lo, a_hi = a & mask, a >> 16
    b_lo, b_hi = b & mask, b >> 16
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = lh + (ll >> 16)
    mid2 = hl + (mid & mask)
    lo = (ll & mask) | ((mid2 & mask) << 16)
    hi = hh + (mid >> 16) + (mid2 >> 16)
    return hi, lo


@partial(jax.jit, static_argnames=("iterations", "dtype"))
def rope_tables_cordic(positions, f_hi, f_lo, iterations: int = 16, dtype=jnp.float32):
    """sin/cos rotary tables via exact phase + CORDIC.

    positions: (S,) int array.  Returns (sin, cos) of shape
    (S, head_dim//2) in ``dtype``.
    """
    theta_q = exact_rope_phase_q16(positions[..., None], f_hi[None, :], f_lo[None, :])
    sin_q, cos_q = cordic_sincos_q16(theta_q, iterations=iterations)
    return (
        from_fixed(sin_q, Q16_16, dtype=dtype),
        from_fixed(cos_q, Q16_16, dtype=dtype),
    )
