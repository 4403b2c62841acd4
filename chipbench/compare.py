"""The comparison that decides ``correct``.

After the window has closed, a sample of the finished requests, drawn
from the seed and holding the longest request of each rung, is re-run
through the configuration's plain reference: once per request over its
prompt and served tokens (teacher forcing), padded to the cell's
``max_len`` so that one compiled program serves every request.  At each
served position it reads the reference's logits and compares:

* ``<rung>_gap``: how far below the reference's best logit the served
  token's reference logit lies, in units of the spread (standard
  deviation over the vocabulary) of that row -- the widest over the
  rung's sampled tokens.  0 where every served token is the reference's
  argmax.
* ``<rung>_amp``: the distance between the largest |logit| the served
  path reported for the token (the health signal the server pulls every
  tick) and the reference's, in the same units -- the widest over the
  rung's sampled decode tokens.

Rung names are shortened for the numbers: ``f32`` and ``q16`` (q16_16).
A limits file (``chipbench/limits/<workload>.json``) names the numbers
that decide ``correct`` and gives each its limit, with the readings it
was set from; the others are only logged.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import numpy as np

SHORT = {"f32": "f32", "q16_16": "q16"}


def sample(served, level_names, seed: int, min_tokens: int, max_requests: int) -> Dict[str, list]:
    """Per rung: the finished requests to compare -- the longest, then
    others drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    rng = np.random.default_rng(int(seed) + 1)
    out = {}
    for lv in sorted({s.planned.level for s in served}):
        done = [s for s in served if s.planned.level == lv and s.tokens]
        if not done:
            continue
        longest = max(done, key=lambda s: (len(s.tokens), -s.planned.index))
        rest = [s for s in done if s is not longest]
        pick, n_tok = [longest], len(longest.tokens)
        for i in rng.permutation(len(rest)):
            if n_tok >= min_tokens or len(pick) >= max_requests:
                break
            pick.append(rest[i])
            n_tok += len(rest[i].tokens)
        out[lv] = pick
    return out


def _row_stats(ref, tok):
    """Per row of reference logits ``ref`` (R, V): the gap of token
    ``tok`` (R,) below the row's best, in spreads; the spread; the
    largest |logit|."""
    import jax.numpy as jnp

    best = jnp.max(ref, axis=-1)
    spread = jnp.std(ref, axis=-1)
    val = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
    return (best - val) / spread, spread, jnp.max(jnp.abs(ref), axis=-1)


def readings(ref_mod, spec, weights, picks, max_len: int, rows_pad: int,
             control: str = None) -> Dict[str, Dict[str, float]]:
    """The compared numbers, per rung.  With ``control`` (a ``matmul``
    mode of the reference), the control's readings instead: at each of
    the same positions, the gap of the token that the control ranks
    first, and the distance of the control's largest |logit|.

    Every request is padded to ``max_len`` positions and ``rows_pad``
    scored rows, so one compiled program serves them all."""
    import jax
    import jax.numpy as jnp

    stats = jax.jit(_row_stats)
    ctl_top = jax.jit(lambda c: (jnp.argmax(c, axis=-1).astype(jnp.int32),
                                 jnp.max(jnp.abs(c), axis=-1)))
    out = {}
    for lv, reqs in picks.items():
        gaps, amps = [], []
        for s in reqs:
            seq = list(s.planned.prompt) + list(s.tokens)
            plen, n = len(s.planned.prompt), len(s.tokens)
            toks = np.zeros((max_len,), np.int32)
            toks[: len(seq) - 1] = seq[:-1]
            rows = np.full((rows_pad,), plen - 1, np.int32)
            rows[:n] = plen - 1 + np.arange(n)
            ref = ref_mod.logits(weights, toks, rows, spec, "highest")
            if control is None:
                tok = np.zeros((rows_pad,), np.int32)
                tok[:n] = s.tokens
                gap, spread, amp_ref = stats(ref, jnp.asarray(tok))
                amp_srv = np.array([np.nan if a is None else a for a in s.amps], np.float64)
            else:
                top, amp_ctl = ctl_top(ref_mod.logits(weights, toks, rows, spec, control))
                gap, spread, amp_ref = stats(ref, top)
                amp_srv = np.asarray(amp_ctl, np.float64)[:n]
            gap, spread, amp_ref = (np.asarray(x, np.float64)[:n] for x in (gap, spread, amp_ref))
            gaps.append(gap)
            d = np.abs(amp_srv - amp_ref) / spread
            amps.append(d[~np.isnan(d)])
        g, a = np.concatenate(gaps), np.concatenate(amps)
        short = SHORT.get(lv, lv)
        out[f"{short}_gap"] = float(g.max())
        if a.size:
            out[f"{short}_amp"] = float(a.max())
        out[f"{short}_tokens"] = int(g.size)
    return out


def load_limits(root: Path, workload: str) -> dict:
    path = root / "limits" / f"{workload}.json"
    return json.loads(path.read_text()) if path.exists() else {}


def off_rung(served, level_names) -> int:
    """Tokens served at another rung than their request was pinned to
    (the arbiter escalates a slot on a non-finite logit)."""
    return sum(1 for s in served for lv in s.levels if level_names[lv] != s.planned.level)


def judge(numbers: Dict[str, float], limits: dict, failed: int, off: int = 0) -> (bool, dict):
    """``correct`` and the checks printed beside it: every request that
    never finished, every token served off its rung, and every number
    the cell's limits file names, with its limit.  A named number the run
    could not read fails."""
    checks = {"unfinished": {"value": failed, "limit": 0},
              "off_rung_tokens": {"value": off, "limit": 0}}
    ok = failed == 0 and off == 0
    for name, entry in limits.items():
        value = numbers.get(name)
        checks[name] = {"value": value, "limit": entry["limit"]}
        ok = ok and value is not None and value <= entry["limit"]
    return ok, checks
