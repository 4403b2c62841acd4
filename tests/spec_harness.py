"""Reusable exactness harness for ladder-speculative decoding — the
executable spec of the draft/verify contract (docs/speculative.md).

Three properties, checkable across model families x draft rungs x
seeds x draft lengths:

1. **Token exactness** (:meth:`ExactnessHarness.run_exactness`): the
   speculative token stream is token-for-token identical to vanilla
   f32 greedy decode.  Drafts influence only HOW FAST tokens are
   produced, never WHICH tokens.
2. **Cache rollback bit-identity**
   (:meth:`ExactnessHarness.run_rollback`): after a real speculative
   round (real drafts, real rejections), the committed cache pool is
   BIT-identical to what sequentially decoding only the accepted
   tokens would have produced, and every rejected position's entries
   are restored bit-for-bit to their pre-round contents.
3. **Acceptance accounting** (:func:`simulate_acceptance`): the
   decoder's drafted/accepted counters match a NumPy reference
   simulator replaying the per-round (drafts, verify argmax) trace.

The harness compiles each (family, k) combination ONCE and reuses it
across seeds and rungs — tests stay parametrization-wide without
paying per-case compiles.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (
    decode_step,
    init_caches,
    init_params,
    prefill_step,
    segment_step,
    smoke_config,
    write_cache_slot,
)
from repro.runtime.speculative import (
    SPEC_CACHE_DTYPE,
    LadderSpeculativeDecoder,
    SpeculativeConfig,
)

DRAFT_RUNGS = ("q8_8", "q16_16")

#: fixed prompt-length pool: seeds vary CONTENT, not shapes, so the
#: per-family compile is paid once across the whole sweep.
PROMPT_LENS = (5, 9, 7)

MAX_LEN = 64


def family_config(name: str):
    mod = __import__(f"repro.configs.{name}", fromlist=["CONFIG"])
    return smoke_config(mod.CONFIG)


def make_prompts(vocab: int, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in PROMPT_LENS]


# ---------------------------------------------------------------------------
# NumPy acceptance-accounting reference
# ---------------------------------------------------------------------------


def simulate_acceptance(trace: Sequence[dict], k: int) -> Dict[str, int]:
    """Replay a decoder trace (per round: drafts (B,k), preds (B,k+1),
    active (B,)) through plain NumPy and recompute the acceptance
    accounting from first principles: the accepted count of a lane is
    the length of the longest prefix where drafts == verify argmaxes.

    Returns {"rounds", "drafted", "accepted"} plus per-round commit
    counts under "n_commit" for cross-checking the decoder's own
    per-round numbers."""
    drafted = accepted = 0
    per_round: List[np.ndarray] = []
    for rec in trace:
        drafts = np.asarray(rec["drafts"])
        preds = np.asarray(rec["preds"])
        active = np.asarray(rec["active"], bool)
        B = drafts.shape[0]
        n_commit = np.zeros((B,), np.int64)
        for i in range(B):
            if not active[i]:
                continue
            m = 0
            while m < k and drafts[i, m] == preds[i, m]:
                m += 1
            n_commit[i] = m + 1
            drafted += k
            accepted += m
        per_round.append(n_commit)
    return {
        "rounds": len(per_round),
        "drafted": drafted,
        "accepted": accepted,
        "n_commit": per_round,
    }


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExactnessReport:
    family: str
    draft_level: str
    seed: int
    speculative: List[List[int]]
    vanilla: List[List[int]]
    acceptance_rate: float
    accounting: Dict[str, int]
    simulator: Dict[str, int]

    @property
    def tokens_ok(self) -> bool:
        return self.speculative == self.vanilla

    @property
    def accounting_ok(self) -> bool:
        return (self.accounting["drafted"] == self.simulator["drafted"]
                and self.accounting["accepted"] == self.simulator["accepted"])


class ExactnessHarness:
    """One compiled harness per (family, k): holds the model, the
    speculative decoders (one per draft rung, trace-collecting) and the
    jitted vanilla/segment reference steps."""

    def __init__(self, family: str, k: int = 3, eos_id: Optional[int] = None):
        self.family = family
        self.k = k
        self.eos_id = eos_id
        self.cfg = family_config(family)
        self.params = init_params(
            self.cfg, jax.random.PRNGKey(zlib.adler32(family.encode()) % (2**31))
        )
        self._decoders: Dict[str, LadderSpeculativeDecoder] = {}
        cfg = self.cfg
        self._pre = jax.jit(
            lambda pr, t, c: prefill_step(pr, t, c, cfg, mode="exact")
        )
        self._dec = jax.jit(
            lambda pr, t, p, c: decode_step(pr, t, p, c, cfg, mode="exact")
        )
        self._seg = jax.jit(
            lambda pr, t, p, c: segment_step(pr, t, p, c, cfg, mode="exact")
        )

    def decoder(self, draft_level: str) -> LadderSpeculativeDecoder:
        if draft_level not in self._decoders:
            self._decoders[draft_level] = LadderSpeculativeDecoder(
                self.cfg, self.params,
                SpeculativeConfig(
                    k=self.k, draft_level=draft_level, max_len=MAX_LEN,
                    eos_id=self.eos_id, collect_trace=True,
                ),
            )
        return self._decoders[draft_level]

    # -- property 1 + 3 ------------------------------------------------------

    def run_exactness(self, draft_level: str, seed: int,
                      max_new: int = 12) -> ExactnessReport:
        """Decode speculatively and vanilla from the same prompts;
        report token identity and acceptance accounting vs the NumPy
        simulator."""
        prompts = make_prompts(self.cfg.vocab, seed)
        dec = self.decoder(draft_level)
        trace_start = len(dec.trace)
        stats_before = dict(dec.stats)
        spec = dec.generate(prompts, max_new=max_new)
        accounting = {
            key: dec.stats[key] - stats_before[key]
            for key in ("rounds", "drafted", "accepted")
        }
        sim = simulate_acceptance(dec.trace[trace_start:], self.k)
        vanilla = self._vanilla(prompts, max_new)
        d = accounting["drafted"]
        return ExactnessReport(
            family=self.family, draft_level=draft_level, seed=seed,
            speculative=spec, vanilla=vanilla,
            acceptance_rate=accounting["accepted"] / d if d else float("nan"),
            accounting=accounting, simulator=sim,
        )

    def _vanilla(self, prompts, max_new: int) -> List[List[int]]:
        outs = []
        for p in prompts:
            caches = init_caches(self.cfg, 1, MAX_LEN, dtype=SPEC_CACHE_DTYPE)
            logits, caches = self._pre(
                self.params, jnp.asarray([list(p)], jnp.int32), caches
            )
            cur = int(jnp.argmax(logits, axis=-1)[0])
            toks = [cur]
            pos = len(p)
            while len(toks) < max_new:
                if self.eos_id is not None and cur == self.eos_id:
                    break
                logits, caches = self._dec(
                    self.params, jnp.asarray([[cur]], jnp.int32),
                    jnp.asarray([pos], jnp.int32), caches,
                )
                cur = int(jnp.argmax(logits, axis=-1)[0])
                toks.append(cur)
                pos += 1
            outs.append(toks)
        return outs

    # -- property 2 ----------------------------------------------------------

    def run_rollback(self, draft_level: str, seed: int) -> Dict[str, bool]:
        """One REAL speculative round (real drafts at the rung, real
        rejections), then two bit-level checks against the same
        pre-round cache state:

        * committed pool == sequentially decoding exactly the accepted
          tokens (bit-for-bit, every leaf) — since the sequential
          reference never touches the rejected positions at all, this
          also proves their entries were restored to their pre-round
          bits, not merely zeroed;
        * no position-indexed entry in the committed pool carries a
          position beyond the lane's last accepted one (rejected draft
          writes truly disappeared).
        """
        cfg = self.cfg
        k = self.k
        prompts = make_prompts(cfg.vocab, seed)
        B = len(prompts)
        dec = self.decoder(draft_level)

        caches = init_caches(cfg, B, MAX_LEN, dtype=SPEC_CACHE_DTYPE)
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        for i, p in enumerate(prompts):
            single = init_caches(cfg, 1, MAX_LEN, dtype=SPEC_CACHE_DTYPE)
            logits, single = self._pre(
                self.params, jnp.asarray([list(p)], jnp.int32), single
            )
            caches = write_cache_slot(caches, single, jnp.int32(i))
            tok[i] = int(jnp.argmax(logits, axis=-1)[0])
            pos[i] = len(p)
        tok_d, pos_d = jnp.asarray(tok), jnp.asarray(pos)
        mask = jnp.ones((B,), bool)

        drafts = dec._draft(
            jnp.int32(dec.draft_levels.index(draft_level)),
            dec.params, tok_d, pos_d, caches, mask,
        )
        preds, n_commit, committed, _, _, _, _ = dec._verify(
            dec.params, tok_d, pos_d, drafts, caches, mask
        )
        n_h = np.asarray(n_commit)
        preds_h = np.asarray(preds)

        # reference: decode ONLY the accepted tokens sequentially.
        # lanes step one token at a time until each lane's commit count
        # is reached (lanes beyond their count are masked via where).
        ref = caches
        t = tok_d
        p_ = pos_d
        for j in range(int(n_h.max())):
            step_mask = jnp.asarray(j < n_h)
            _, stepped = self._dec(self.params, t[:, None], p_, ref)
            ref = jax.tree.map(
                lambda r, s: jnp.where(
                    step_mask.reshape((1, -1) + (1,) * (r.ndim - 2)),
                    s.astype(r.dtype), r,
                ),
                ref, stepped,
            )
            nxt = jnp.asarray(preds_h[np.arange(B), np.minimum(j, n_h - 1)])
            t = jnp.where(step_mask, nxt, t)
            p_ = p_ + step_mask.astype(jnp.int32)

        commit_eq = all(
            bool((a == b).all())
            for a, b in zip(jax.tree.leaves(committed), jax.tree.leaves(ref))
        )

        # no committed pos-indexed entry may sit beyond the lane's last
        # accepted position: rejected draft writes must have vanished
        keep_pos = pos + (n_h - 1)  # pos + m
        restored = True
        for key in committed:
            if not (isinstance(committed[key], dict) and "pos" in committed[key]):
                continue  # SSM caches are fully covered by commit_eq
            pc = np.asarray(committed[key]["pos"])        # (P, B, L)
            restored &= not (pc > keep_pos[None, :, None]).any()

        return {
            "commit_bit_identical": commit_eq,
            "rejected_restored": bool(restored),
            "had_rejections": bool((n_h < k + 1).any()),
        }
