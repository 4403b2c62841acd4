"""Serving runtime: continuous batching over a paged KV pool on the
precision ladder.

:class:`ContinuousBatchingServer` is the serving engine: a fixed device
batch of ``n_slots`` lanes over a :class:`~repro.runtime.cachepool.\
PagedCachePool` allocated ONCE at build.  A
:class:`~repro.runtime.scheduler.ContinuousScheduler` interleaves
per-request chunked prefill (admission) with pool decode steps;
finished requests are evicted and their slots re-filled immediately, so
short requests never wait for long ones.  Each slot carries its own
ladder level — per-REQUEST precision — driven by a vectorized
:class:`~repro.core.arbiter.SlotArbiter` on the request's own
NaN/amplitude signals, and dispatched through the jit-safe
``engine.switched`` traced-index path: mixed-precision batches run with
ZERO retraces (one compiled pool step per active level per decode
step, merged by an on-device slot mask).

Precision levels: the ``f32`` rung maps to the model-layer ``"exact"``
mode (f32 residual stream/matmuls/head — see
:func:`repro.models.layers.pdot`), which is what makes greedy decode
agree with its own prefill re-derivation even for deep hybrid stacks
(jamba).  Serving caches are f32 for the same reason: prefill attends
to its freshly computed k/v, decode to the cache — a bf16 cache would
round one side only.  The FAST memory path (int8 Q-format KV) is
orthogonal and unaffected.

FAST-path weights are quantized ONCE at server build through the
engine's :class:`~repro.core.quantization.QuantizedWeightCache`
(``attach_quantized_weights``): decode consumes pre-quantized int8
payloads and never requantizes a weight, and the MLP hidden stage runs
the fused single-correction path (kernels/fused_mlp).  Sampling is
vectorized (``jax.random.categorical``) on device.  Host-sync budget:
with ``eos_id`` set, one (B, 3) pull per step — sampled token, finite
flag, logit amplitude — serves the EOS check AND the per-slot arbiter
signals in a single transfer; without ``eos_id`` the decode loop
dispatches fully async (tokens accumulate in a device ring, pulled
once per request at eviction; health syncs on a configurable cadence).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arbiter import SlotArbiter
from repro.core.precision import MathEngine
from repro.models import commit_segment, decode_rows, segment_step
from repro.models.config import ModelConfig
from repro.models.layers import attach_quantized_weights
from repro.runtime.cachepool import PagedCachePool
from repro.runtime.config import (
    SERVE_CACHE_DTYPE,
    SERVE_STEP_LEVELS,
    ServingConfig,
)
from repro.runtime.scheduler import ContinuousScheduler, FinishedRequest, Request
from repro.runtime.speculative import SPEC_DRAFT_LEVELS, register_spec_steps
from repro.runtime.telemetry import Telemetry

__all__ = [
    "ServingConfig",
    "ContinuousBatchingServer",
    "SERVE_STEP_LEVELS",
    "SERVE_CACHE_DTYPE",
]


class ContinuousBatchingServer:
    """Continuous-batching engine with per-request precision.

    Device state (allocated once at build):

    * ``pool``  — the page pool's device state (``cache_ops.alloc()``:
      pages of the position-indexed caches, slot rows of the SSM
      state), addressed through host block tables;
    * ``_tok`` / ``_pos`` — (n_slots,) current token / next position.

    Host state: the :class:`ContinuousScheduler` (queue + slot table +
    token bookkeeping) and the :class:`SlotArbiter` (per-slot ladder
    indices).

    One decode step runs the jitted pool step once per DISTINCT active
    level: the level is a traced ``lax.switch`` index (zero retraces),
    and each pass merges its slots' logits and cache rows under an
    on-device occupancy mask, so a batch mixing ``q16_16`` and ``f32``
    requests costs one compiled executable, not one compile per mix.

    Isolation contract (pinned by tests/test_scheduler.py): every
    lane's computation is row-independent (attention, SSD, batch-local
    MoE routing all operate per batch row), and each pass zeroes
    non-member lanes at the input (``lane_mask``) so the FAST path's
    per-TENSOR activation exponents cannot couple a request to other
    levels' lanes or to evicted residue — a request's output is
    therefore identical to serving it alone at its level.  (Multiple
    FAST requests decoding in the SAME pass still share one activation
    exponent; per-row activation scales are the noted next step.)
    """

    def __init__(self, cfg: ModelConfig, params, scfg: ServingConfig):
        self.cfg = cfg
        self.scfg = scfg
        self.level_names = tuple(lv for lv, _ in SERVE_STEP_LEVELS)
        if scfg.default_level not in self.level_names:
            raise ValueError(
                f"default_level {scfg.default_level!r} not in {self.level_names}"
            )
        if scfg.arbiter.n_levels != len(self.level_names):
            raise ValueError("arbiter ladder size must match SERVE_STEP_LEVELS")
        # telemetry: ONE registry shared by every subsystem (scheduler,
        # page pool, weight cache, arbiter hooks) so metrics_snapshot()
        # is the whole server in one dict.  The registry tier is always
        # on; spans/timestamps only when scfg.telemetry.enabled.
        self.telemetry = Telemetry(scfg.telemetry)
        self._declare_metrics(self.telemetry.registry)
        self.engine = MathEngine(scfg.default_level)
        self.engine.weight_cache.use_registry(self.telemetry.registry)
        self.params = attach_quantized_weights(
            params, self.engine.weight_cache, level="q16_16"
        )
        if scfg.health_sync_every < 1:
            raise ValueError("health_sync_every must be >= 1")
        # the paged block pool: allocated once, reused across every
        # request the server ever serves
        self.cache_ops = PagedCachePool(
            cfg, scfg.n_slots, scfg.max_len, scfg.page_size,
            dtype=SERVE_CACHE_DTYPE, n_pages=scfg.n_pages,
            prefix_sharing=scfg.prefix_sharing,
            registry=self.telemetry.registry,
        )
        self.pool = self.cache_ops.alloc()
        self._tok = jnp.zeros((scfg.n_slots,), jnp.int32)
        self._pos = jnp.zeros((scfg.n_slots,), jnp.int32)
        # generated tokens stay ON DEVICE in a per-slot ring (pulled
        # once per request at eviction); health signals accumulate
        # on device between syncs ([finite_and, amp_max] per slot).
        self._gen_buf = jnp.zeros((scfg.n_slots, scfg.max_len), jnp.int32)
        self._gen_count = jnp.zeros((scfg.n_slots,), jnp.int32)
        self._health = jnp.tile(jnp.asarray([1.0, 0.0], jnp.float32), (scfg.n_slots, 1))
        self.scheduler = ContinuousScheduler(
            scfg.n_slots, scfg.max_len, scfg.eos_id, levels=self.level_names,
            registry=self.telemetry.registry,
        )
        self.arbiter = SlotArbiter(scfg.n_slots, scfg.arbiter)
        self.arbiter.on_switch = self._make_switch_hook("serve", self.level_names)
        # speculative mode: a SEPARATE per-slot arbiter whose rungs index
        # the DRAFT ladder (SPEC_DRAFT_LEVELS) — acceptance-rate driven,
        # while self.arbiter keeps governing vanilla slots' serve levels.
        self.draft_arbiter: Optional[SlotArbiter] = None
        if scfg.speculative is not None:
            draft_names = tuple(lv for lv, _ in SPEC_DRAFT_LEVELS)
            self.draft_arbiter = SlotArbiter(
                scfg.n_slots,
                dataclasses.replace(
                    scfg.arbiter,
                    n_levels=len(draft_names),
                    start_idx=draft_names.index(scfg.speculative.draft_level),
                ),
            )
            self.draft_arbiter.on_switch = self._make_switch_hook(
                "draft", draft_names
            )
        self._key = jax.random.PRNGKey(scfg.seed)
        self._step = 0
        self._rid_counter = 0
        self._req_t0: Dict[int, float] = {}  # slot -> admission wall time
        if self.telemetry.on:
            self.telemetry.thread_name(0, "engine")
            for s in range(scfg.n_slots):
                self.telemetry.thread_name(s + 1, f"slot{s}")
        self._build()

    # -- telemetry ----------------------------------------------------------

    def _declare_metrics(self, reg) -> None:
        """Every serving metric family, registered up front (a metric
        that never fires still appears in the snapshot at 0 — absence
        means a typo, not an idle path).  See docs/observability.md."""
        tb = self.scfg.telemetry.tick_buckets
        self._m_decode_ticks = reg.counter(
            "decode_ticks_total", "pool decode steps executed")
        self._m_level_passes = reg.counter(
            "level_passes_total", "compiled pool passes per ladder level",
            labelnames=("level",))
        self._m_prefills = reg.counter(
            "prefills_total", "request prefills (admissions)")
        self._m_prefill_chunks = reg.counter(
            "prefill_chunks_total", "fixed-shape chunk-prefill dispatches")
        self._m_prefix_hits = reg.counter(
            "prefix_cache_hits_total", "admissions that reused a shared prefix")
        self._m_prefix_reused = reg.counter(
            "prefix_tokens_reused_total",
            "prompt tokens served from shared prefix pages")
        self._m_spec_rounds = reg.counter(
            "spec_rounds_total", "speculative draft/verify rounds")
        self._m_spec_drafted = reg.counter(
            "spec_drafted_total", "draft tokens proposed")
        self._m_spec_accepted = reg.counter(
            "spec_accepted_total", "draft tokens accepted by f32 verify")
        self._m_spec_acc_rate = reg.gauge(
            "spec_acceptance_rate", "cumulative accepted/drafted ratio")
        self._m_retrace = reg.counter(
            "retrace_total",
            "jitted step-function (re)traces, by trace-time side effect",
            labelnames=("step",))
        self._m_finished = reg.counter(
            "requests_finished_total", "requests finished",
            labelnames=("reason",))
        self._m_tokens = reg.counter(
            "tokens_generated_total", "tokens committed to finished requests")
        self._m_syncs = reg.counter(
            "host_syncs_total", "device->host synchronizations",
            labelnames=("kind",))
        self._m_active = reg.gauge("active_slots", "slots bound to a request")
        self._m_attn_rows = reg.counter(
            "attn_rows_total",
            "cache rows decode passes' latent-attention layers compute over "
            "(batch lanes x view length) and attend live (position + 1 a member lane)",
            labelnames=("kind", "rows"))
        self._m_arb = reg.counter(
            "arbiter_switches_total", "slot-arbiter rung switches",
            labelnames=("arbiter", "cause"))
        self._m_tick_s = reg.histogram(
            "tick_seconds", "decode-tick phase wall time (s)",
            labelnames=("phase",), buckets=tb)
        self._m_prefill_s = reg.histogram(
            "prefill_seconds", "admission prefill wall time (s)", buckets=tb)
        self._m_req_latency = reg.histogram(
            "request_latency_seconds", "admission->finish wall time (s)",
            buckets=tb)

    def _count_attn_rows(self, lanes: np.ndarray) -> None:
        """``attn_rows_total{kind="mla"}`` for one decode pass whose member
        lanes are set in ``lanes``, summed over the model's MLA layers:
        ``computed``, the rows its attention runs over (every lane of the
        batch -- a pass computes masked lanes too -- x the view length);
        ``live``, the rows its member lanes attend: the decoded token's
        position + 1 each, which is the scheduler's next position."""
        n_mla = self.cfg.n_periods * sum(s.kind == "mla" for s in self.cfg.period)
        if not n_mla:
            return
        live = sum(self.scheduler.position(int(s)) for s in np.nonzero(lanes)[0])
        computed = len(lanes) * self.scfg.max_len
        self._m_attn_rows.inc(n_mla * computed, kind="mla", rows="computed")
        self._m_attn_rows.inc(n_mla * live, kind="mla", rows="live")

    def _make_switch_hook(self, arbiter_name: str, rung_names):
        """Observer for :attr:`SlotArbiter.on_switch`: promotes every
        rung switch to ``arbiter_switches_total{arbiter,cause}`` plus a
        trace instant on the slot's lane."""
        def hook(step, slot, old_idx, new_idx, cause):
            self._m_arb.inc(arbiter=arbiter_name, cause=cause)
            if self.telemetry.on:
                self.telemetry.instant(
                    "arbiter-switch", tid=slot + 1, args={
                        "arbiter": arbiter_name, "cause": cause,
                        "from": rung_names[old_idx], "to": rung_names[new_idx],
                        "step": step,
                    })
        return hook

    @property
    def stats(self) -> Dict[str, int]:
        """The historical counting-hook dict, now a read-only view of
        the registry (same keys/values as the pre-telemetry ad-hoc
        ``stats`` attribute)."""
        return {
            "decode_steps": int(self._m_decode_ticks.value()),
            "level_passes": int(self._m_level_passes.total()),
            "prefills": int(self._m_prefills.value()),
            "spec_rounds": int(self._m_spec_rounds.value()),
            "spec_drafted": int(self._m_spec_drafted.value()),
            "spec_accepted": int(self._m_spec_accepted.value()),
            "prefill_chunks": int(self._m_prefill_chunks.value()),
            "prefix_hits": int(self._m_prefix_hits.value()),
            "prefix_tokens_reused": int(self._m_prefix_reused.value()),
        }

    @property
    def _chunk_traces(self) -> int:
        """Trace-time counter for the fixed-shape chunk-prefill step —
        pinned by the zero-retrace test: after warmup it must not move,
        whatever mix of prompt lengths is admitted.  Alias for
        ``retrace_total{step="chunk"}``."""
        return int(self._m_retrace.value(step="chunk"))

    def metrics_snapshot(self) -> dict:
        """Point-in-time nested dict of every metric (refreshes the
        page-pool occupancy gauges first)."""
        self.cache_ops.scrape_gauges()
        self._m_active.set(len(self.scheduler.active_slots()))
        return self.telemetry.registry.snapshot()

    def render_prometheus(self) -> str:
        """Prometheus text exposition of :meth:`metrics_snapshot`."""
        self.cache_ops.scrape_gauges()
        self._m_active.set(len(self.scheduler.active_slots()))
        return self.telemetry.render_prometheus()

    # -- jitted step functions ---------------------------------------------

    def _build(self):
        """The jitted steps.  Every step that touches the cache wraps a
        level-switched model body between a block-table GATHER (pages
        -> the logical slot-contiguous view the model steps consume)
        and a row/page SCATTER of exactly what the step wrote.  Block
        tables are jit ARGUMENTS — allocation/CoW/sharing change table
        content, never shapes, so the serving loop stays zero-retrace."""
        cfg = self.cfg
        temperature = self.scfg.temperature
        pool: PagedCachePool = self.cache_ops
        C = self.scfg.resolved_chunk

        def make_decode(mode):
            # lane_mask zeroes non-member lanes so a pass's input tensor
            # (and therefore the FAST path's per-tensor activation
            # exponents) is independent of the other slots' contents —
            # the slot-isolation contract (see models.decode_rows).  The
            # step returns the rows it adds; the caller commits them.
            def fn(params, tok, pos, caches, lane_mask):
                self._m_retrace.inc(step="decode")
                return decode_rows(
                    params, tok, pos, caches, cfg, mode=mode, lane_mask=lane_mask
                )
            return fn

        self.engine.register(
            "decode", **{lv: make_decode(m) for lv, m in SERVE_STEP_LEVELS}
        )
        dec_disp, _ = self.engine.switched("decode", levels=self.level_names)

        def mask_cache_view(caches, mask):
            """Non-member lanes see a PRISTINE cache: zero payloads,
            pos sentinel -1 (the zero page's fill).  Without this, a
            masked lane attends to its own live cache (q=0 still
            averages the cached V rows), re-acquiring nonzero
            activations that leak into the FAST path's per-tensor
            activation exponents — the isolation contract would then
            depend on the neighbor's magnitudes.  Fills are constants,
            so this holds no second pool alive."""
            def walk(node):
                out = {}
                for k, v in node.items():
                    if isinstance(v, dict):
                        out[k] = walk(v)
                    else:
                        m = mask.reshape((1, -1) + (1,) * (v.ndim - 2))
                        out[k] = jnp.where(m, v, jnp.asarray(-1 if k == "pos" else 0, v.dtype))
                return out
            return walk(caches)

        def finish(logits, key):
            """Sample + per-slot health: [token, finite, amplitude].
            The (B, 3) view is pulled per step only in EOS mode; the
            async mode leaves it on device and folds it into the
            health accumulator."""
            if temperature <= 0:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                tok = jax.random.categorical(
                    key, jnp.asarray(logits, jnp.float32) / temperature, axis=-1
                ).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(logits), axis=-1)
            amp = jnp.max(jnp.abs(logits), axis=-1)
            host_view = jnp.stack(
                [tok.astype(jnp.float32), finite.astype(jnp.float32), amp], axis=1
            )
            return tok, host_view

        self._finish = jax.jit(finish)

        def step_update(gen_buf, gen_count, cur_tok, pos, health, tok, hv, active):
            """Fold one decode step's results into the device state:
            append active slots' tokens to their rings, advance their
            counts/positions, accumulate health — all without a host
            round-trip (inactive lanes write out-of-bounds -> dropped)."""
            B, L = gen_buf.shape
            idx = jnp.where(active, gen_count, L)
            gen_buf = gen_buf.at[jnp.arange(B), idx].set(tok, mode="drop")
            act = active.astype(jnp.int32)
            gen_count = gen_count + act
            cur_tok = jnp.where(active, tok, cur_tok)
            pos = pos + act
            health = jnp.where(
                active[:, None],
                jnp.stack(
                    [jnp.minimum(health[:, 0], hv[:, 1]),
                     jnp.maximum(health[:, 1], hv[:, 2])], axis=1,
                ),
                health,
            )
            return gen_buf, gen_count, cur_tok, pos, health

        self._step_update = jax.jit(step_update, donate_argnums=(0, 1, 2, 3, 4))

        # chunked prefill: ONE fixed (1, C) segment shape for every
        # prompt length, so admission never retraces.  The tail chunk
        # keeps only its r valid rows: commit_segment rolls the pad
        # positions' writes back bit-for-bit (same rollback machinery
        # as speculative verify).
        def make_chunk(mode):
            def fn(params, tokens, positions, view, keep_pos, keep_count):
                # trace-time side effect (the zero-retrace counting hook)
                self._m_retrace.inc(step="chunk")
                logits, after, aux = segment_step(
                    params, tokens, positions, view, cfg, mode=mode
                )
                view = commit_segment(
                    after=after, before=view, seg_aux=aux, cfg=cfg,
                    keep_pos=keep_pos, keep_count=keep_count,
                    active=jnp.ones((1,), bool),
                )
                last = jnp.take_along_axis(
                    logits, jnp.clip(keep_count - 1, 0, C - 1).reshape(1, 1, 1),
                    axis=1,
                )[:, 0]
                return last, view
            return fn

        self.engine.register(
            "chunk", **{lv: make_chunk(m) for lv, m in SERVE_STEP_LEVELS}
        )
        chunk_disp, _ = self.engine.switched("chunk", levels=self.level_names)

        def chunk_admit(level_idx, params, tokens, positions, state,
                        slot_tables, scatter_ids, slot, keep_pos, keep_count):
            view = pool.slot_view(state, slot_tables, slot)
            last, view = chunk_disp(
                level_idx, params, tokens, positions, view, keep_pos, keep_count
            )
            return last, pool.slot_commit(state, scatter_ids, slot, view)

        self._chunk_admit = jax.jit(chunk_admit, donate_argnums=(4,))

        def tick_p(level_idx, params, tok, pos, state, tables, mask, key,
                   gen_buf, gen_count, health):
            """Fused single-level decode step: gather -> pool pass ->
            scatter the ONE row each active lane adds -> sampling +
            ring/health update, in ONE dispatch.  The hot path when all
            active slots share a level; its masked lanes are only EMPTY
            slots (zero tables -> pristine gather), so no masked view
            is needed here."""
            self._m_retrace.inc(step="tick")
            view = pool.device_view(state, tables)
            logits, rows = dec_disp(
                level_idx, params, tok[:, None], pos, view, mask
            )
            state = pool.commit_rows(state, tables, rows, pos, mask)
            new_tok, hv = finish(logits, key)
            gen_buf, gen_count, tok, pos, health = step_update(
                gen_buf, gen_count, tok, pos, health, new_tok, hv, mask
            )
            return state, gen_buf, gen_count, tok, pos, health, hv

        self._tick_p = jax.jit(tick_p, donate_argnums=(2, 3, 4, 8, 9, 10))

        def pool_pass_p(level_idx, params, tok, pos, state, tables, mask,
                        logits_acc):
            """One decode pass of the whole pool at one level (the
            mixed-batch path): other levels' lanes are LIVE in the page
            pool, so the gathered view is pristine-masked (the
            isolation contract) before the pass; their rows are dropped
            at the scatter, and logits are written only where ``mask``
            is set."""
            self._m_retrace.inc(step="pool_pass")
            view = mask_cache_view(pool.device_view(state, tables), mask)
            logits, rows = dec_disp(level_idx, params, tok, pos, view, mask)
            state = pool.commit_rows(state, tables, rows, pos, mask)
            logits_acc = jnp.where(mask[:, None], logits, logits_acc)
            return logits_acc, state

        # NB: logits_acc is NOT donated — the zero accumulator template
        # is reused across steps and must stay valid.
        self._pool_pass_p = jax.jit(pool_pass_p, donate_argnums=(4,))

        # speculative per-slot mode: draft dispatch (traced rung index)
        # + fused f32 verify/commit over the gathered view, plus a ring
        # update that appends a VARIABLE number of committed tokens per
        # slot in one dispatch.
        if self.scfg.speculative is not None:
            k = self.scfg.speculative.k
            draft_j, verify_j, _ = register_spec_steps(self.engine, cfg, k)

            def spec_update(gen_buf, gen_count, preds, n_commit, mask):
                B, L = gen_buf.shape
                rows = jnp.arange(B)
                for j in range(k + 1):  # static unroll: k+1 masked appends
                    w = mask & (j < n_commit)
                    idx = jnp.where(w, gen_count + j, L)
                    gen_buf = gen_buf.at[rows, idx].set(preds[:, j], mode="drop")
                return gen_buf, gen_count + n_commit

            self._spec_update = jax.jit(spec_update, donate_argnums=(0, 1))

            def spec_draft_p(ri, params, tok, pos, state, tables, dmask):
                return draft_j(ri, params, tok, pos,
                               pool.device_view(state, tables), dmask)

            self._spec_draft_p = jax.jit(spec_draft_p)

            def spec_verify_p(params, tok, pos, drafts, state, tables, mask):
                """Verify + page-granular rollback: the committed view's
                k+1 segment rows carry accepted tokens' NEW bits and
                rejected positions' PRE-SEGMENT bits, so scattering all
                k+1 rows back restores rejected pages bit-for-bit."""
                view = pool.device_view(state, tables)
                preds, n_commit, view, new_tok, new_pos, finite, amp = verify_j(
                    params, tok, pos, drafts, view, mask
                )
                for j in range(k + 1):
                    state = pool.commit_rows(
                        state, tables, pool.view_rows(view, pos + j), pos + j, mask
                    )
                return preds, n_commit, state, new_tok, new_pos, finite, amp

            self._spec_verify_p = jax.jit(spec_verify_p, donate_argnums=(4,))

        self._zero_logits = jnp.zeros((self.scfg.n_slots, cfg.vocab), jnp.float32)
        self._health_neutral = jnp.tile(
            jnp.asarray([1.0, 0.0], jnp.float32), (self.scfg.n_slots, 1)
        )

    # -- admission / eviction ----------------------------------------------

    def _level_idx(self, req: Request) -> int:
        name = req.level or self.scfg.default_level
        if name not in self.level_names:
            raise ValueError(f"request {req.rid}: unknown level {name!r}")
        return self.level_names.index(name)

    def _admit(self, slot: int, req: Request) -> None:
        """Prefill the request at its own level into the slot's pages.
        No host pull unless EOS checking needs the first token's value."""
        tel = self.telemetry
        plen = len(req.prompt)
        if req.speculative:
            # the exactness anchor: a speculative request's prefill and
            # (verify) decode both run the f32/"exact" rung; the
            # request-level rung choice moves to the DRAFT arbiter.
            li = self.level_names.index("f32")
            self.draft_arbiter.reset_slot(slot)
        else:
            li = self._level_idx(req)
        self.arbiter.reset_slot(slot, li)
        t0 = 0.0
        if tel.on:
            t0 = time.perf_counter()
            self._req_t0[slot] = t0
            tel.async_begin("request", id=req.rid, tid=slot + 1, args={
                "rid": req.rid, "prompt_len": plen,
                "level": self.level_names[li], "speculative": req.speculative,
            })
        with tel.span("admit", tid=slot + 1,
                      args={"rid": req.rid, "prompt_len": plen}
                      if tel.on else None):
            logits = self._prefill_chunked(slot, req.prompt, li)
            self._m_prefills.inc()
            self._key, sub = jax.random.split(self._key)
            tok, hv = self._finish(logits, sub)
            if tel.on and self.scfg.telemetry.sync_device:
                hv = jax.block_until_ready(hv)
        if tel.on:
            self._m_prefill_s.observe(time.perf_counter() - t0)
        self._tok = self._tok.at[slot].set(tok[0])
        self._pos = self._pos.at[slot].set(plen)
        self._gen_buf = self._gen_buf.at[slot, 0].set(tok[0])
        self._gen_count = self._gen_count.at[slot].set(1)
        self._health = self._health.at[slot].set(
            jnp.stack([hv[0, 1], hv[0, 2]])
        )
        eos_seen = False
        if self.scfg.eos_id is not None:
            self._m_syncs.inc(kind="eos")
            eos_seen = int(np.asarray(hv)[0, 0]) == self.scfg.eos_id
        self._m_active.set(len(self.scheduler.active_slots()))
        reason = self.scheduler.advance(slot, eos=eos_seen)
        if reason is not None:
            self._finish_slot(slot, reason)

    def _prefill_chunked(self, slot: int, prompt: List[int], li: int):
        """Admission: prefix-match + attach shared pages, then feed the
        unmatched tail through the fixed-shape chunk step — every
        admission costs ``ceil(tail / C)`` dispatches of ONE compiled
        executable regardless of prompt length.  Returns the last-token
        logits (1, vocab) for first-token sampling."""
        pool: PagedCachePool = self.cache_ops
        self.pool, matched, chain = pool.prepare_admission(self.pool, slot, prompt)
        if matched:
            self._m_prefix_hits.inc()
            self._m_prefix_reused.inc(matched)
        C = self.scfg.resolved_chunk
        plen = len(prompt)
        li_dev = jnp.int32(li)
        slot_dev = jnp.int32(slot)
        # tables are fully allocated by prepare_admission -> constant
        # over the chunk loop
        slot_tables = pool.slot_tables(slot)
        scatter_ids = pool.scatter_ids(slot)
        last = None
        start = matched
        tel = self.telemetry
        while start < plen:
            r = min(C, plen - start)
            toks = np.zeros((1, C), np.int32)
            toks[0, :r] = prompt[start : start + r]
            positions = start + np.arange(C, dtype=np.int32)[None]
            with tel.span("prefill-chunk", tid=slot + 1,
                          args={"start": start, "rows": r} if tel.on else None):
                last, self.pool = self._chunk_admit(
                    li_dev, self.params, jnp.asarray(toks), jnp.asarray(positions),
                    self.pool, slot_tables, scatter_ids, slot_dev,
                    jnp.asarray([start + r - 1], jnp.int32),
                    jnp.asarray([r], jnp.int32),
                )
            self._m_prefill_chunks.inc()
            start += r
        # matched <= plen - 1 by construction (the block holding the
        # first decode write is never attached shared), so at least one
        # chunk always runs and `last` is real logits.
        assert last is not None
        pool.finish_admission(slot, chain, matched)
        return last

    def _finish_slot(self, slot: int, reason: str) -> FinishedRequest:
        """Pull the request's generated tokens (the one device->host
        transfer a request ever costs in async mode), record it
        finished, and release the slot: its pages return to the pool
        (allocation pristine-fills them) and its SSM lanes are zeroed,
        so no KV/SSM state leaks into the next occupant."""
        n = self.scheduler.n_generated(slot)
        self._m_syncs.inc(kind="evict")
        toks = np.asarray(self._gen_buf[slot, :n]).tolist()
        fin = self.scheduler.finish(slot, toks, reason)
        self._m_finished.inc(reason=reason)
        self._m_tokens.inc(n)
        if self.telemetry.on:
            t0 = self._req_t0.pop(slot, None)
            if t0 is not None:
                self._m_req_latency.observe(time.perf_counter() - t0)
            self.telemetry.async_end("request", id=fin.rid, tid=slot + 1,
                                     args={"reason": reason, "n_generated": n})
        # release the slot's page references (shared pages survive in
        # the prefix cache) and zero its cumulative SSM lanes; page
        # PAYLOADS are not touched — allocation pristine-fills.
        self.pool = self.cache_ops.reset(self.pool, slot)
        self._tok = self._tok.at[slot].set(0)
        self._pos = self._pos.at[slot].set(0)
        self._gen_count = self._gen_count.at[slot].set(0)
        self._m_active.set(len(self.scheduler.active_slots()))
        return fin

    # -- speculative round --------------------------------------------------

    def _spec_round(self, spec_now: np.ndarray, k: int) -> None:
        """One draft/verify round for the speculative lanes: draft k
        tokens per lane at each lane's DRAFT rung (grouped passes over
        the draft ladder, mask-merged like the vanilla multi-level
        path), verify all k+1 positions in one f32 segment pass that
        also commits/rolls back the pool in-dispatch, append the
        committed tokens to the device ring, and feed the measured
        acceptance rate to the draft arbiter.  The per-round host sync
        is (B, k+2) ints — commit counts + committed token values (the
        EOS/bookkeeping pull, the speculative analogue of the vanilla
        per-step (B, 3) pull)."""
        tel = self.telemetry
        tel_on = tel.on
        rungs = self.draft_arbiter.idx
        present = sorted(set(int(v) for v in rungs[spec_now]))
        tables = self.cache_ops.device_tables()
        drafts = None
        with tel.span("draft", args={"rungs": len(present)} if tel_on else None):
            for ri in present:
                dmask = jnp.asarray(spec_now & (rungs == ri))
                part = self._spec_draft_p(
                    jnp.int32(ri), self.params, self._tok, self._pos,
                    self.pool, tables, dmask,
                )
                drafts = part if drafts is None else jnp.where(dmask[:, None], part, drafts)
        mask_dev = jnp.asarray(spec_now)
        with tel.span("verify", args={"k": k} if tel_on else None):
            (preds, n_commit, self.pool, self._tok, self._pos,
             finite, amp) = self._spec_verify_p(
                self.params, self._tok, self._pos, drafts, self.pool,
                tables, mask_dev,
            )
            self._gen_buf, self._gen_count = self._spec_update(
                self._gen_buf, self._gen_count, preds, n_commit, mask_dev
            )
        # the per-round bookkeeping pull: commit counts + token values
        # (one logical sync, whatever mode)
        self._m_syncs.inc(kind="spec")
        n_h = np.asarray(n_commit)
        preds_h = np.asarray(preds)
        accepted = np.maximum(n_h - 1, 0)
        acc = np.where(spec_now, accepted / k, np.nan)
        self.draft_arbiter.observe(
            self._step, nonfinite=~np.asarray(finite), amplitude=np.asarray(amp),
            active=spec_now, acceptance=acc,
        )
        self._m_spec_rounds.inc()
        self._m_spec_drafted.inc(int(k * spec_now.sum()))
        self._m_spec_accepted.inc(int(accepted[spec_now].sum()))
        if self._m_spec_drafted.value():
            self._m_spec_acc_rate.set(
                self._m_spec_accepted.value() / self._m_spec_drafted.value()
            )
        eos_id = self.scfg.eos_id
        for slot in np.nonzero(spec_now)[0]:
            for j in range(int(n_h[slot])):
                eos = eos_id is not None and int(preds_h[slot, j]) == eos_id
                reason = self.scheduler.advance(int(slot), eos=eos)
                if reason is not None:
                    self._finish_slot(int(slot), reason)
                    break

    # -- the serving loop ---------------------------------------------------

    def serve(self, requests: Sequence[Request]) -> Dict[int, FinishedRequest]:
        """Run all requests to completion; returns {rid: FinishedRequest}.

        The loop structure is the continuous-batching engine: admission
        (per-request prefill into freed slots) interleaves with pool
        decode steps.  Host-sync policy: with ``eos_id`` set, one (B, 3)
        pull per step (token values are needed to detect EOS — the
        sanctioned per-token sync), and it carries the arbiter signals
        for free.  Without ``eos_id``, eviction times are deterministic
        from per-request budgets, so the loop dispatches fully async:
        tokens accumulate in the device ring and are pulled ONCE per
        request at eviction; health syncs every ``health_sync_every``
        steps (the arbiter's hysteresis then operates on that cadence).
        """
        # atomic submission: validate the whole batch (including
        # intra-batch rid collisions) before any request enters the
        # queue, so a bad request cannot strand its predecessors
        seen = set()
        for r in requests:
            self.scheduler.validate(r)
            if r.speculative and self.scfg.speculative is None:
                raise ValueError(
                    f"request {r.rid}: speculative=True but the server was "
                    "built without a speculative config"
                )
            if r.rid in seen:
                raise ValueError(f"duplicate request id {r.rid} within one serve() call")
            seen.add(r.rid)
        for r in requests:
            self.scheduler.submit(r)

        eos_mode = self.scfg.eos_id is not None
        wanted = [r.rid for r in requests]
        k = self.scfg.speculative.k if self.scfg.speculative is not None else 0
        mask_key, mask_dev = None, None  # device occupancy mask, uploaded on membership change
        # capacity predicate: FIFO admission stops while the head
        # request's worst-case block span exceeds free pages (running
        # requests release pages as they finish)
        can_admit = lambda r: self.cache_ops.can_admit(r.prompt)
        while self.scheduler.has_work():
            # one admission per admit() call: _admit allocates the
            # request's pages, so the NEXT head's capacity check must
            # see the decremented free count (approving a whole batch
            # against one stale count over-commits)
            while True:
                pairs = self.scheduler.admit(can_admit, limit=1)
                if not pairs:
                    break
                self._admit(*pairs[0])

            active = self.scheduler.active_mask()
            if not active.any():
                continue  # everything admitted finished at its first token

            # speculative lanes run their own draft/verify round; a
            # spec lane without segment headroom (pos + k would cross
            # max_len) falls back to a vanilla f32 step this iteration.
            spec_now = np.zeros_like(active)
            if self.scfg.speculative is not None:
                for s in np.nonzero(active)[0]:
                    if (self.scheduler.request_at(int(s)).speculative
                            and self.scheduler.position(int(s)) + k < self.scfg.max_len):
                        spec_now[s] = True
            van_now = active & ~spec_now

            # make this step's write targets physically backed:
            # vanilla lanes write one row at pos, spec lanes up to k+1
            # rows — allocate missing blocks (and CoW shared ones)
            # BEFORE the jitted step reads the tables
            for s in np.nonzero(active)[0]:
                p = self.scheduler.position(int(s))
                hi = p + k if spec_now[s] else p
                self.pool = self.cache_ops.ensure_rows(
                    self.pool, int(s), p, min(hi, self.scfg.max_len - 1)
                )

            if spec_now.any():
                with self.telemetry.span(
                        "spec-round",
                        args={"step": self._step, "lanes": int(spec_now.sum())}
                        if self.telemetry.on else None):
                    self._spec_round(spec_now, k)

            if van_now.any():
                tel = self.telemetry
                tel_on = tel.on
                t0 = time.perf_counter() if tel_on else 0.0
                with tel.span("decode-tick",
                              args={"step": self._step,
                                    "lanes": int(van_now.sum())}
                              if tel_on else None):
                    levels = self.arbiter.idx
                    present = sorted(set(int(v) for v in levels[van_now]))
                    self._key, sub = jax.random.split(self._key)
                    tables = self.cache_ops.device_tables()
                    t1 = time.perf_counter() if tel_on else 0.0
                    if len(present) == 1:
                        # hot path: homogeneous level -> ONE fused dispatch
                        key = (van_now.tobytes(), present[0])
                        if key != mask_key:
                            mask_key, mask_dev = key, jnp.asarray(van_now)
                        lv = self.level_names[present[0]]
                        with tel.span("level-pass",
                                      args={"level": lv} if tel_on else None):
                            (self.pool, self._gen_buf, self._gen_count, self._tok,
                             self._pos, self._health, hv) = self._tick_p(
                                jnp.int32(present[0]), self.params, self._tok,
                                self._pos, self.pool, tables, mask_dev, sub,
                                self._gen_buf, self._gen_count, self._health,
                            )
                        self._m_level_passes.inc(level=lv)
                        self._count_attn_rows(van_now)
                    else:
                        # mixed levels: one pool pass per level, mask-merged
                        logits = self._zero_logits
                        for li in present:
                            members = van_now & (levels == li)
                            self._count_attn_rows(members)
                            mask = jnp.asarray(members)
                            lv = self.level_names[li]
                            with tel.span("level-pass",
                                          args={"level": lv} if tel_on else None):
                                logits, self.pool = self._pool_pass_p(
                                    jnp.int32(li), self.params, self._tok[:, None],
                                    self._pos, self.pool, tables, mask, logits,
                                )
                            self._m_level_passes.inc(level=lv)
                        tok, hv = self._finish(logits, sub)
                        active_dev = jnp.asarray(van_now)
                        (self._gen_buf, self._gen_count, self._tok, self._pos,
                         self._health) = self._step_update(
                            self._gen_buf, self._gen_count, self._tok, self._pos,
                            self._health, tok, hv, active_dev,
                        )
                    self._m_decode_ticks.inc()
                    if tel_on and self.scfg.telemetry.sync_device:
                        # profiling mode ONLY: barrier so device_dispatch
                        # measures device time, not async dispatch time
                        hv = jax.block_until_ready(hv)
                    t2 = time.perf_counter() if tel_on else 0.0
                    self._step += 1

                    eos_flags = np.zeros((self.scfg.n_slots,), bool)
                    if eos_mode:
                        self._m_syncs.inc(kind="eos")
                        hv_host = np.asarray(hv)  # the per-step EOS pull
                        eos_flags = hv_host[:, 0].astype(np.int32) == self.scfg.eos_id
                        self.arbiter.observe(
                            self._step, nonfinite=hv_host[:, 1] < 0.5,
                            amplitude=hv_host[:, 2], active=van_now,
                        )
                    elif self._step % self.scfg.health_sync_every == 0:
                        self._m_syncs.inc(kind="health")
                        h = np.asarray(self._health)  # periodic aggregated sync
                        self.arbiter.observe(
                            self._step, nonfinite=h[:, 0] < 0.5, amplitude=h[:, 1],
                            active=van_now,
                        )
                        self._health = self._health_neutral.copy()  # template stays valid under donation

                    for slot in np.nonzero(van_now)[0]:
                        reason = self.scheduler.advance(int(slot), eos=bool(eos_flags[slot]))
                        if reason is not None:
                            self._finish_slot(int(slot), reason)
                if tel_on:
                    t3 = time.perf_counter()
                    self._m_tick_s.observe(t1 - t0, phase="host_schedule")
                    self._m_tick_s.observe(t2 - t1, phase="device_dispatch")
                    self._m_tick_s.observe(t3 - t2, phase="sync")
            else:
                self._step += 1

        # hand results out AND release them from the scheduler: a
        # server outlives its serve() calls, so retaining per-request
        # state forever would leak memory proportional to lifetime
        # traffic (a rid may be reused once its result is delivered).
        return {rid: self.scheduler.pop_finished(rid) for rid in wanted}

    def next_rid(self) -> int:
        """Fresh request id (the server outlives any one ``serve`` call
        — rids are unique for the server's lifetime)."""
        rid = self._rid_counter
        self._rid_counter += 1
        return rid

    def generate(self, prompts: List[List[int]], max_new: int = 32,
                 level: Optional[str] = None,
                 speculative: bool = False) -> List[List[int]]:
        """Convenience: serve the prompts as one batch of requests and
        return their token lists (prompt + generated) in input order."""
        reqs = [
            Request(rid=self.next_rid(), prompt=list(p), max_new=max_new,
                    level=level, speculative=speculative)
            for p in prompts
        ]
        fins = self.serve(reqs)
        return [fins[r.rid].tokens for r in reqs]
