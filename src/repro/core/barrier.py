"""Two-phase mode-transition barrier (paper §4.3.1), SPMD edition.

The paper's protocol on FreeRTOS:

1. *Suspension phase* — Core 0 notifies the worker; the worker finishes
   its in-flight operation and signals readiness via a semaphore.
2. *Transition phase* — Core 0 swaps the dispatch table and releases.

On a JAX SPMD deployment the analogous hazards are (a) asynchronous
dispatch — a step may still be executing on device when the host wants
to switch — and (b) multi-host divergence — hosts must switch at the
same step boundary or the executables' collectives deadlock.

Phase 1 therefore (a) blocks on the in-flight device values and (b)
reaches cross-host agreement; phase 2 performs the swap.  Agreement
uses ``multihost_sync`` — a tiny all-reduce across processes — which is
a no-op in single-process deployments (and in this CPU container).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax

__all__ = ["TwoPhaseBarrier", "multihost_sync"]


def multihost_sync(tag: int = 0) -> None:
    """Cross-host agreement point.

    With >1 JAX processes, runs a 1-element psum across all devices so
    every host reaches this line before any host proceeds — the SPMD
    analogue of the paper's notify/semaphore pair.  Single-process:
    no-op (there is nobody to disagree with).
    """
    if jax.process_count() > 1:  # pragma: no cover - needs real multi-host
        import jax.numpy as jnp

        val = jnp.ones((jax.local_device_count(),), jnp.int32) * (tag + 1)
        out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(val)
        jax.block_until_ready(out)


@dataclass
class BarrierEvent:
    quiesce_s: float
    swap_s: float
    total_s: float


@dataclass
class TwoPhaseBarrier:
    """quiesce -> agree -> swap, with per-event timing."""

    sync_fn: Callable[[], None] = multihost_sync
    events: list = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def transition(self, *, inflight: Any, swap_fn: Callable[[], None]) -> BarrierEvent:
        with self._lock:
            t0 = time.perf_counter()
            # Phase 1a: the in-flight operation completes (paper: worker
            # drains its current job and blocks).  Host-only leaves pass
            # through unchanged; a device error raised here propagates.
            if inflight is not None:
                jax.block_until_ready(inflight)
            # Phase 1b: cross-host agreement (paper: xTaskNotify + semaphore).
            self.sync_fn()
            t1 = time.perf_counter()
            # Phase 2: the swap itself — a reference assignment.
            swap_fn()
            t2 = time.perf_counter()
            ev = BarrierEvent(quiesce_s=t1 - t0, swap_s=t2 - t1, total_s=t2 - t0)
            self.events.append(ev)
            return ev
