"""The serving configuration surface.

:class:`ServingConfig` is ONE validated dataclass that
:class:`~repro.runtime.serve.ContinuousBatchingServer` builds from: the
batch shape, sampling, the per-request precision ladder, speculation,
and the page pool's policy (``page_size`` / ``prefill_chunk`` /
``prefix_sharing`` / ``n_pages``).

Validation happens eagerly in ``__post_init__`` — a config that
constructs is a config a server can build from (model-dependent checks
such as "page_size divides every sliding window" run at server build,
where the :class:`~repro.models.config.ModelConfig` is known).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

from repro.core.arbiter import SlotArbiterConfig
from repro.runtime.speculative import SpeculativeConfig
from repro.runtime.telemetry import TelemetryConfig

__all__ = ["ServingConfig", "SERVE_STEP_LEVELS", "SERVE_CACHE_DTYPE"]

#: engine levels the serve steps are implemented at -> model-layer
#: dispatch string.  The precise rung runs the models' "exact" (f32
#: serving) mode rather than the bf16 training mode — see the
#: repro.runtime.serve module docstring.
SERVE_STEP_LEVELS = (("q16_16", "fast"), ("f32", "exact"))

#: serving caches are f32 (bf16 would round the decode side of the
#: prefill/decode consistency contract only); quantized KV stays the
#: FAST-path memory option.
SERVE_CACHE_DTYPE = jnp.float32


@dataclasses.dataclass
class ServingConfig:
    """The serving engine's configuration.

    Core: ``n_slots`` (device lanes), ``max_len`` (context window =
    pool length), ``eos_id``, ``temperature``, ``default_level`` (the
    ladder level of requests that name none), ``seed``,
    ``health_sync_every``, ``arbiter``, ``speculative`` — see
    :class:`~repro.runtime.serve.ContinuousBatchingServer`.

    Cache: fixed-size pages + free-list block tables (see
    :mod:`repro.runtime.cachepool`): slots map logical blocks to
    physical pages, admission runs CHUNKED prefill (``prefill_chunk``-
    token fixed-shape segments — zero retraces across prompt lengths),
    and ``prefix_sharing=True`` shares full pages between requests with
    a common token prefix (copy-on-write, token-hash keyed).
    ``cache`` names the layout and accepts only ``"paged"``, the one
    layout there is.

    ``page_size`` must divide ``max_len`` (and, checked at server
    build, every sliding-window cache length).  ``prefill_chunk``
    defaults to ``page_size``; prefix sharing REQUIRES chunk ==
    page_size so page contents are a deterministic function of the
    token prefix alone (chunk boundaries land on the same global grid
    regardless of how much prefix was reused).  ``n_pages`` overrides
    the full-length page-pool size (default: one full span per slot,
    twice that when sharing is on, plus the zero page).
    """

    n_slots: int = 4
    max_len: int = 256
    eos_id: Optional[int] = None
    temperature: float = 0.0          # 0 = greedy
    default_level: Any = "f32"        # ladder level name
    seed: int = 0
    #: health-signal sync cadence (decode steps) when NO eos_id is set.
    health_sync_every: int = 8
    arbiter: SlotArbiterConfig = dataclasses.field(
        default_factory=lambda: SlotArbiterConfig(n_levels=len(SERVE_STEP_LEVELS))
    )
    #: enable ladder-speculative decoding for requests that ask for it.
    speculative: Optional[SpeculativeConfig] = None
    #: cache layout: "paged", the only one.
    cache: str = "paged"
    #: physical page length (cache rows per page).
    page_size: int = 16
    #: chunked-prefill segment length; None = page_size.
    prefill_chunk: Optional[int] = None
    #: share full prefix pages between requests (requires a model whose
    #: caches are all full-context position-indexed — no sliding
    #: windows, no SSM state).
    prefix_sharing: bool = False
    #: total pages in the full-length page pool (incl. the reserved
    #: zero page); None = a validated default.
    n_pages: Optional[int] = None
    #: runtime telemetry (see repro.runtime.telemetry).  The metrics
    #: REGISTRY is always on (plain host counters, same cost as the
    #: counting hooks it replaced); ``telemetry.enabled`` additionally
    #: turns on the span tracer / tick profiler, and
    #: ``telemetry.sync_device`` opts into device barriers for honest
    #: phase timings (changes performance, never tokens).
    telemetry: TelemetryConfig = dataclasses.field(default_factory=TelemetryConfig)

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2")
        if self.health_sync_every < 1:
            raise ValueError("health_sync_every must be >= 1")
        if self.cache != "paged":
            raise ValueError(
                f"cache must be 'paged', the paged pool is the only cache layout; "
                f"got {self.cache!r}"
            )
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.max_len % self.page_size:
            raise ValueError(
                f"page_size {self.page_size} must divide max_len {self.max_len}"
            )
        if self.prefill_chunk is not None:
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if self.max_len % self.prefill_chunk:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must divide max_len {self.max_len}"
                )
        if self.prefix_sharing and self.resolved_chunk != self.page_size:
            raise ValueError(
                "prefix_sharing requires prefill_chunk == page_size: page "
                "contents must be a deterministic function of the token "
                "prefix alone (chunk boundaries must land on the page grid "
                "regardless of how much prefix was matched)"
            )
        if self.n_pages is not None:
            # every slot needs its max_len worth of blocks available in
            # the worst case, plus the reserved zero page
            if self.n_pages < self.max_len // self.page_size + 1:
                raise ValueError(
                    f"n_pages {self.n_pages} cannot hold even one slot's "
                    f"{self.max_len // self.page_size} blocks (+1 zero page)"
                )

    @property
    def resolved_chunk(self) -> int:
        """The effective chunked-prefill segment length."""
        return self.prefill_chunk if self.prefill_chunk is not None else self.page_size
