"""Serving driver: batched generation with runtime precision modes.

On the CPU, at smoke widths (``--smoke``; the tests run this way):
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b --smoke --continuous
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek_7b --smoke \
        --continuous --paged --prefix-sharing \
        --metrics-out metrics.prom --trace-out trace.json

On a TPU, at published widths with only the depth cut (``--layers``):
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek_7b --layers 4 \
        --continuous --slots 8 --max-len 1536 --prompt-lens 256,1024 \
        --levels f32,q16_16 --max-new 24

``--continuous`` runs the continuous-batching engine (per-request
precision via ``--levels``) on a mixed-length/mixed-budget workload;
the default runs the static lock-step ``BatchedServer``.  Both routes
build ONE :class:`~repro.runtime.config.ServingConfig` — assembled by
:func:`serving_config_from_args`, which is what
tests/test_serve_cli.py pins: every cache/telemetry flag must round-trip
into a validated config.  The model comes from
:func:`model_config_from_args`: the published config of ``--arch``,
cut to ``--layers`` (never a width), or its smoke reduction under
``--smoke``.  Without ``--prompt-lens`` the launcher serves four short
built-in prompts; with it, one seeded random prompt per length.
``--continuous --speculative`` serves every
request through ladder-speculative decoding (draft at ``--draft-level``,
verify at f32 — output identical to vanilla f32 greedy; watch
``spec_rounds`` / ``spec_accepted`` in the printed stats).  ``--paged``
switches the cache pool to fixed-size pages + block tables with chunked
prefill (``--prefill-chunk`` tokens per fixed-shape segment); add
``--prefix-sharing`` to share full prefix pages between requests
(full-context attention models only).

Telemetry outputs (see docs/observability.md): ``--metrics-out FILE``
writes the Prometheus text exposition after serving; ``--trace-out
FILE`` writes the Chrome ``trace_event`` JSON (open in Perfetto).
Either flag turns the profiler tier on for the run.

The persistent compilation cache is turned on before the first compile
(:func:`enable_compile_cache`).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path

import jax
import numpy as np

MAX_LEN = 128

#: The persistent compilation cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed directory in the checkout, so a later run finds what
#: an earlier one cached (no temporary names).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

BUILTIN_PROMPTS = ([1, 2, 3, 4, 5], [10, 11, 12], [7, 7, 7, 7], [3, 1, 4, 1, 5, 9])


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  ``JAX_COMPILATION_CACHE_DIR`` wins where it is set, and no
    other directory is configured then; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's CPU smoke reduction instead of its "
                         "published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut: serve this many layers (a multiple of the "
                         "layer period); every width stays as published")
    ap.add_argument("--max-len", type=int, default=MAX_LEN,
                    help="context length of every cache slot")
    ap.add_argument("--prompt-lens", default=None,
                    help="comma list of prompt lengths: one seeded random prompt "
                         "per length instead of the four built-in prompts")
    ap.add_argument("--mode", default="precise", choices=["precise", "fast"])
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine instead of the static server")
    ap.add_argument("--slots", type=int, default=2,
                    help="device batch slots for --continuous")
    ap.add_argument("--levels", default=None,
                    help="comma list of per-request ladder levels for --continuous "
                         "(cycled over requests; e.g. 'q16_16,f32')")
    ap.add_argument("--speculative", action="store_true",
                    help="with --continuous: serve every request in "
                         "ladder-speculative mode (draft at --draft-level, "
                         "verify at f32 — output identical to vanilla f32)")
    ap.add_argument("--draft-level", default="q16_16", choices=["q8_8", "q16_16"],
                    help="draft rung for --speculative")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens per speculative round")
    ap.add_argument("--paged", action="store_true",
                    help="with --continuous: paged cache pool + chunked prefill")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache rows per page for --paged")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill segment length (default: page size)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="with --paged: share full prefix pages across requests")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="total pages in the full-length pool (default: sized "
                         "to the slot count)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the Prometheus metrics exposition here after "
                         "serving (enables telemetry)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the Chrome trace_event JSON here after serving "
                         "(enables telemetry + tracing; open in Perfetto)")
    return ap


def model_config_from_args(args):
    """The one flags -> :class:`ModelConfig` mapping: the published
    config of ``--arch`` (or its smoke reduction), cut to ``--layers``."""
    from repro.configs import get_config, smoke

    cfg = smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.layers is not None:
        period = len(cfg.period)
        if args.layers < period or args.layers % period:
            raise ValueError(
                f"--layers {args.layers} must be a positive multiple of "
                f"{cfg.name}'s {period}-layer period"
            )
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    return cfg


def random_prompts(lens, vocab: int, seed: int):
    """One seeded random prompt per length, token ids drawn from
    ``[1, vocab)``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def prompts_from_args(args, vocab: int, seed: int):
    """The built-in prompts, or one seeded random prompt per
    ``--prompt-lens`` entry."""
    if not args.prompt_lens:
        return [list(p) for p in BUILTIN_PROMPTS]
    return random_prompts([int(n) for n in args.prompt_lens.split(",")], vocab, seed)


def serving_config_from_args(args):
    """The one flags -> :class:`ServingConfig` mapping (validated by
    the config's own ``__post_init__``)."""
    from repro.runtime.config import ServingConfig
    from repro.runtime.speculative import SpeculativeConfig
    from repro.runtime.telemetry import TelemetryConfig

    spec = (
        SpeculativeConfig(k=args.spec_k, draft_level=args.draft_level,
                          max_len=args.max_len)
        if args.speculative else None
    )
    telemetry = TelemetryConfig(
        enabled=bool(args.metrics_out or args.trace_out),
        trace=bool(args.trace_out),
    )
    return ServingConfig(
        n_slots=args.slots, max_len=args.max_len, speculative=spec,
        cache="paged" if args.paged else "contiguous",
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
        prefix_sharing=args.prefix_sharing, n_pages=args.n_pages,
        telemetry=telemetry,
    )


def _write_outputs(srv, args) -> None:
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(srv.render_prometheus())
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        srv.telemetry.write_trace(args.trace_out)
        print(f"trace   -> {args.trace_out}")


def main(argv=None):
    args = build_parser().parse_args(argv)

    from repro.core.precision import Mode
    from repro.models import init_params
    from repro.runtime.config import ServingConfig
    from repro.runtime.scheduler import Request
    from repro.runtime.serve import BatchedServer, ContinuousBatchingServer

    dev = jax.devices()
    print(f"device: platform={dev[0].platform} kind={dev[0].device_kind} "
          f"count={len(dev)}")
    print(f"compile cache: {enable_compile_cache()}")
    cfg = model_config_from_args(args)
    scfg = serving_config_from_args(args)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = prompts_from_args(args, cfg.vocab, scfg.seed)

    if args.continuous:
        srv = ContinuousBatchingServer(cfg, params, scfg)
        levels = args.levels.split(",") if args.levels else [None]
        reqs = [
            Request(rid=srv.next_rid(), prompt=p, max_new=args.max_new + 4 * (i % 2),
                    level=levels[i % len(levels)],
                    speculative=args.speculative)
            for i, p in enumerate(prompts)
        ]
        fins = srv.serve(reqs)
        for r in reqs:
            f = fins[r.rid]
            print(f"req{r.rid} [{r.level or 'default'}] ({f.reason}): {f.tokens}")
        print(f"stats: {srv.stats}")
        if args.paged:
            print(f"pages: {srv.cache_ops.report()}")
        _write_outputs(srv, args)
        return

    from repro.runtime.telemetry import TelemetryConfig

    srv = BatchedServer(
        cfg, params,
        ServingConfig(n_slots=4, max_len=args.max_len, max_new=args.max_new,
                      default_level=Mode(args.mode),
                      telemetry=TelemetryConfig(
                          enabled=bool(args.metrics_out or args.trace_out),
                          trace=bool(args.trace_out))),
    )
    for i, seq in enumerate(srv.generate(prompts)):
        print(f"req{i}: {seq}")
    if args.metrics_out or args.trace_out:
        print("note: --metrics-out/--trace-out apply to --continuous; "
              "static-server metrics are limited to the weight cache")
        _write_outputs(srv, args)


if __name__ == "__main__":
    main()
