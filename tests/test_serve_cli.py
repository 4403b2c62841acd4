"""Launcher CLI: every serving flag must round-trip through
``serving_config_from_args`` into a validated :class:`ServingConfig`,
and every model flag through ``model_config_from_args``.

The launcher is the one place flag spellings meet config fields; a
typo'd ``dest`` or a forgotten field silently serves with defaults, so
this suite pins the mapping flag-by-flag, plus one end-to-end ``main``
run that writes the telemetry artifacts (--metrics-out / --trace-out)
to disk.
"""

import json

import jax
import pytest

from repro.launch.serve import (
    MAX_LEN,
    REPO_CACHE_DIR,
    build_parser,
    enable_compile_cache,
    model_config_from_args,
    prompts_from_args,
    serving_config_from_args,
)


@pytest.fixture
def restore_compile_cache():
    """``main`` turns the persistent compile cache on for the process;
    put the setting back so later tests in this worker are unaffected."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _cfg(argv):
    return serving_config_from_args(
        build_parser().parse_args(["--arch", "gemma2_2b"] + argv))


def test_defaults_round_trip():
    scfg = _cfg([])
    assert scfg.n_slots == 2
    assert scfg.max_len == MAX_LEN
    assert scfg.cache == "paged"
    assert scfg.page_size == 16
    assert scfg.speculative is None
    assert scfg.telemetry.enabled is False
    assert scfg.telemetry.trace is False    # no --trace-out given


def test_paged_flags_round_trip():
    scfg = _cfg(["--page-size", "8",
                 "--prefill-chunk", "8", "--prefix-sharing",
                 "--n-pages", "40", "--slots", "3"])
    assert scfg.cache == "paged"
    assert scfg.page_size == 8
    assert scfg.prefill_chunk == 8   # prefix sharing pins chunk == page
    assert scfg.prefix_sharing is True
    assert scfg.n_pages == 40
    assert scfg.n_slots == 3


def test_speculative_flags_round_trip():
    scfg = _cfg(["--speculative", "--spec-k", "4",
                 "--draft-level", "q8_8"])
    assert scfg.speculative is not None
    assert scfg.speculative.k == 4
    assert scfg.speculative.draft_level == "q8_8"
    assert scfg.speculative.max_len == MAX_LEN


@pytest.mark.parametrize("argv,enabled,trace", [
    ([], False, True),
    (["--metrics-out", "m.prom"], True, False),
    (["--trace-out", "t.json"], True, True),
    (["--metrics-out", "m.prom", "--trace-out", "t.json"], True, True),
])
def test_telemetry_enabled_iff_output_requested(argv, enabled, trace):
    scfg = _cfg(argv)
    assert scfg.telemetry.enabled is enabled
    if enabled:
        assert scfg.telemetry.trace is trace


def test_invalid_flag_combination_raises():
    # page_size must divide into max_len; the config's own validation
    # fires through the CLI path, not just direct construction
    with pytest.raises(ValueError):
        _cfg(["--page-size", "1000"])


def _args(argv):
    return build_parser().parse_args(["--arch", "deepseek_7b"] + argv)


def test_max_len_round_trips():
    scfg = serving_config_from_args(_args(["--speculative", "--max-len", "2048"]))
    assert scfg.max_len == 2048
    assert scfg.speculative.max_len == 2048


def test_model_config_published_widths_with_depth_cut():
    cfg = model_config_from_args(_args(["--layers", "4"]))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4096, 32, 32, 128)
    assert (cfg.d_ff, cfg.vocab) == (11008, 102400)
    assert cfg.n_layers == 4
    assert model_config_from_args(_args([])).n_layers == 30


def test_model_config_smoke_and_bad_depth():
    assert model_config_from_args(_args(["--smoke"])).d_model == 64
    gemma = build_parser().parse_args(["--arch", "gemma2_2b", "--layers", "3"])
    with pytest.raises(ValueError, match="period"):
        model_config_from_args(gemma)   # gemma2's period is 2 layers


def test_prompts_builtin_or_seeded():
    assert len(prompts_from_args(_args([]), vocab=128, seed=0)) == 4
    args = _args(["--prompt-lens", "7,300"])
    a = prompts_from_args(args, vocab=102400, seed=0)
    assert [len(p) for p in a] == [7, 300]
    assert all(1 <= t < 102400 for p in a for t in p)
    assert a == prompts_from_args(args, vocab=102400, seed=0)
    assert a != prompts_from_args(args, vocab=102400, seed=1)


def test_compile_cache_placement(tmp_path, monkeypatch, restore_compile_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_main_end_to_end_writes_artifacts(tmp_path, capsys, monkeypatch,
                                          restore_compile_cache):
    from repro.launch.serve import main

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    metrics = tmp_path / "metrics.prom"
    trace = tmp_path / "trace.json"
    # page size 4: it must divide gemma2's 8-row sliding window too
    main(["--arch", "gemma2_2b", "--smoke", "--page-size", "4", "--max-new", "2",
          "--metrics-out", str(metrics), "--trace-out", str(trace)])

    out = capsys.readouterr().out
    assert "req" in out and "stats:" in out and "pages:" in out
    assert "device: platform=cpu" in out

    text = metrics.read_text()
    assert "# TYPE decode_ticks_total counter" in text
    assert "prefills_total 4" in text    # the launcher serves 4 prompts

    tr = json.loads(trace.read_text())
    names = {e["name"] for e in tr["traceEvents"]}
    assert "decode-tick" in names and "admit" in names
