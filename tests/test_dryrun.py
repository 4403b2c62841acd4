"""Dry-run machinery tests: HLO analyzer unit tests + an end-to-end
mini dry-run in a subprocess (own XLA device-count override, so the
main test process keeps its single real device)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.hlo_analysis import analyze_hlo, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[2,2]{1,0}") == 8
    assert _shape_bytes("(f32[8], s8[16])") == 32 + 16
    assert _shape_bytes("pred[]") == 1
    assert _shape_bytes("token[]") == 0


HLO_SAMPLE = textwrap.dedent("""\
    HloModule test

    %body.1 (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
      %p = (s32[], f32[64,64]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[64,64] get-tuple-element(%p), index=1
      %w = f32[64,64] constant({...})
      %dot.1 = f32[64,64] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[64,64] all-reduce(%dot.1), replica_groups={}, to_apply=%add.1
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[64,64]) tuple(%i2, %ar)
    }

    %cond.1 (p2: (s32[], f32[64,64])) -> pred[] {
      %p2 = (s32[], f32[64,64]) parameter(0)
      %i3 = s32[] get-tuple-element(%p2), index=0
      %n = s32[] constant(10)
      ROOT %lt = pred[] compare(%i3, %n), direction=LT
    }

    ENTRY %main (a: f32[64,64]) -> f32[64,64] {
      %a = f32[64,64] parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[64,64]) tuple(%zero, %a)
      %wl = (s32[], f32[64,64]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
      ROOT %out = f32[64,64] get-tuple-element(%wl), index=1
    }
    """)


def test_trip_count_multiplication():
    c = analyze_hlo(HLO_SAMPLE)
    # dot: 2 * 64*64 * 64 flops, x10 trips
    assert c.flops == pytest.approx(2 * 64 * 64 * 64 * 10)
    assert c.collective_bytes["all-reduce"] == pytest.approx(64 * 64 * 4 * 10)
    assert c.collective_counts["all-reduce"] == 10


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
import sys, json
import jax
from jax.sharding import Mesh
from repro.launch.steps import build_cell
from repro.launch import dryrun
import numpy as np

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
dryrun.make_mesh_by_name = lambda name: mesh  # shrink to the host's 8 devices
rec = dryrun.run_cell("{arch}", "{shape}", "host8", verbose=False)
print("RESULT:" + json.dumps({{"status": rec["status"],
    "collective": rec.get("hlo_costs", {{}}).get("total_collective_bytes", 0),
    "flops": rec.get("hlo_costs", {{}}).get("flops", 0)}}))
"""


@pytest.mark.parametrize("arch,shape", [("gemma2_2b", "train_4k"), ("mamba2_1_3b", "decode_32k")])
def test_mini_dryrun_subprocess(arch, shape):
    """Full dry-run path on an 8-device host mesh in a subprocess."""
    code = DRYRUN_SNIPPET.format(arch=arch, shape=shape)
    env = dict(PYTHONPATH="src")
    import os

    env.update(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=Path(__file__).parent.parent, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][0]
    rec = json.loads(line[len("RESULT:"):])
    assert rec["status"] == "ok"
    assert rec["flops"] > 0
    assert rec["collective"] > 0


def test_skip_rules():
    """long_500k skip/run set matches DESIGN.md §4 exactly."""
    from repro.configs import ARCH_IDS, get_config

    runs = {a for a in ARCH_IDS if get_config(a).is_subquadratic}
    assert runs == {"mixtral_8x22b", "jamba_v01_52b", "mamba2_1_3b"}


def test_production_mesh_shapes():
    """Mesh factory contract (without touching device state: just specs)."""
    from repro.launch.steps import SHAPES

    assert SHAPES["train_4k"].batch == 256 and SHAPES["train_4k"].seq == 4096
    assert SHAPES["prefill_32k"].batch == 32 and SHAPES["prefill_32k"].seq == 32768
    assert SHAPES["decode_32k"].batch == 128
    assert SHAPES["long_500k"].batch == 1 and SHAPES["long_500k"].seq == 524288
