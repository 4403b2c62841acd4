"""Q-format gradient compression (paper §8.6): correctness vs exact
pmean, error-feedback recirculation, and int8 wire payloads — run on an
8-device host mesh in a subprocess."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.optim.grad_compress import compressed_mean

mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
g_global = rng.normal(0, 1, (8, 64, 33)).astype(np.float32)  # per-device grads

def worker(g_local, r_local):
    grads = {"w": g_local}
    res = {"w": r_local}
    mean, new_res = compressed_mean(grads, res, "data", 8, bits=8)
    exact = {"w": jax.lax.pmean(g_local, "data")}
    return mean, new_res, exact

f = jax.jit(jax.shard_map(worker, mesh=mesh,
    in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data"), P("data")),
    check_vma=False))
gl = jnp.asarray(g_global.reshape(8 * 64, 33))
rl = jnp.zeros_like(gl)
mean, new_res, exact = f(gl, rl)

mean_np = np.asarray(mean["w"]).reshape(8, 64, 33)[0]
exact_np = np.asarray(exact["w"]).reshape(8, 64, 33)[0]
rel = float(np.abs(mean_np - exact_np).mean() / np.abs(exact_np).mean())
res_norm = float(np.abs(np.asarray(new_res["w"])).mean())

# int8 payloads on the wire?
hlo = f.lower(gl, rl).compile().as_text()
s8_colls = sum(1 for l in hlo.splitlines()
               if ("all-to-all" in l or "all-gather" in l) and "s8[" in l)

# two rounds of error feedback shrink accumulated bias:
m1, r1, _ = f(gl, rl)
m2, r2, _ = f(gl, r1["w"])
two_round = np.asarray(m1["w"]).reshape(8,64,33)[0] + np.asarray(m2["w"]).reshape(8,64,33)[0]
bias2 = float(np.abs(two_round - 2 * exact_np).mean() / np.abs(exact_np).mean())

print("RESULT:" + json.dumps({"rel": rel, "res_norm": res_norm,
    "s8_colls": s8_colls, "bias2": bias2}))
"""


@pytest.fixture(scope="module")
def result():
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SNIPPET], capture_output=True, text=True,
        cwd=Path(__file__).parent.parent, timeout=60, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def test_compressed_mean_close_to_exact(result):
    # two quantization stages (pre-wire int8 + requantized sum): the
    # grid of the summed stage is 2**(e+log2 n); ~5% relative on white
    # noise, recirculated by error feedback
    assert result["rel"] < 0.08, result


def test_error_feedback_state_nonzero(result):
    assert result["res_norm"] > 0  # quantization error is recirculated


def test_wire_payloads_are_int8(result):
    assert result["s8_colls"] >= 2, result  # all_to_all + all_gather in s8


def test_error_feedback_reduces_accumulated_bias(result):
    # with EF the accumulated two-round error stays SUBLINEAR: less
    # than 2x the single-round error (without EF it would be ~2x rel)
    assert result["bias2"] < 1.6 * result["rel"], result
