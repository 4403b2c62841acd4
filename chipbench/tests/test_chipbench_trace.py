"""The trace reduction on a small recorded trace (text form in data/)."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace.pbtxt"


@pytest.fixture(scope="module")
def reduced():
    text = "".join(l for l in DATA.read_text().splitlines(True) if not l.startswith("#"))
    return trace.reduce(ProfileData.from_text_proto(text))


def test_module_times(reduced):
    assert reduced["chips"] == 1
    assert reduced["modules"] == pytest.approx(
        {"jit_tick_p": 8e-3, "jit_finish": 1e-3, "jit_chunk_admit": 2e-3})
    assert reduced["module_counts"] == {"jit_tick_p": 2, "jit_finish": 1, "jit_chunk_admit": 1}


def test_busy_is_the_union_of_ops(reduced):
    # ops cover [0,4] [5,6] [9,13] [20,22] ms
    assert reduced["busy_s"] == pytest.approx(11e-3)
    assert reduced["span_ns"] == [1_000_000, 23_000_000]


def test_gaps_longest_first_named_by_host_span(reduced):
    # gaps: [13,20] ms under "admit", [6,9] under "ensure_rows" (innermost
    # covering span), [4,5] under none of the benchmark's spans
    assert reduced["gaps"] == [["host:admit", pytest.approx(7e-3)],
                               ["host:ensure_rows", pytest.approx(3e-3)],
                               ["host:outside-spans", pytest.approx(1e-3)]]


def test_module_name():
    assert trace.module_name("jit_tick_p(123)") == "jit_tick_p"
    assert trace.module_name("jit_finish") == "jit_finish"
