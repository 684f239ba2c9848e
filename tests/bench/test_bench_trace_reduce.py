"""The trace reduction on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``): inside a ``bench_window`` host span, three
``engine_step`` spans each run one jitted program (a bf16 2048x2048
matmul, tanh and sum) to completion, and a ``pump_push_streams`` span
sleeps 20 ms after each."""
import pathlib

import pytest

from bench import trace_reduce as TR

PATH = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return TR.reduce(str(PATH), "bench_window",
                     {"engine_step", "pump_push_streams"})


def test_window_and_busy_union(red):
    assert red.window_s > 3 * 0.02
    assert 0 < red.busy_s < red.window_s - 3 * 0.019
    # one program at a time: the union of op intervals is their sum
    assert sum(red.op_seconds().values()) == pytest.approx(red.busy_s,
                                                           rel=1e-6)


def test_idle_gaps_are_labelled_by_the_host_span(red):
    top = red.top_gaps(10)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert [lab for lab, _ in top[:3]] == ["pump_push_streams"] * 3
    assert all(0.019 < s < 0.03 for _, s in top[:3])
    idle = sum((b - a) for a, b, _ in red.gaps) / 1e9
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_per_op_time(red):
    ops = red.top_ops(10)
    assert ops and all(name.startswith("jit_") for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    # each op runs once per program run, so all have one event count
    # (the profiler had not yet caught the first run's device events)
    counts = {}
    for o in red.ops:
        counts[o.name] = counts.get(o.name, 0) + 1
    assert len(set(counts.values())) == 1 and min(counts.values()) >= 2
    assert [a for n, a, b, _ in red.spans if n == "engine_step"] == \
        sorted(a for n, a, b, _ in red.spans if n == "engine_step")
    assert sorted(s[3]["step"] for s in red.spans
                  if s[0] == "engine_step") == [0, 1, 2]
