"""``bench.spans``: the readings of the program's own stamps and ``nalar.``
host spans, on hand-made events and calls, on the recorded chip trace, and
on a profile the program writes on the CPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spans, trace
from bench.run import Call
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def _ops():
    return [Event(DEV, trace.OPS, "fusion.1", 0 * MS, 4 * MS),
            Event(DEV, trace.OPS, "fusion.2", 6 * MS, 2 * MS)]


def _span(name, start, dur, line="pump"):
    return Event(HOST, line, name, start * MS, dur * MS)


def test_idle_by_span_charges_the_innermost_span():
    # idle: [4, 6) inside the step, with admit over [4.2, 5.5); [8, 10)
    # under no span
    sp = [_span("nalar.engine.step", 3.5, 3),
          _span("nalar.engine.admit", 4.2, 1.3)]
    got = dict(spans.idle_by_span(_ops(), sp, (0.0, 10 * MS)))
    assert got["nalar.engine.admit"] == pytest.approx(1.3e-3)
    assert got["nalar.engine.step"] == pytest.approx(0.7e-3)
    assert got[spans.UNCOVERED] == pytest.approx(2e-3)
    assert sum(got.values()) == pytest.approx(4e-3)


def test_idle_by_span_on_another_thread():
    # a control round opened on another thread during the step's gap takes
    # the instants after its start; the step keeps the rest
    sp = [_span("nalar.engine.step", 3.5, 3),
          _span("nalar.runtime.control_round", 5.0, 2, line="timer")]
    got = dict(spans.idle_by_span(_ops(), sp, (0.0, 10 * MS)))
    assert got["nalar.engine.step"] == pytest.approx(1e-3)
    assert got["nalar.runtime.control_round"] == pytest.approx(1e-3)


def test_host_ms_takes_step_and_delivery_less_device_wait():
    sp = [_span("nalar.engine.step", 0, 10),
          _span("nalar.engine.device_wait", 2, 6),
          _span("nalar.bridge.deliver", 10.5, 1),
          _span("nalar.engine.step", 12, 10),
          _span("nalar.engine.device_wait", 13, 7),
          # a step that starts after the window is left out
          _span("nalar.engine.step", 30, 10),
          _span("nalar.engine.device_wait", 31, 1)]
    # (10 + 1 - 6) and (10 + 0 - 7)
    assert spans.host_ms(sp, (0.0, 25 * MS)) == pytest.approx(4.0)
    assert spans.host_ms(sp, (50 * MS, 60 * MS)) is None


RECORDED = Path(__file__).parent / "data" / "trace_small.json"


def test_recorded_trace_keeps_its_gap_names():
    """The recorded step with a ``nalar.`` span laid over each ``bench.``
    step span: ``reduce`` still names the gaps by the harness's spans, and
    ``idle_by_span`` accounts for every idle nanosecond."""
    rec = json.loads(RECORDED.read_text())
    ev = [Event(*e) for e in rec["events"]]
    window = tuple(rec["window_ns"])
    before = trace.reduce(ev, window)["idle_gaps"]
    nalar = [Event(e.plane, e.line, "nalar.engine.step", e.start_ns,
                   e.dur_ns) for e in ev if e.name == "bench.engine.step"]
    red = trace.reduce(ev + nalar, window)
    assert red["idle_gaps"] == before
    assert {n for n, _s in before} == {"bench.engine.step"}
    got = spans.idle_by_span(ev, nalar, window, top=100)
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _n, s in got) == pytest.approx(idle, rel=1e-9)
    assert dict(got)["nalar.engine.step"] > 0


def _call(context, due, first, ok=True):
    c = Call(session=0, turn=0, due=due, context=np.asarray(context,
                                                            np.int32),
             asked=1, temperature=0.0)
    if ok:
        c.stamps, c.tokens, c.done = [first], [5], True
    return c


def _future(prompt, created, started, value):
    meta = SimpleNamespace(created_at=created, started_at=started)
    return SimpleNamespace(args=(np.asarray(prompt, np.int32),), meta=meta,
                           value=lambda timeout=None: value)


def _value(sub, adm, first, steps=3):
    return SimpleNamespace(submitted_wall=sub, admitted_at=adm,
                           first_token_at=first, finished_at=first + 1,
                           prefill_steps=steps)


def test_calls_find_their_futures_and_stamps():
    # kernel clock = monotonic - 100
    calls = [_call([1, 2, 3], 100.0, 101.0),
             _call([9, 8, 7, 4, 5], 100.5, 102.5),   # a later turn: [4, 5]
             _call([6, 6], 101.0, 0.0, ok=False),
             _call([3, 3], 101.5, 103.0)]
    futs = [_future([4, 5], 0.51, 0.52, _value(100.53, 100.6, 102.4)),
            _future([1, 2, 3], 0.01, 0.02, _value(100.03, 100.2, 100.9)),
            _future([6, 6], 1.01, 1.02, _value(101.03, 101.1, 101.5)),
            # the parent program's value carries no engine stamps
            _future([3, 3], 1.51, 1.52, SimpleNamespace())]
    got = spans.stamps_of(calls, futs, kernel_offset=100.0)
    assert got[2] is None and got[3] is None
    assert got[0]["admitted"] == 100.2 and got[1]["admitted"] == 100.6
    parts = spans.stretches(got[1])
    assert parts["to_future"] == pytest.approx(0.01)
    assert parts["admit_wait"] == pytest.approx(0.07)
    assert parts["prefill"] == pytest.approx(1.8)
    assert parts["delivery"] == pytest.approx(0.1)
    assert sum(parts.values()) == pytest.approx(2.0)
    assert spans.admit_wait_p95_ms(got) == pytest.approx(
        np.percentile([170.0, 70.0], 95))
    assert spans.prefill_p95_ms(got) == pytest.approx(
        np.percentile([700.0, 1800.0], 95))
    assert spans.admit_wait_p95_ms([None]) is None


def test_ttft_split_takes_the_p95_call():
    calls = [_call([i, i], 0.0, 1.0 + i) for i in range(20)]
    futs = [_future([i, i], -99.9, -99.8,
                    _value(0.3, 0.4 + 0.01 * i, 0.9 + i)) for i in range(20)]
    stamps = spans.stamps_of(calls, futs, kernel_offset=100.0)
    got = spans.ttft_split(calls, stamps, give_up=100.0)
    # nearest rank: the 19th of 20 calls (ttft 19 s)
    assert got["ttft_ms"] == pytest.approx(19e3)
    assert got["admit_wait_ms"] == pytest.approx(280.0)
    assert got["remainder_ms"] == pytest.approx(0.0, abs=1e-6)


def test_program_spans_are_read_from_a_cpu_profile(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("nalar.engine.step"):
            with TraceAnnotation("nalar.engine.device_wait"):
                jax.numpy.ones(4).block_until_ready()
        with TraceAnnotation("bench.engine.step"):
            pass
    got = spans.load_spans(str(tmp_path))
    assert sorted(e.name for e in got) == ["nalar.engine.device_wait",
                                           "nalar.engine.step"]
    step, wait = sorted(got, key=lambda e: e.start_ns)
    assert step.line == wait.line
    assert step.start_ns <= wait.start_ns and wait.end_ns <= step.end_ns


def test_span_cost_is_small():
    cost = spans.span_cost_us(200)
    assert 0 < cost["no_session"] < 1000
    assert 0 < cost["session_active"] < 1000


def test_idle_inside_device_wait_by_side_of_the_program():
    # program 1 ends at 4, program 2 starts at 6; the host waits on
    # program 1 over [1, 4.5) and on program 2 over [5.5, 7)
    sp = [_span("nalar.engine.device_wait", 1, 3.5),
          _span("nalar.engine.device_wait", 5.5, 1.5)]
    got = spans.wait_idle(_ops(), sp, (0.0, 10 * MS))
    assert got["after_program"] == pytest.approx(0.5e-3)
    assert got["before_program"] == pytest.approx(0.5e-3)
