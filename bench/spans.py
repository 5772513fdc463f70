"""Where a cell's time to first token and the device's idle time go, read
from the program's own stamps and host spans.

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s> \\
        [--keep-trace DIR]

One traced run of the cell, made as ``bench.run --trace 1`` makes it (the
same set-up, traffic, window, trace and checks), that also keeps the value
each ``llm`` future resolved to (``GenerationResult``: the engine's stamps
``submitted_wall``, ``admitted_at``, ``first_token_at``, ``finished_at`` and
``prefill_steps``) and the program's ``nalar.`` host spans in the profile.
It prints one JSON line: the run's result line under ``result``, and beside
it

- ``engine.admit_wait_p95_ms``: p95 over the finished calls due in the
  window of ``admitted_at - submitted_wall`` (the wait for a slot);
- ``engine.prefill_p95_ms``: p95 over the same calls of
  ``first_token_at - admitted_at``;
- ``engine.host_ms``: mean, over the ``nalar.engine.step`` spans that start
  in the traced stretch, of that span plus the ``nalar.bridge.deliver`` that
  follows it, less its ``nalar.engine.device_wait``;
- ``idle_by_span``: the device's idle seconds in the traced stretch, split
  by the innermost ``nalar.`` span over each instant (top 10), and
  ``device_wait_idle``: the idle seconds inside ``device_wait``, before the
  step's program started and after it ended;
- ``ttft_split``: the p95 call's time to first token cut at each stamp,
  and ``stretch_p95_ms``, each stretch's own p95 over the calls;
- ``span_cost_us``: the host cost of one step's spans, with no profiler
  session and with one active.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import run as bench_run
from . import trace
from .latency import p95, ttft_s
from .trace import Event

SPAN_PREFIX = "nalar."
STEP, DEVICE_WAIT = "nalar.engine.step", "nalar.engine.device_wait"
DELIVER = "nalar.bridge.deliver"
UNCOVERED = "host: no nalar span"
# one paged step's spans, nested as the engine opens them
STEP_SPANS = (STEP, ("nalar.engine.admit", "nalar.engine.prepare",
                     "nalar.kv.append", "nalar.engine.dispatch",
                     "nalar.kv.commit", DEVICE_WAIT, "nalar.engine.sample",
                     "nalar.engine.emit"), DELIVER)
STRETCHES = ("to_future", "runtime_wait", "bridge_handover", "admit_wait",
             "prefill", "delivery")


# ------------------------------------------------------------ the profile
def load_spans(log_dir: str) -> List[Event]:
    """The program's host spans in the profile under ``log_dir``; an
    event's ``line`` tells the threads apart."""
    import jax
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        return []
    out = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for n, line in enumerate(plane.lines):
            out += [Event(plane.name, f"{line.name}#{n}", e.name,
                          float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out


def idle_gaps(events: List[Event],
              window_ns: Tuple[float, float]) -> List[Tuple[float, float]]:
    """Stretches of the window in which a device ran no op, over every
    device plane."""
    lo, hi = window_ns
    gaps = []
    for p in trace.device_planes(events):
        u = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
             if e.plane == p and e.line == trace.OPS]
        edges = ([lo] + [x for iv in trace._union([iv for iv in u
                                                    if iv[1] > iv[0]])
                         for x in iv] + [hi])
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def idle_by_span(events: List[Event], spans: List[Event],
                 window_ns: Tuple[float, float], top: int = 10) -> list:
    """Device-idle seconds of the window by the innermost ``nalar.`` span
    over each idle instant (the covering span that started last; of two
    that started together, the shorter): each span is charged its self
    time only.  Idle time no span covers goes under ``UNCOVERED``.  Seconds
    are averaged over the device planes, largest first."""
    n = max(1, len(trace.device_planes(events)))
    spans = sorted(spans, key=lambda e: e.start_ns)
    out: Dict[str, float] = {}
    for s, e in idle_gaps(events, window_ns):
        over = [x for x in spans if x.start_ns < e and x.end_ns > s]
        cuts = sorted({s, e} | {t for x in over
                                for t in (x.start_ns, x.end_ns)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [x for x in over if x.start_ns <= a and x.end_ns >= b]
            name = (max(cover, key=lambda x: (x.start_ns, -x.dur_ns)).name
                    if cover else UNCOVERED)
            out[name] = out.get(name, 0.0) + (b - a)
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9 / n] for k, v in ranked]


def wait_idle(events: List[Event], spans: List[Event],
              window_ns: Tuple[float, float]) -> Dict[str, float]:
    """Device-idle seconds inside ``nalar.engine.device_wait`` spans, split
    by which side of the step's program they lie on: ``before_program``
    where the device was already idle when the host began to wait (the
    dispatched program had not started), ``after_program`` where the host
    was already waiting when the device went idle (the program had ended,
    the host had not yet seen it)."""
    n = max(1, len(trace.device_planes(events)))
    waits = [x for x in spans if x.name == DEVICE_WAIT]
    out = {"before_program": 0.0, "after_program": 0.0}
    for s, e in idle_gaps(events, window_ns):
        for w in waits:
            a, b = max(s, w.start_ns), min(e, w.end_ns)
            if b > a:
                side = "after_program" if w.start_ns <= s else \
                    "before_program"
                out[side] += (b - a) * 1e-9 / n
    return out


def host_ms(spans: List[Event], window_ns: Tuple[float, float]):
    """Mean host milliseconds of an engine step that starts in the window:
    its ``nalar.engine.step`` span plus the ``nalar.bridge.deliver`` that
    follows it on the same thread, less the ``nalar.engine.device_wait``
    inside it.  None without such a step."""
    lo, hi = window_ns
    per_line: Dict[str, List[Event]] = {}
    for x in sorted(spans, key=lambda e: e.start_ns):
        per_line.setdefault(x.line, []).append(x)
    times = []
    for line in per_line.values():
        steps = [i for i, x in enumerate(line) if x.name == STEP]
        for k, i in enumerate(steps):
            st = line[i]
            if not lo <= st.start_ns < hi:
                continue
            nxt = steps[k + 1] if k + 1 < len(steps) else len(line)
            wait = sum(x.dur_ns for x in line[i + 1:nxt]
                       if x.name == DEVICE_WAIT and x.end_ns <= st.end_ns)
            deliver = next((x.dur_ns for x in line[i + 1:nxt]
                            if x.name == DELIVER
                            and x.start_ns >= st.end_ns), 0.0)
            times.append(st.dur_ns + deliver - wait)
    return 1e-6 * sum(times) / len(times) if times else None


# ------------------------------------------------------------ the calls
def keep_values(h, futures: list, served: dict) -> None:
    """Hooks on a built harness: every future the bridge takes is kept, and
    so is what ``Harness.serve`` returns (the calls due in the window)."""
    submit, serve = h.bridge.submit_future, h.serve
    # the runtime's kernel clock is time.monotonic() less a fixed offset
    served["kernel_offset"] = time.monotonic() - h.rt.kernel.now()

    def kept_submit(fut, controller, method):
        futures.append(fut)
        return submit(fut, controller, method)

    def kept_serve(*a, **kw):
        out = serve(*a, **kw)
        served.update(out)
        return out

    h.bridge.submit_future, h.serve = kept_submit, kept_serve


def stamps_of(calls, futures, kernel_offset: float) -> List[Optional[dict]]:
    """For each call, the monotonic stamps of its way to the first token,
    from its future (matched by the prompt the call sent, the end of its
    context); None where the call did not finish or the program lacks the
    engine's stamps."""
    by_prompt: Dict[Tuple[int, bytes], object] = {}
    for f in futures:
        toks = np.asarray(f.args[0], np.int32)
        by_prompt[(len(toks), toks.tobytes())] = f
    lengths = sorted({n for n, _b in by_prompt})
    out = []
    for c in calls:
        ctx = np.asarray(c.context, np.int32)
        fut = value = None
        for n in (n for n in lengths if n <= len(ctx)):
            fut = by_prompt.get((n, ctx[len(ctx) - n:].tobytes()))
            if fut is not None:
                break
        if fut is not None and c.ok:
            value = fut.value(timeout=0)
        if getattr(value, "admitted_at", -1.0) < 0:
            out.append(None)
            continue
        out.append({"due": c.due,
                    "future": fut.meta.created_at + kernel_offset,
                    "started": fut.meta.started_at + kernel_offset,
                    "submitted": value.submitted_wall,
                    "admitted": value.admitted_at,
                    "first_token": value.first_token_at,
                    "client_first": c.stamps[0],
                    "prefill_steps": value.prefill_steps})
    return out


def stretches(s: dict) -> Dict[str, float]:
    """Seconds from due to the client's first token, cut at each stamp."""
    marks = (s["due"], s["future"], s["started"], s["submitted"],
             s["admitted"], s["first_token"], s["client_first"])
    return {k: b - a for k, a, b in zip(STRETCHES, marks, marks[1:])}


def admit_wait_p95_ms(stamps: Sequence[Optional[dict]]):
    return p95([(s["admitted"] - s["submitted"]) * 1e3
                for s in stamps if s is not None])


def prefill_p95_ms(stamps: Sequence[Optional[dict]]):
    return p95([(s["first_token"] - s["admitted"]) * 1e3
                for s in stamps if s is not None])


def ttft_split(calls, stamps, give_up: float) -> Optional[dict]:
    """The stretches of the call at the 95th percentile's rank of time to
    first token (nearest rank), with what they leave unexplained."""
    ranked = sorted(range(len(calls)), key=lambda i: ttft_s(calls[i],
                                                             give_up))
    if not ranked:
        return None
    i = ranked[max(0, math.ceil(0.95 * len(ranked)) - 1)]
    if stamps[i] is None:
        return None
    parts = {k: v * 1e3 for k, v in stretches(stamps[i]).items()}
    ttft = ttft_s(calls[i], give_up) * 1e3
    return {"ttft_ms": ttft, **{f"{k}_ms": v for k, v in parts.items()},
            "remainder_ms": ttft - sum(parts.values()),
            "prefill_steps": stamps[i]["prefill_steps"]}


# ------------------------------------------------------------ span cost
def span_cost_us(iterations: int = 20000) -> Dict[str, float]:
    """Microseconds of one paged step's spans (the step, its eight phases
    and the delivery after it), opened and closed in a loop with nothing
    inside: with no profiler session, then with one active."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    step, phases, deliver = STEP_SPANS

    def loop() -> float:
        t = time.perf_counter()
        for _ in range(iterations):
            with annotate(step):
                for name in phases:
                    with annotate(name):
                        pass
            with annotate(deliver):
                pass
        return (time.perf_counter() - t) / iterations * 1e6

    off = loop()
    log_dir = tempfile.mkdtemp(prefix="bench-span-cost-")
    opts = jax.profiler.ProfileOptions()     # as the harness traces
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        on = loop()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(log_dir, ignore_errors=True)
    return {"no_session": off, "session_active": on}


# ------------------------------------------------------------ the run
def read(result: dict, served: dict, futures: list, log_dir: str) -> dict:
    """The readings above, from one finished traced run."""
    calls, give_up = served["calls"], served["give_up"]
    stamps = stamps_of(calls, futures, served["kernel_offset"])
    events = trace.load(log_dir)
    spans = load_spans(log_dir)
    ops = [e for e in events if e.line == trace.OPS]
    lo = min((e.start_ns for e in ops), default=0.0)
    # the traced stretch on the trace's clock, as the harness takes it
    window = (lo, lo + result["device"]["window_s"] * 1e9)
    per = {k: [v * 1e3 for v in vals] for k, vals in zip(
        STRETCHES, zip(*[stretches(s).values() for s in stamps if s]))}
    return {"engine.admit_wait_p95_ms": admit_wait_p95_ms(stamps),
            "engine.prefill_p95_ms": prefill_p95_ms(stamps),
            "engine.host_ms": host_ms(spans, window),
            "idle_by_span": idle_by_span(events, spans, window),
            "idle_s": sum(e - s for s, e in idle_gaps(events, window))
            * 1e-9 / max(1, len(trace.device_planes(events))),
            "device_wait_idle": wait_idle(events, spans, window),
            "ttft_split": ttft_split(calls, stamps, give_up),
            "stretch_p95_ms": {k: p95(v) for k, v in per.items()},
            "calls_with_stamps": sum(s is not None for s in stamps),
            "calls": len(calls)}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep-trace", default=None,
                   help="write the profiler trace here and keep it")
    args = p.parse_args(argv)
    spec = bench_run.cell_spec(args.workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    from repro.configs import get_config
    bench_run.require_chips(int(spec["cell"]["chips"]))
    bench_run.use_cache()
    hf = spec["config"]
    model_cfg = get_config(hf["program_arch"])
    bench_run.check_program_config(model_cfg, hf)
    futures: list = []
    served: dict = {}
    log_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench-spans-")
    result = bench_run.run_cell(
        spec=spec, model_cfg=model_cfg, seed=args.seed,
        window_s=args.seconds, trace=True, t_start=bench_run.T_PROCESS,
        on_harness=lambda h: keep_values(h, futures, served),
        keep_trace=log_dir)
    out = read(result, served, futures, log_dir)
    if args.keep_trace is None:
        shutil.rmtree(log_dir, ignore_errors=True)
    out["span_cost_us"] = span_cost_us()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "spans": out, "result": result}), flush=True)


if __name__ == "__main__":
    main()
