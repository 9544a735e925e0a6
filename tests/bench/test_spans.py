"""The reduction that puts the program's own host spans beside the device
trace (``bench/spans.py``), on the CPU: the host clock's offset from causal
pairs and the shift it makes, the iteration and idle-gap attributions, and
that none of it moves a metric the benchmark has.
"""
from __future__ import annotations

import copy
import glob
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import spans, trace  # noqa: E402

PARENT_RECORDING = os.path.join(DATA, "mistral7b-l8.decode32k_b4.trace.json")
SPAN_RECORDING = os.path.join(DATA,
                              "mistral7b-l8.decode32k_b4.spans.trace.json")
MS = 1e6


def profile(host_events):
    """A stand-in for ``jax.profiler.ProfileData``: one host plane holding
    ``(name, start_ns, duration_ns, stats)`` events."""
    evs = [NS(name=n, start_ns=s, duration_ns=d, stats=list(a.items()))
           for n, s, d, a in host_events]
    return NS(planes=[NS(name="/host:CPU", lines=[NS(name="t", events=evs)])])


def decode_run(skew, n=5, period=70 * MS, launch=0.3 * MS):
    """An extract of ``n`` decode steps and the profile of their host
    spans, whose host events are stamped ``skew`` ns late.  Each program
    starts ``launch`` ns after its dispatch begins.  As in a real extract,
    the device's events are cut at the traced window's start on the host's
    (late) clock."""
    t0 = 100 * MS                                # true window start
    modules, ops, host = [], [], [(trace.WINDOW, t0 + skew, n * period, {})]
    for k in range(n):
        it = t0 + k * period + 1 * MS            # true host times
        disp = it + 0.5 * MS
        prog = disp + launch - t0 - skew         # in the extract's frame
        if prog < 0:                             # cut at the window's start
            continue
        modules.append(["jit__decode_impl", prog, 60 * MS, 0])
        ops.append(["fusion.1", "", prog, 60 * MS, 0])
        host += [("serve.iteration", it + skew, period - 2 * MS,
                  {"step": k}),
                 ("serve.decode_dispatch", disp + skew, 0.4 * MS, {}),
                 ("serve.device_wait", disp + skew + 0.5 * MS, 62 * MS, {})]
    ex = {"window_ns": n * period, "chips": 1, "ops": ops,
          "modules": modules, "host": []}
    return ex, profile(host)


@pytest.mark.parametrize("skew", [0.0, 0.25 * MS, 1.0 * MS, 3.0 * MS])
def test_offset_is_estimated_from_causal_pairs_and_shifted_out(skew):
    ex, data = decode_run(skew)
    spans.attach(ex, data)
    # the estimate is the skew less the smallest dispatch-to-start time
    assert ex["host_offset_ns"] == pytest.approx(max(skew - 0.3 * MS, 0.0))
    # after the shift every program starts at or after its dispatch began
    disp = [s[1] for s in ex["spans"] if s[0] == spans.DISPATCH]
    for m in ex["modules"]:
        assert any(d <= m[1] + 1e-6 < d + 1 * MS for d in disp), m
    assert (spans.host_offset_ns(ex["spans"], ex["modules"]) or 0.0) <= 1e-6
    assert all(0.0 <= s[1] and s[1] + s[2] <= ex["window_ns"] + 1e-6
               for s in ex["spans"])


def test_steps_pair_by_the_readback_not_by_nearness():
    """A dispatch pairs with the program its readback waited for, even
    when prefill chunks queued ahead of it start it long after the dispatch
    and nearer the next step's dispatch; a step cut by the window pairs with
    nothing."""
    spans_, modules = [], []
    for k in range(4):
        d = k * 200 * MS
        spans_ += [[spans.DISPATCH, d, 0.3 * MS, {}],
                   [spans.WAIT, d + 1 * MS, 150 * MS, {}]]
        # chunk programs run first: the decode program starts 100 ms late
        modules.append(["jit__decode_impl", d + 100 * MS - 0.2 * MS,
                        45 * MS, 0])
    assert spans.host_offset_ns(spans_, modules) == pytest.approx(-99.8 * MS)
    # the first step's program ran before the window: nothing pairs with it
    assert spans.host_offset_ns(spans_, modules[1:]) == \
        pytest.approx(-99.8 * MS)
    assert spans.host_offset_ns(spans_[2:], modules[:1]) is None
    assert spans.host_offset_ns([], modules) is None


def test_host_step_is_iteration_less_device_wait():
    ex, data = decode_run(1.0 * MS)
    spans.attach(ex, data)
    # whole iterations only
    assert len(spans.iterations(ex)) == 5
    ex["spans"][-3][2] += 3 * MS                 # the last one runs past
    assert len(spans.iterations(ex)) == 4
    assert spans.host_step_ms(ex) == pytest.approx(68.0 - 62.0)
    ex["spans"] = [s for s in ex["spans"] if s[0] != spans.DISPATCH]
    assert spans.host_step_ms(ex) is None     # no decode step dispatched


def test_idle_is_named_by_the_innermost_span_covering_it():
    ex = {"window_ns": 100.0, "chips": 1, "modules": [],
          "ops": [["f", "", 0.0, 40.0, 0], ["g", "", 70.0, 30.0, 0]],
          "host": [["bench.decode_call", 40.0, 30.0]],
          "spans": [["serve.iteration", 38.0, 40.0, {}],
                    ["serve.emit", 40.0, 10.0, {}],
                    ["serve.on_step", 50.0, 4.0, {}],
                    ["serve.decode_dispatch", 62.0, 9.0, {}]]}
    split = spans.idle_split(ex)
    assert split == {"serve.emit": 10.0, "serve.on_step": 4.0,
                     "serve.iteration": 8.0, "serve.decode_dispatch": 8.0}
    assert sum(split.values()) == 100.0 - trace.busy_ns(ex)
    # the one gap (40-70): the iteration alone covers most of it; a longer
    # emit, inside it, takes it over; with no span over half of it the
    # benchmark's own annotation names it, as before
    assert [g[2] for g in spans.idle_gaps(ex)] == ["serve.iteration"]
    ex["spans"][1][2] = 20.0
    assert [g[2] for g in spans.idle_gaps(ex)] == ["serve.emit"]
    ex["spans"] = ex["spans"][2:]
    assert [g[2] for g in spans.idle_gaps(ex)] == ["bench.decode_call"]
    ex["spans"] = []
    assert spans.idle_split(ex) == {"none": 30.0}


def test_attach_reads_spans_from_a_cpu_profile(tmp_path):
    import jax

    from repro.obs.trace import SpanTracer
    tr = SpanTracer()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with tr.span("iteration", "scheduler", step=7, n_live=2):
                with tr.span("device_wait", "scheduler"):
                    jax.numpy.ones(8).block_until_ready()
            tr.end(tr.begin("teardown", "req1"))   # not mirrored
    from jax.profiler import ProfileData
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    data = ProfileData.from_file(path)
    ex = trace.extract(data)
    assert ex["host"] == []                   # the bench.* list as before
    spans.attach(ex, data)
    names = [s[0] for s in ex["spans"]]
    assert names == ["serve.iteration", "serve.device_wait"]
    assert ex["spans"][0][3] == {"step": 7, "n_live": 2}
    assert ex["host_offset_ns"] is None       # no device program on a CPU


# -- recordings -----------------------------------------------------------

def recording_context(path):
    from bench import harness, model
    from bench.traffic import load_traffic
    with open(path) as f:
        rec = json.load(f)
    steps = [tuple(s) for s in rec["steps"]]
    cfg = model.load_config(rec["config"])
    am = model.load_module(cfg["architecture"])
    return harness.Context(
        recs=[], extract=rec["extract"], steps=steps,
        tr0=min(s[0] for s in steps), tr1=max(s[0] for s in steps),
        arch=am.Arch(cfg), traffic=load_traffic(rec["traffic"]),
        counts=am.counts, trace=trace,
        peaks=model.load_json("peaks.json"), device_kind=rec["device_kind"])


def read_metric(name, ctx):
    from bench import harness
    return harness._read_metric(name, ctx)


def per_layer_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


@pytest.mark.parametrize("name", per_layer_metrics())
def test_spans_move_no_metric_the_benchmark_had(name):
    """Attaching spans to the recording made before the program had any
    leaves every per-layer metric reading what it read."""
    before = recording_context(PARENT_RECORDING)
    after = recording_context(PARENT_RECORDING)
    ex = after.extract
    ex["spans"] = [["serve.iteration", m[1] - 3e6, 70e6, {}]
                   for m in ex["modules"] if "_decode_impl" in m[0]]
    ex["host_offset_ns"] = 1e6
    assert read_metric(name, after) == read_metric(name, before)


def span_recording():
    if not os.path.exists(SPAN_RECORDING):
        pytest.fail(f"missing chip recording {SPAN_RECORDING}")
    return recording_context(SPAN_RECORDING)


def test_host_spans_of_the_chip_recording():
    """The recorded run's spans, shifted by its offset: every decode
    program starts at or after its dispatch, and the host's time per step
    and the idle under the loop's parts are there to read."""
    ctx = span_recording()
    ex = copy.deepcopy(ctx.extract)
    assert ex["host_offset_ns"] is not None and ex["host_offset_ns"] >= 0
    assert (spans.host_offset_ns(ex["spans"], ex["modules"]) or 0.0) <= 1e-3
    step = spans.host_step_ms(ex)
    assert step is not None and 0.1 < step < 20.0
    split = spans.idle_split(ex)
    idle = ex["window_ns"] - trace.busy_ns(ex)
    assert sum(split.values()) == pytest.approx(idle)
    own = split.get("serve.iteration", 0.0) + split.get("none", 0.0)
    assert own <= 0.2 * idle
