"""The serving program's own host spans in a traced run, on the device's
clock.

With a ``repro.obs.trace.SpanTracer`` installed, the program's scheduler
opens a ``jax.profiler.TraceAnnotation`` named ``serve.<name>`` for each
span of its loop: one ``serve.iteration`` per turn, with ``upkeep``,
``admit``, ``prefill_chunk``, ``decode_step`` (holding ``decode_dispatch``,
``sample``, ``device_wait`` and ``emit``) and ``on_step`` inside it.
:func:`attach` adds them to a trace extract (``bench/trace.py``):

``spans``           ``[name, start, duration, args]``, ns from the traced
                    window's start, shifted by the offset below and cut to
                    the window;
``host_offset_ns``  how late the profiler's host events sit against its
                    device events, from causal pairs: a ``_decode_impl``
                    program cannot start before the ``serve.decode_dispatch``
                    that launched it began, so the offset is the largest
                    (dispatch begin - program start) over the window's
                    pairs (0 where no program starts early; None where no
                    step pairs).

The reductions here read only that extract, so a recorded one replays them
on the CPU.  A program without the spans (or a run without a tracer) gives
an empty ``spans`` list, and every reduction then returns None.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Optional

from bench import trace

PREFIX = "serve."
DISPATCH = "serve.decode_dispatch"
ITERATION = "serve.iteration"
WAIT = "serve.device_wait"
DECODE = "_decode_impl"


def read(data) -> list:
    """The ``serve.*`` host events of a profile: [name, start_ns,
    duration_ns, args], in the profile's own time."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append([ev.name, ev.start_ns, ev.duration_ns,
                                trace._stats(ev)])
    return sorted(out, key=lambda s: (s[1], -s[2]))


def window_start(data) -> Optional[float]:
    """Start of the traced window (``bench.traced_window``), in the
    profile's time; None where the run marked none."""
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW:
                        return ev.start_ns
    return None


def host_offset_ns(spans: list, modules: list, chip: int = 0
                   ) -> Optional[float]:
    """Largest (dispatch begin - program start) over the window's decode
    steps.  A ``serve.decode_dispatch`` pairs with the one ``_decode_impl``
    program that ends between the dispatch's begin and the end of the
    ``serve.device_wait`` after it (the step's readback waits for that
    program, and the previous program ended before the previous readback);
    a dispatch with no such program, or several, pairs with nothing.  None
    where nothing pairs."""
    progs = sorted((m[1] + m[2], m[1]) for m in modules
                   if DECODE in m[0] and m[3] == chip)
    ends = [e for e, _ in progs]
    waits = sorted((s[1], s[1] + s[2]) for s in spans if s[0] == WAIT)
    diffs = []
    for d in sorted(s[1] for s in spans if s[0] == DISPATCH):
        i = bisect.bisect_left(waits, (d, float("-inf")))
        if i == len(waits):
            continue
        lo = bisect.bisect_left(ends, d)
        if bisect.bisect_right(ends, waits[i][1]) - lo == 1:
            diffs.append(d - progs[lo][1])
    return max(diffs) if diffs else None


def attach(ex: dict, data) -> dict:
    """Add ``spans`` and ``host_offset_ns`` to extract ``ex`` of profile
    ``data`` (see the module docstring); returns ``ex``."""
    t0 = window_start(data)
    raw = read(data)
    if t0 is None:
        t0 = min((s[1] for s in raw), default=0.0)
    rel = [[n, s - t0, d, a] for n, s, d, a in raw]
    off = host_offset_ns(rel, ex["modules"])
    ex["host_offset_ns"] = max(off, 0.0) if off is not None else None
    shift = ex["host_offset_ns"] or 0.0
    kept = []
    for n, s, d, a in rel:
        s0, s1 = max(s - shift, 0.0), min(s - shift + d, ex["window_ns"])
        if s1 > s0:
            kept.append([n, s0, s1 - s0, a])
    ex["spans"] = kept
    return ex


def _inside(s, outer) -> bool:
    return outer[1] <= s[1] and s[1] + s[2] <= outer[1] + outer[2]


def iterations(ex: dict) -> list:
    """Each ``serve.iteration`` wholly inside the window, with the spans it
    holds: [(iteration, [children])]."""
    spans = ex.get("spans") or []
    its = [s for s in spans if s[0] == ITERATION
           and s[1] > 0 and s[1] + s[2] < ex["window_ns"]]
    return [(it, [s for s in spans if s is not it and _inside(s, it)])
            for it in its]


def host_step_ms(ex: dict) -> Optional[float]:
    """Median over the iterations that dispatched a decode step of the
    iteration's length less its ``serve.device_wait``: the host's own time
    per step (ms)."""
    vals = [(it[2] - sum(c[2] for c in kids if c[0] == WAIT)) * 1e-6
            for it, kids in iterations(ex)
            if any(c[0] == DISPATCH for c in kids)]
    return statistics.median(vals) if vals else None


def idle_gaps(ex: dict, chip: int = 0, top: int = 10) -> list:
    """``bench/trace.idle_gaps``, each gap named by the innermost
    ``serve.*`` span covering most of it, else by the benchmark's own
    annotation as there."""
    spans = ex.get("spans") or []
    out = []
    for s, d, name in trace.idle_gaps(ex, chip, top):
        most = [sp for sp in spans
                if min(s + d, sp[1] + sp[2]) - max(s, sp[1]) > d / 2]
        # nested spans: the inner one starts later, or as early and ends
        # sooner
        inner = max(most, key=lambda sp: (sp[1], -sp[2]), default=None)
        out.append((s, d, inner[0] if inner else name))
    return out


def idle_split(ex: dict, chip: int = 0) -> dict:
    """Device idle time of the window under each innermost ``serve.*``
    span ('none' where no span covers it), in ns."""
    ops = sorted((o[2], o[2] + o[3]) for o in ex["ops"] if o[4] == chip)
    gaps, cur = [], 0.0
    for s, e in ops:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if ex["window_ns"] > cur:
        gaps.append((cur, ex["window_ns"]))
    spans = ex.get("spans") or []
    # cut each gap at every span edge; each piece goes to the span that
    # covers it and began last (the innermost)
    edges = sorted({x for sp in spans for x in (sp[1], sp[1] + sp[2])})
    out = {}
    for g0, g1 in gaps:
        cuts = [g0] + [x for x in edges if g0 < x < g1] + [g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
            name = max(cover, key=lambda sp: sp[1])[0] if cover else "none"
            out[name] = out.get(name, 0.0) + (b - a)
    return out
