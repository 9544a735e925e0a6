"""The profiler trace of a traced run, reduced to plain lists.

:class:`Capture` runs JAX's profiler over the window and marks the part
the metrics read; :func:`extract` reads the ``.xplane.pb`` it wrote and keeps, as
JSON-able lists with times in nanoseconds from the traced window's start:

``ops``      device operations: ``[name, module, start, duration, chip]``;
             ``name`` carries the HLO op name and, where the profiler gives
             them, the framework op names (a Pallas kernel's own name rides
             there);
``modules``  compiled programs run on the device: ``[name, start, duration,
             chip]`` (a jitted step's program name holds its function's);
``host``     the benchmark's own annotations (``bench.*``) on the host.

Everything per-layer is read from this extract by ``bench/metrics/*``, so a
recorded extract (``tests/bench/data``) replays the reduction on the CPU.
"""
from __future__ import annotations

import glob
import os
import shutil
from typing import Optional

WINDOW = "bench.traced_window"
KERNELS = ("sals_latent_topk", "sals_recon_attend", "flash_attention")
_OP_STATS = ("long_name", "tf_op", "hlo_category")


class Capture:
    """The profiler runs from :meth:`arm` to :meth:`stop`; the part that
    the metrics read is annotated on the host between :meth:`begin` and
    :meth:`end`, so that starting and stopping the profiler, which stall
    the host, fall outside it."""

    def __init__(self, directory: str):
        self.dir = directory
        self._ann = None
        self.armed = self.started = self.ended = self.stopped = False

    def arm(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.armed = True

    def begin(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self.started = True

    def end(self):
        if self.started and not self.ended:
            self._ann.__exit__(None, None, None)
            self.ended = True

    def stop(self):
        import jax
        self.end()
        if self.armed and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def extract(self) -> Optional[dict]:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        from jax.profiler import ProfileData
        out = extract(ProfileData.from_file(files[0]))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def extract(data) -> dict:
    host = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    win = [h for h in host if h[0] == WINDOW]
    t0, t1 = (win[0][1], win[0][1] + win[0][2]) if win else (None, None)

    def inside(ev):
        return t0 is None or (ev.start_ns < t1
                              and ev.start_ns + ev.duration_ns > t0)

    ops, modules, chips = [], [], 0
    for plane in data.planes:
        # a chip is a device plane with an "XLA Ops" line; other device
        # planes (no ops of their own) are not chips
        if not plane.name.startswith("/device:") or "CPU" in plane.name \
                or not any(ln.name == "XLA Ops" for ln in plane.lines):
            continue
        chip, chips = chips, chips + 1
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [[ev.name, ev.start_ns, ev.duration_ns, chip]
                            for ev in line.events if inside(ev)]
            elif line.name == "XLA Ops":
                for ev in line.events:
                    if not inside(ev):
                        continue
                    st = _stats(ev)
                    label = " ".join([ev.name] + [
                        str(st[k]) for k in _OP_STATS if k in st])
                    ops.append([label, str(st.get("hlo_module", "")),
                                ev.start_ns, ev.duration_ns, chip])
    if t0 is None:
        spans = [(o[2], o[2] + o[3]) for o in ops] or [(0.0, 0.0)]
        t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)

    def cut(rows, si, di):
        kept = []
        for r in rows:
            s, e = max(r[si], t0), min(r[si] + r[di], t1)
            if e > s:
                r = list(r)
                r[si], r[di] = s - t0, e - s
                kept.append(r)
        return kept

    return {"window_ns": t1 - t0, "chips": max(chips, 1),
            "ops": cut(ops, 2, 3), "modules": cut(modules, 1, 2),
            "host": cut([h for h in host if h[0] != WINDOW], 1, 2)}


# -- reductions shared by the per-layer metrics ------------------------------

def union_ns(intervals) -> float:
    """Length of the union of ``(start, duration)`` intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_ns(ex: dict) -> float:
    """Mean over chips of the time some operation ran on the chip."""
    per = [union_ns([(o[2], o[3]) for o in ex["ops"] if o[4] == c])
           for c in range(ex["chips"])]
    return sum(per) / len(per)


def kernel_ns(ex: dict, kernel: str) -> tuple:
    """(total device ns, events) of operations whose name holds
    ``kernel``."""
    hits = [o for o in ex["ops"] if kernel in o[0]]
    return sum(o[3] for o in hits), len(hits)


def module_ns(ex: dict, fn: str) -> tuple:
    """(total device ns, runs) of compiled programs of function ``fn``."""
    hits = [m for m in ex["modules"] if fn in m[0]]
    return sum(m[2] for m in hits), len(hits)


def idle_gaps(ex: dict, chip: int = 0, top: int = 10) -> list:
    """The ``top`` longest idle stretches of one chip: [(start, length, host
    activity)], the host activity being the benchmark annotation that
    covers most of the stretch ('host' where none does)."""
    spans = sorted((o[2], o[2] + o[3]) for o in ex["ops"] if o[4] == chip)
    gaps, cur = [], 0.0
    for s, e in spans:
        if s > cur:
            gaps.append((cur, s - cur))
        cur = max(cur, e)
    if ex["window_ns"] > cur:
        gaps.append((cur, ex["window_ns"] - cur))
    out = []
    for s, d in sorted(gaps, key=lambda g: -g[1])[:top]:
        best, name = 0.0, "host"
        for h in ex["host"]:
            ov = min(s + d, h[1] + h[2]) - max(s, h[1])
            if ov > best:
                best, name = ov, h[0]
        out.append((s, d, name))
    return out


def leaves(ops: list) -> list:
    """The operations that contain no other operation of their chip: the
    trace also lists control-flow ops (a layer loop's ``while``) whose
    interval spans their body's."""
    out = []
    by_chip = {}
    for o in ops:
        by_chip.setdefault(o[4], []).append(o)
    for rows in by_chip.values():
        rows = sorted(rows, key=lambda o: (o[2], -o[3]))
        for i, o in enumerate(rows):
            nxt = rows[i + 1] if i + 1 < len(rows) else None
            if nxt is None or nxt[2] >= o[2] + o[3] or \
                    nxt[2] + nxt[3] > o[2] + o[3]:
                out.append(o)
    return out


def breakdown(ex: dict, top: int = 10) -> dict:
    """The device operations that took most time (leaf ops by HLO op name,
    kernels by their own name), and the longest idle gaps by what the host
    was doing, both in seconds."""
    by = {}
    for o in leaves(ex["ops"]):
        key = next((k for k in KERNELS if k in o[0]), o[0].split(" ")[0])
        by[key] = by.get(key, 0.0) + o[3]
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k[:120], v * 1e-9] for k, v in ops],
            "idle_gaps": [[g[2], g[1] * 1e-9]
                          for g in idle_gaps(ex, top=top)]}
