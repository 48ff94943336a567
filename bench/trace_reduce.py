"""Reduce a profiler trace to the intervals the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each as ``(name, start_ns, end_ns)``:

  ops      the ``XLA Ops`` line of each device plane (``/device:TPU:<n>``)
  modules  the ``XLA Modules`` line of the same planes: one event per
           executed XLA program, named after the jitted function
  spans    host events whose name starts with ``campaign.`` or
           ``bench.`` (the benchmark's own ``TraceAnnotation`` spans)

The reductions are plain interval arithmetic, checked by
``bench/tests/test_trace_reduce.py`` on a recorded v5e trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("campaign.", "bench.")


@dataclasses.dataclass
class Trace:
    ops: dict          # device index -> [(name, start, end)]
    modules: dict      # device index -> [(name, start, end)]
    spans: list        # [(name, start, end)], host

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        conv = lambda d: {int(k): [tuple(e) for e in v] for k, v in d.items()}
        return Trace(conv(obj["ops"]), conv(obj["modules"]),
                     [tuple(e) for e in obj["spans"]])


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    ops, modules, spans = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules).setdefault(
                    int(m.group(1)), [])
                dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
            elif not m:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(ops, modules, sorted(spans, key=lambda s: s[1]))


def union(events) -> list:
    """Merged, sorted ``[start, end]`` intervals covered by ``events``."""
    out = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals, lo, hi) -> float:
    """Length of ``[lo, hi]`` that the merged ``intervals`` cover."""
    return float(sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals))


def gaps(intervals, lo, hi) -> list:
    """Uncovered ``[start, end]`` stretches of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in intervals:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return out


def span_at(spans, lo, hi) -> str:
    """The ``campaign.*`` span that overlaps ``[lo, hi]`` most, else the
    ``bench.*`` span that does, else ``outside``."""
    best, best_key = "outside", (False, 0.0)
    for name, s, e in spans:
        ov = min(e, hi) - max(s, lo)
        key = (not name.startswith("bench."), ov)
        if ov > 0 and key > best_key:
            best, best_key = name, key
    return best


def window(trace: Trace) -> tuple[float, float]:
    """The traced window: the ``bench.window`` span."""
    for name, s, e in trace.spans:
        if name == "bench.window":
            return s, e
    raise ValueError("no bench.window span in the trace")


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Device-busy nanoseconds in ``[lo, hi]``, averaged over devices."""
    if not trace.ops:
        return 0.0
    return sum(covered(union(ev), lo, hi) for ev in trace.ops.values()) / len(trace.ops)


def module_busy(trace: Trace, program: str) -> float:
    """Device-busy nanoseconds inside executions of the XLA program of the
    jitted function ``program``, averaged over devices."""
    pat = re.compile(r"^jit_" + re.escape(program) + r"(\(\d+\))?$")
    total = 0.0
    for dev, mods in trace.modules.items():
        merged = union(trace.ops.get(dev, []))
        total += sum(covered(merged, s, e) for name, s, e in mods
                     if pat.match(name))
    return total / max(len(trace.modules), 1)


def campaigns(trace: Trace) -> list:
    """``[start, end]`` of each campaign: from its ``campaign.build`` to
    the end of its ``campaign.read``."""
    out, start = [], None
    for name, s, e in trace.spans:
        if name == "campaign.build":
            start = s
        elif name == "campaign.read" and start is not None:
            out.append([start, e])
            start = None
    return out


def self_times(events) -> dict:
    """Per op name, the device time not covered by ops nested inside it (a
    ``while`` op spans its body's ops), in ns."""
    out: dict = {}
    stack: list = []            # [name, end, self time]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + item[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name.split(" = ", 1)[0], e, e - s])
    for item in stack:
        close(item)
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (self time, by HLO instruction
    name) and the longest idle gaps of the window, each gap named by the
    host span it fell in (seconds)."""
    lo, hi = window(trace)
    per_op: dict = {}
    for ev in trace.ops.values():
        inside = [x for x in ev if x[1] >= lo and x[2] <= hi]
        for name, t in self_times(inside).items():
            per_op[name] = per_op.get(name, 0.0) + t
    n_dev = max(len(trace.ops), 1)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    first = min(trace.ops) if trace.ops else None
    idle = gaps(union(trace.ops.get(first, [])), lo, hi)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v / n_dev * 1e-9] for n, v in ops],
            "idle_gaps": [[span_at(trace.spans, s, e), (e - s) * 1e-9]
                          for s, e in idle]}

