"""The program's own spans and device scopes in a profiler trace.

The engines under ``src/repro/fed/`` write host spans named ``fed.<phase>``
(``TraceAnnotation``, with counts such as ``bytes`` as event stats) and tag
their device ops with ``jax.named_scope`` scopes (``local_train``,
``aggregate``, ``eval``, ``policy_solve``). ``bench.trace_reduce.load``
keeps neither; ``load`` here reads them from the same ``.xplane.pb``:

  fed     host events whose name starts with ``fed.``:
          ``(name, start_ns, end_ns, stats)``
  scoped  per device, ``(start_ns, end_ns, op-name path)`` of each op on
          the ``XLA Ops`` line

An op's op-name path (``jit(_fused_cycles)/while/body/.../local_train/...``)
is the ``tf_op`` stat of its event metadata on the device plane, which
``jax.profiler.ProfileData`` does not expose: ``op_paths`` reads it from
the file's protobuf wire format, skipping the event lines.

A trace of a program without these spans or scopes gives empty lists or
paths without the scopes, and every reader here then returns None. The
reductions are checked by ``bench/tests/test_program_spans.py`` on a
recorded v5e trace.
"""

from __future__ import annotations

import dataclasses
import warnings

from bench import trace_reduce as tr

FED_PREFIX = "fed."
#: the event-metadata stat that holds an op's op-name path
PATH_STAT = "tf_op"


@dataclasses.dataclass
class Spans:
    fed: list          # [(name, start, end, stats)], host, by start
    scoped: dict       # device index -> [(start, end, op-name path)]

    @staticmethod
    def from_json(obj: dict) -> "Spans":
        """From a recorded trace; one without these keys gives no spans."""
        return Spans([(n, s, e, dict(st)) for n, s, e, st in obj.get("fed", [])],
                     {int(k): [tuple(x) for x in v]
                      for k, v in obj.get("scoped", {}).items()})


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for varints, a memoryview for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            size = {1: 8, 5: 4}.get(wire)
            if size is None:            # 2: length-delimited
                size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_paths(path: str) -> dict:
    """Per device index, each op event name's op-name path: the ``tf_op``
    stat of the device plane's event metadata (``XSpace.planes`` = 1;
    ``XPlane``: name = 2, event_metadata = 4, stat_metadata = 5; map
    entries: key = 1, value = 2; ``XEventMetadata``: name = 2, stats = 5;
    ``XStatMetadata``: id = 1, name = 2; ``XStat``: metadata_id = 1,
    str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda v: bytes(v).decode()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = {2: [], 4: [], 5: []}
        for g, v in _fields(plane):
            if g in parts:
                parts[g].append(v)
        m = parts[2] and tr.DEVICE_PLANE.match(text(parts[2][0]))
        if not m:
            continue
        stat_names = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry))[2]))
            stat_names[meta.get(1, 0)] = text(meta.get(2, b""))
        paths = out.setdefault(int(m.group(1)), {})
        for entry in parts[4]:
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next((text(v) for g, v in meta if g == 2), "")
            for g, v in meta:
                stat = dict(_fields(v)) if g == 5 else {}
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    paths[name] = (text(stat[5]) if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
    return out


def load(path: str) -> Spans:
    from jax.profiler import ProfileData

    paths = op_paths(path)
    fed, scoped = [], {}
    with warnings.catch_warnings():
        # the first read of an event's stats builds a nanobind type that
        # warns (no __module__); where warnings are errors that aborts
        warnings.simplefilter("ignore", DeprecationWarning)
        planes = ProfileData.from_file(path).planes
        for plane in planes:
            m = tr.DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == tr.OPS_LINE:
                    names = paths.get(int(m.group(1)), {})
                    scoped.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         names.get(e.name, ""))
                        for e in line.events)
                elif not m:
                    fed.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)) for e in line.events
                               if e.name.startswith(FED_PREFIX))
    return Spans(sorted(fed, key=lambda s: s[1]), scoped)


def of(ctx) -> Spans:
    """The spans of the traced run that ``ctx`` describes, loaded once from
    the harness's trace directory and kept on ``ctx``."""
    if getattr(ctx, "program_spans", None) is None:
        from bench.harness import TRACE_DIR

        ctx.program_spans = load(tr.find_xplane(str(TRACE_DIR)))
    return ctx.program_spans


def in_campaigns(trace: tr.Trace, spans: Spans, names) -> list:
    """The ``fed.*`` spans named in ``names`` that lie inside a campaign."""
    camps = tr.campaigns(trace)
    return [f for f in spans.fed if f[0] in names
            and any(s <= f[1] and f[2] <= e for s, e in camps)]


def per_campaign_ms(ctx, *names):
    """Host time under the ``fed.*`` spans ``names`` (the length of their
    union, so a span nested in another counts once) per campaign, in ms;
    None where the trace has none."""
    found = in_campaigns(ctx.trace, of(ctx), names)
    if not found:
        return None
    ns = sum(e - s for s, e in tr.union([(n, s, e) for n, s, e, _ in found]))
    return ns / len(tr.campaigns(ctx.trace)) * 1e-6


def unattributed_ns(trace: tr.Trace, spans: Spans) -> float:
    """Device idle inside the campaigns that no ``fed.*`` span covers, in
    ns, averaged over devices (the rule of ``host_gap_ms``)."""
    covered = tr.union([(n, s, e) for n, s, e, _ in spans.fed])
    total = 0.0
    for ev in trace.ops.values():
        busy = tr.union(ev)
        for lo, hi in tr.campaigns(trace):
            total += sum((e - s) - tr.covered(covered, s, e)
                         for s, e in tr.gaps(busy, lo, hi))
    return total / max(len(trace.ops), 1)


def scope_ns(trace: tr.Trace, spans: Spans, scope: str):
    """Device self time (``trace_reduce.self_times``' nesting rule) of the
    window's ops whose op-name path holds ``/<scope>/``, in ns, averaged
    over devices; None where no op carries the scope."""
    lo, hi = tr.window(trace)
    key = f"/{scope}/"
    total, seen = 0.0, False
    for ev in spans.scoped.values():
        t = tr.self_times([(str(key in f"/{p}/"), s, e) for s, e, p in ev
                           if s >= lo and e <= hi])
        seen = seen or "True" in t
        total += t.get("True", 0.0)
    return total / max(len(spans.scoped), 1) if seen else None


def scope_ms(ctx, scope: str):
    """``scope_ns`` per campaign (the count ``program_ms`` divides by),
    in ms; None where no op carries the scope."""
    ns = scope_ns(ctx.trace, of(ctx), scope)
    if ns is None or not ctx.campaigns:
        return None
    return ns / len(ctx.campaigns) * 1e-6
