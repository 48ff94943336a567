"""Host bytes staged for the campaign program: the ``bytes`` stats of the
``fed.stage`` spans, summed, per campaign, in MB (1e6 bytes)."""

from bench import program_spans as ps
from bench import trace_reduce as tr


def read(ctx):
    found = ps.in_campaigns(ctx.trace, ps.of(ctx), ("fed.stage",))
    if not found:
        return None
    staged = sum(stats.get("bytes", 0) for *_, stats in found)
    return staged / len(tr.campaigns(ctx.trace)) * 1e-6
