"""Device busy time of the campaign's XLA program (the traffic's
``program``: ``_fused_cycles``, ``_fused_realloc_cycles`` or
``_bucketed_events``), per campaign, in ms."""

from bench import trace_reduce as tr


def read(ctx):
    ns = tr.module_busy(ctx.trace, ctx.program)
    if ns <= 0 or not ctx.campaigns:
        return None
    return ns / len(ctx.campaigns) * 1e-6
