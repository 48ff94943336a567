"""Device idle inside each campaign's ``campaign.*`` spans that no
``fed.*`` span of the program covers: the part of ``host_gap_ms`` the
program's spans do not explain, per campaign, in ms."""

from bench import program_spans as ps
from bench import trace_reduce as tr


def read(ctx):
    spans, n = ps.of(ctx), len(tr.campaigns(ctx.trace))
    if not spans.fed or not n:
        return None
    return ps.unattributed_ns(ctx.trace, spans) / n * 1e-6
