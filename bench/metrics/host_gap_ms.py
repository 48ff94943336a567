"""Device idle time inside each campaign's ``campaign.*`` spans: what the
host (engine construction, allocation, partitioner, shard and group
staging, schedule build) holds the chip back, per campaign, in ms."""

from bench import trace_reduce as tr


def read(ctx):
    spans = tr.campaigns(ctx.trace)
    if not spans:
        return None
    idle = sum((e - s) - tr.busy(ctx.trace, s, e) for s, e in spans)
    return idle / len(spans) * 1e-6
