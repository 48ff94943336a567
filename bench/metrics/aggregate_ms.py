"""Device self time of the ops in scope ``aggregate``: the weighted
aggregation contractions (``ops.fed_agg``, the accumulate and flush), per
campaign, in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.scope_ms(ctx, "aggregate")
