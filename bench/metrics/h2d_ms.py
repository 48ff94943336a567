"""Host time of the copies of the staged blocks and the small arguments
to the device: the ``fed.h2d`` spans, summed, per campaign, in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.per_campaign_ms(ctx, "fed.h2d")
