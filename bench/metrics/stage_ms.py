"""Host time of the partitioner draws and the host staging of the
campaign's shard blocks: the ``fed.stage`` spans and the ``fed.draw``
spans (nested in them or not), per campaign, in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.per_campaign_ms(ctx, "fed.draw", "fed.stage")
