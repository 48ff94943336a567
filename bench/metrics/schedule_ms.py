"""Host time of the asynchronous engine's schedule build (the event heap
loop and its allocation solve): the ``fed.schedule`` span, per campaign,
in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.per_campaign_ms(ctx, "fed.schedule")
