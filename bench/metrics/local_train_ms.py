"""Device self time of the ops in scope ``local_train``: the learners'
local GD steps (or the train+aggregate megakernel), per campaign, in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.scope_ms(ctx, "local_train")
