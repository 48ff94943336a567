"""Device self time of the ops in scope ``policy_solve``: the traced
KKT/SAI allocation solve inside the realloc scan, per campaign, in ms."""

from bench import program_spans as ps


def read(ctx):
    return ps.scope_ms(ctx, "policy_solve")
