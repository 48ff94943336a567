"""Share of the campaign program's device time that the useful training
work (``bench/work.py``, counted by the learner model's family) needs at
the chip's peaks: the larger of its FLOPs at peak FLOP/s and its
unavoidable bytes at peak HBM bandwidth, over the program's device-busy
time, in %."""

from bench import trace_reduce as tr
from bench import work


def read(ctx):
    ns = tr.module_busy(ctx.trace, ctx.program)
    if ns <= 0 or not ctx.campaigns:
        return None
    rows = [r for c in ctx.campaigns for r in c.schedule]
    return 100.0 * work.least_seconds(ctx.model, ctx.cfg, rows, ctx.peak) / (ns * 1e-9)
