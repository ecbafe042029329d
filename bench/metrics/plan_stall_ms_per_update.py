"""Milliseconds per update in which the program's ``sim.plan`` spans
run inside the traced window and the device runs nothing: the plan time
that no device work hides, averaged over the cell's chips. A program
without the span reads nothing."""
import tracereduce

SPAN = "sim.plan"


def read(ctx):
    updates = ctx.work.get("updates", 0)
    plan = tracereduce.intersect(
        tracereduce.merge((s, e) for n, s, e in ctx.trace.host
                          if n == SPAN), ctx.trace.window)
    chips = ctx.trace.chips(ctx.chips)
    if not plan or not updates or not chips:
        return None
    stall = sum(tracereduce.total(tracereduce.subtract(
        plan, tracereduce.merge((s, e) for _, s, e in ops)))
        for ops in chips) / len(chips)
    return stall / 1e6 / updates
