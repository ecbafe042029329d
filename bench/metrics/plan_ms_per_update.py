"""Host milliseconds of the program's ``sim.plan`` spans inside the
traced window, per update: everything the host does before an executor
call (the plan chain, the search for the next visited tick, index
sampling, the schedule tensors). A program without the span reads
nothing."""
import tracereduce

SPAN = "sim.plan"


def read(ctx):
    updates = ctx.work.get("updates", 0)
    plan = tracereduce.intersect(
        tracereduce.merge((s, e) for n, s, e in ctx.trace.host
                          if n == SPAN), ctx.trace.window)
    if not plan or not updates:
        return None
    return tracereduce.total(plan) / 1e6 / updates
