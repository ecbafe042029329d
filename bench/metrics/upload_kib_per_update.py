"""KiB the run loop sent from host to device per update in the traced
episode (plan tensors and flags, the host-path eval's eval set):
``exec.upload_bytes`` over ``updates`` of the program's own counters
for its last ``RoundEngine.run`` (``repro.obs.last_run``). Read only
where that run is the traced episode, whose updates the harness counted
too; a program without the counters reads nothing."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    run = obs.last_run()
    updates = run.get("updates", 0)
    if not updates or updates != ctx.work.get("updates") \
            or "exec.upload_bytes" not in run:
        return None
    return run["exec.upload_bytes"] / 1024 / updates
