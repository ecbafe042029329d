"""Host seconds of the program's ``exec.build`` spans in the process:
the first call of each executor program, which traces, lowers and
compiles it (or reads it from the persistent cache) and enqueues it.
The window compiles nothing, so this is set-up. A program without the
span reads nothing."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.totals().get("exec.build.seconds")
