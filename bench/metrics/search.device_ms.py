"""Device time per call of the search executable (the XLA module of
the plan's jitted ``chunk_fn``, one call per micro-batch), from the
profiler trace of the window."""


def read(ctx):
    if ctx.trace is None:
        return None
    calls, seconds = ctx.trace.module_time("chunk_fn")
    return 1e3 * seconds / calls if calls else None
