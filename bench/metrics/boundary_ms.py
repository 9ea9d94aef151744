"""Host time of the scheduler's micro-run boundaries, per micro-run: the
``ContinuousScheduler`` counter ``boundary_seconds`` (from the return of
a micro-run's token fetch to the return of the next launch, the span
``serve.boundary``) over ``micro_runs``, as deltas over the window. A
program without the counter reads None."""


def read(ctx):
    a, b = ctx.counters_start["scheduler"], ctx.counters_end["scheduler"]
    if "boundary_seconds" not in b:
        return None
    runs = b["micro_runs"] - a["micro_runs"]
    secs = b["boundary_seconds"] - a["boundary_seconds"]
    return secs / runs * 1e3 if runs else None
