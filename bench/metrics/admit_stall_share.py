"""Share of the window's admission chances that admitted nothing: of the
micro-run boundaries where a lane was free and the queue was not empty
(``ContinuousScheduler`` counter ``admit_chances``), those at which no
request got a lane (``admit_stalls``), as deltas over the window. A
program without these counters reads None."""


def read(ctx):
    a, b = ctx.counters_start["scheduler"], ctx.counters_end["scheduler"]
    if "admit_chances" not in b:
        return None
    chances = b["admit_chances"] - a["admit_chances"]
    stalls = b["admit_stalls"] - a["admit_stalls"]
    return 100.0 * stalls / chances if chances else None
