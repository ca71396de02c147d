"""The engine step's share of its memory roofline, in percent: the least
time the chip could take to move the bytes one step needs, over the
device time one run of the step program took."""

import trace_reduce


def read(spec, ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    prog = trace_reduce.program_time(ctx["trace"], spec["pattern"])
    if prog is None or prog["seconds"] <= 0:
        return None
    least_s = ctx["step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (prog["seconds"] / prog["events"])
