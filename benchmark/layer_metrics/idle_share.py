"""The device's idle share of the traced span, in percent."""


def read(spec, ctx):
    trace = ctx["trace"]
    if trace is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
