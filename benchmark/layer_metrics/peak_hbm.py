"""Peak bytes in use on the fullest chip, by its ``memory_stats()``."""


def read(spec, ctx):
    return float(ctx["device"]["memory_peak_bytes"]) or None
