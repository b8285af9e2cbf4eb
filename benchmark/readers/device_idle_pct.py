"""1 - union of the device-op intervals over the traced window, on the
busiest chip, in percent."""


def read(ctx):
    t = ctx.trace_summary
    return None if t is None else 100.0 * t.idle_share()
