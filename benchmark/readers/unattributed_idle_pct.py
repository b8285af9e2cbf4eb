"""The share of the busiest chip's idle-gap time (gaps of 20 us or more)
at whose middle no ``ks:`` span of the program is open, in percent.
Nothing on a CPU run or where the program opens no ``ks:`` span."""

from benchmark import spans


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    share = spans.unattributed_idle_share(t)
    return None if share is None else 100.0 * share
