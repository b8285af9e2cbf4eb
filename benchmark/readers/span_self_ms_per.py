"""Self time of the program's ``ks:<span>`` spans in the traced window
(a span's duration less what the ``ks:`` spans nested in it cover), in ms
per ``per`` ("step" or "work": the window's steps or its units of work).
Nothing on a CPU run or where the program opens no such span."""

from benchmark import spans


def read(ctx, span, per="step"):
    t = ctx.trace_summary
    n = ctx.window.get("steps" if per == "step" else "work")
    if t is None or not n:
        return None
    seconds = spans.span_self_s(t, span)
    return None if seconds is None else 1e3 * seconds / n
