"""Device time of the events whose name matches ``pattern`` on a line
("ops" or "modules"), in ms per ``per`` ("step" or "work": the window's
steps or its units of work)."""


def read(ctx, pattern, line="ops", per="step"):
    t = ctx.trace_summary
    n = ctx.window.get("steps" if per == "step" else "work")
    if t is None or not n:
        return None
    seconds, count = t.op_time(pattern, line=line)
    return 1e3 * seconds / n if count else None
