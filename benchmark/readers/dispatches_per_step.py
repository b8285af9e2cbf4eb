"""XLA module executions on a chip over the window's steps."""


def read(ctx, pattern=".*"):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    if t is None or not steps:
        return None
    _, count = t.op_time(pattern, line="modules")
    return count / steps if count else None
