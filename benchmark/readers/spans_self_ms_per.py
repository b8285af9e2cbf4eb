"""Self time of every ``ks:<prefix>...`` span of the program in the
traced window, summed (``span_self_ms_per`` reads one name), in ms per
``per`` ("step" or "work"). Nothing on a CPU run or where the program
opens no such span."""

from benchmark import spans


def read(ctx, prefix, per="step"):
    t = ctx.trace_summary
    n = ctx.window.get("steps" if per == "step" else "work")
    if t is None or not n:
        return None
    found = [ns for name, (ns, _) in spans.self_ns_by_name(t).items()
             if name.startswith(spans.PREFIX + prefix)]
    return 1e3 * sum(found) / 1e9 / n if found else None
