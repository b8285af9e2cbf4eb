"""The program's ``ks:`` spans as a profiler session records them:
shared by the suites that hold spans against ``jax.profiler``'s trace."""


def profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; the ``ks:`` events of the
    calling thread as (start ns, end ns, name), parents before children."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("test:calling-thread"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for e in line.events
            ]
            if any(n == "test:calling-thread" for _, _, n in events):
                return sorted(
                    (e for e in events if e[2].startswith("ks:")),
                    key=lambda e: (e[0], -e[1]),
                )
    raise AssertionError("the calling thread's line is not in the trace")


def parent_names(events):
    """name of each event's innermost enclosing event (None at the top),
    in the events' order."""
    out, stack = [], []
    for s, e, name in events:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
        stack.append((s, e, name))
    return out
