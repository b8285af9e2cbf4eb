"""Drivers: how a cell's window is driven. Named by a workload file's
``driver`` key. Each has setup(ctx) -> state, window(ctx, state) -> record,
sample(ctx, state) -> what the reference compares (and frees the
program's state)."""

from __future__ import annotations

import importlib
import time


def program_of(ctx):
    return importlib.import_module("benchmark.programs." + ctx.config["program"])


def loop_steps(ctx, step) -> tuple:
    """Call ``step(i)`` until ``ctx.seconds`` have passed; a step that
    began inside the window is finished and counted, with its time.
    Returns (steps, elapsed seconds)."""
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    steps = 0
    while time.perf_counter() < deadline:
        with ctx.span("step"):
            step(steps)
        steps += 1
    return steps, time.perf_counter() - t0
