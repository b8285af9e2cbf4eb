"""Whole fits, one after another, for ``--seconds``.

traffic keys: ``rate_metric`` (the end-to-end rate's name),
``rows_per_chip``, ``heldout_rows``. The rate is rows x whole fits over
the window's seconds. Three of the window's fitted models are kept for
the comparison: the first, the last and one drawn from the seed.
"""

from __future__ import annotations

import random

from benchmark.drivers import loop_steps, program_of


def setup(ctx) -> dict:
    program = program_of(ctx)
    inputs = program.make_inputs(ctx)
    with ctx.span("warmup"):
        fitted = program.fit(inputs)  # compiles every program of a fit
        program.outputs(fitted, inputs)  # and of the predictor
    del fitted
    return {"program": program, "inputs": inputs, "kept": {}}


def window(ctx, state: dict) -> dict:
    program, inputs, kept = state["program"], state["inputs"], state["kept"]
    rng = random.Random(ctx.seed)

    def step(i: int) -> None:
        fitted = program.fit(inputs)
        if i == 0:
            kept["first"] = fitted
        else:
            kept["last"] = fitted
            if rng.random() < 1.0 / i:  # reservoir of one over steps 1..
                kept["drawn"] = fitted

    steps, elapsed = loop_steps(ctx, step)
    rows = inputs["rows"]
    return {
        "attempted": steps, "failed": 0, "steps": steps,
        "elapsed_s": elapsed, "work": steps * rows,
        "metrics": {ctx.traffic["rate_metric"]: steps * rows / elapsed},
    }


def sample(ctx, state: dict) -> dict:
    program, inputs = state["program"], state["inputs"]
    out = program.reference_inputs(inputs)
    out["outputs"] = {
        k: program.outputs(f, inputs) for k, f in state["kept"].items()
    }
    state["kept"].clear()
    program.free(inputs)
    return out
