"""Batch scoring, one Dataset after another, for ``--seconds``.

traffic keys: ``rate_metric``, ``images_per_step``, ``check_images``. A
step applies the predictor (top-k and all) to the step's images and reads
the result back. The rate is images whose top-k came back over the
window's seconds. Once the window has closed, the same pipeline without
its top-k node gives the scores of ``check_images`` images drawn from the
seed (ties would scramble a comparison of the top-k themselves), and the
window's top-k of those images has to be the top-k of those scores.
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.drivers import loop_steps, program_of


def setup(ctx) -> dict:
    program = program_of(ctx)
    inputs = program.make_inputs(ctx)
    with ctx.span("warmup"):
        program.score(inputs)
        program.score(inputs, "scorer")
    return {"program": program, "inputs": inputs, "kept": {}}


def window(ctx, state: dict) -> dict:
    program, inputs, kept = state["program"], state["inputs"], state["kept"]

    def step(i: int) -> None:
        kept["last"] = program.score(inputs)
        kept.setdefault("first", kept["last"])

    steps, elapsed = loop_steps(ctx, step)
    n = inputs["work"]
    failed = sum(1 for k in ("first", "last")
                 if kept[k].shape[0] != n)
    return {
        "attempted": steps, "failed": failed, "steps": steps,
        "elapsed_s": elapsed, "work": steps * n,
        "metrics": {ctx.traffic["rate_metric"]: steps * n / elapsed},
    }


def sample(ctx, state: dict) -> dict:
    program, inputs, kept = state["program"], state["inputs"], state["kept"]
    n = inputs["work"]
    rows = sorted(random.Random(ctx.seed).sample(
        range(n), min(int(ctx.traffic["check_images"]), n)))
    scores = program.score(inputs, "scorer")
    # the window's own top-k against the scores they were taken from
    k = kept["first"].shape[1]
    top = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    agree = all(np.array_equal(kept[key], top) for key in ("first", "last"))
    out = {"images": inputs["images"][rows],
           "outputs": [(np.arange(len(rows)), scores[rows])] if agree else []}
    state["kept"].clear()
    program.free(inputs)
    return out
