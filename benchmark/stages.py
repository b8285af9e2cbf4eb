"""Where does the flagship program leave its reference? Stage by stage.

    python3 -m benchmark.stages --workload flagship-score --seed 5 [--images 8]
                                [--precision highest|high|bfloat16]

Runs the cell's fitted pipeline node by node on a few of its images and
prints, for each stage the reference also has, how far the program's
output is from the reference's (relative Frobenius error, and the share
of entries that differ at all). With ``--precision`` it prints the same
for the reference computed at that precision, against the reference at
``highest``. A tool for setting and explaining limits; the benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmark import run as run_lib

STAGE_OF = {"GrayScaler": "gray", "SIFTExtractor": "sift",
            "LCSExtractor": "lcs", "VectorCombiner": "features",
            "BlockLinearMapper": "scores"}


def diff(got, want) -> dict:
    got = np.asarray(got, np.float64).reshape(np.shape(want))
    want = np.asarray(want, np.float64)
    return {"rel": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
            "differ": float(np.mean(got != want))}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--images", type=int, default=8)
    p.add_argument("--precision", default=None)
    p.add_argument("--allow-cpu", action="store_true")
    a = p.parse_args(argv)
    sys.path.insert(0, run_lib.ROOT)
    import jax.numpy as jnp

    from benchmark.programs import flagship
    from benchmark.reference import imagenet_sift_lcs_fv as ref
    from keystone_tpu.parallel.dataset import Dataset

    manifest = run_lib.load_json(run_lib.ROOT, "BENCHMARK.json")
    cell, config, workload = run_lib.find_cell(manifest, a.workload)
    workload["traffic"]["images_per_step"] = a.images
    ctx = run_lib.Context(cell, config, workload, a.seed, 1.0, False)
    run_lib.check_devices(ctx, not a.allow_cpu)
    run_lib.setup_compile_cache()
    inputs = flagship.make_inputs(ctx)
    fitted = inputs["scorer"].fit()
    values = {fitted.source: Dataset.from_items(inputs["items"])}
    got = {}
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        values[node] = op.batch_transform(
            [values[d] for d in fitted.graph.dependencies[node]])
        name = STAGE_OF.get(type(op).__name__)
        if name:
            got[name] = np.asarray(values[node].array())
    params = {k: jnp.asarray(v)
              for k, v in ref.draw_params(config, a.seed).items()}
    want: dict = {}
    ref.scores_of(jnp.asarray(inputs["images"]), config, params,
                  "highest", want)
    for name in ("gray", "sift", "lcs", "features", "scores"):
        g = got[name]
        if name == "gray":
            g = g[..., 0]
        print(json.dumps({"stage": name, "program": diff(g, want[name])}))
    print(json.dumps({"score_gap.program": ref.score_gap(
        got["scores"], np.asarray(want["scores"]))}))
    if a.precision:
        low: dict = {}
        ref.scores_of(jnp.asarray(inputs["images"]), config, params,
                      a.precision, low)
        for name in ("gray", "sift", "lcs", "features", "scores"):
            print(json.dumps({"stage": name, a.precision:
                              diff(low[name], want[name])}))
        print(json.dumps({"score_gap." + a.precision: ref.score_gap(
            np.asarray(low["scores"]), np.asarray(want["scores"]))}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
