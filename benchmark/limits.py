"""Readings for a cell's limits: the program and the control, seed by seed.

    python3 -m benchmark.limits --workload W --seeds 1,2,3 [--control-seeds 1,2]
                                [--seconds S]

One process, so that set-up is paid once per seed and the compiled
programs are shared. For each seed: set up the cell, drive a short window
(``--seconds``, long enough for the mix's longest request), compare with
the reference (the lower reading); for each control seed also put the
reference's lower-precision form in the program's place (the upper
reading). Prints one JSON line per seed. The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run as run_lib


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--allow-cpu", action="store_true")
    a = p.parse_args(argv)
    sys.path.insert(0, run_lib.ROOT)
    manifest = run_lib.load_json(run_lib.ROOT, "BENCHMARK.json")
    cell, config, workload = run_lib.find_cell(manifest, a.workload)
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in [int(s) for s in a.seeds.split(",") if s]:
        ctx = run_lib.Context(cell, config, workload, seed, a.seconds, False)
        reference, sample = run_lib.drive_to_sample(ctx, not a.allow_cpu)
        out = {"seed": seed, "steps": ctx.window.get("steps"),
               "program": reference.compare(ctx, sample)}
        if seed in controls:
            out["control"] = reference.control(ctx, sample)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
