"""Run one cell once and print the contract's result line.

    python3 -m benchmark.run --workload W --seed N --seconds S --trace 0|1

New process, fail without the chips the cell asks for, make data and
weights from ``--seed`` on the device, warm every shape the cell uses
(set-up), measure for ``S`` seconds, read the peak memory, free the
program's state, compare what the window produced with the plain
reference, print one JSON line. Everything that belongs to one cell,
configuration or metric is a file found by the name in BENCHMARK.json
(README.md).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse
import importlib
import json
import os
import sys
import tempfile
import threading
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


class BenchFailure(Exception):
    """The run cannot give a result; exit non-zero and print none."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileCounter:
    """Programs XLA compiled (compile requests minus persistent-cache
    hits), from jax.monitoring. Copied from chip_smoke.py."""

    def __init__(self) -> None:
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.requests += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    @property
    def compiled(self) -> int:
        with self._lock:
            return self.requests - self.hits


class Context:
    """What a driver, a reference and a reader get to see of one run."""

    def __init__(self, cell: dict, config: dict, workload: dict, seed: int,
                 seconds: float, trace: bool):
        self.cell = cell  # the BENCHMARK.json entry
        self.config = config  # benchmark/configs/<config>.json
        self.workload = workload  # benchmark/workloads/<cell>.json
        self.traffic = workload["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.chips = int(cell["chips"])
        self.compiles = CompileCounter()
        self.peaks: Optional[dict] = None
        self.devices: List[Any] = []
        # filled as the run goes: the window's record, the trace
        self.window: Dict[str, Any] = {}
        self.trace_summary = None
        self.compiles_in_window: Optional[int] = None

    def span(self, name: str):
        """A host span on the profiler's clock (no-op cost when no
        trace is being taken): the idle gaps are attributed to these."""
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)


def phase(name: str) -> None:
    """One stderr line per phase reached, with the seconds since the
    process started: where a run's time went, outside the result."""
    print(f"[benchmark.run] {time.perf_counter() - _T0:8.2f}s {name}",
          file=sys.stderr, flush=True)


def find_cell(manifest: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(
            f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})"
        )
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT, configs[cell["config"]]["file"])
    workload = load_json(HERE, "workloads", name + ".json")
    return cell, config, workload


def modules_of(config: dict, workload: dict) -> tuple:
    """(driver, reference) named by a cell's files."""
    return (
        importlib.import_module("benchmark.drivers." + workload["driver"]),
        importlib.import_module("benchmark.reference." + config["reference"]),
    )


def drive_to_sample(ctx: "Context", require_chip: bool = True) -> tuple:
    """Set a cell up, drive its window and take the sample, with no trace
    and no result: (reference, sample). For limits.py and the tests."""
    check_devices(ctx, require_chip)
    setup_compile_cache()
    driver, reference = modules_of(ctx.config, ctx.workload)
    state = driver.setup(ctx)
    ctx.window = driver.window(ctx, state)
    return reference, driver.sample(ctx, state)


def check_devices(ctx: Context, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    if len(devs) < ctx.chips:
        raise BenchFailure(
            f"the cell asks for {ctx.chips} chips, jax found {len(devs)}"
        )
    if require_chip:
        if d0.platform != "tpu":
            raise BenchFailure(
                f"jax found platform {d0.platform!r}, not a TPU; the "
                "benchmark does not fall back to the CPU"
            )
        peaks = load_json(HERE, "peaks.json")
        if d0.device_kind not in peaks:
            raise BenchFailure(
                f"device kind {d0.device_kind!r} is not in "
                "benchmark/peaks.json; a device without peaks is an error"
            )
        ctx.peaks = peaks[d0.device_kind]
    ctx.devices = devs[: ctx.chips]
    info["count"] = len(ctx.devices)
    return info


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}  # None on a backend without stats
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def setup_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache: the
    program's own rule (parallel/runtime.py), so both agree."""
    from keystone_tpu.parallel import runtime

    return runtime.setup_compilation_cache()


def per_layer_metrics(ctx: Context, manifest: dict) -> dict:
    """Each per-layer metric that lists this cell, through its reader. A
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    ends = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            moved = ends[m["moves"]].get("workloads")
            if moved is not None and ctx.cell["name"] not in moved:
                continue
        elif ctx.cell["name"] not in cells:
            continue
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest: dict, cell: dict, config: dict, workload: dict, *,
             seed: int, seconds: float, trace: bool,
             require_chip: bool = True,
             keep_trace: Optional[str] = None) -> dict:
    """One whole run; returns the result object (not printed here)."""
    ctx = Context(cell, config, workload, seed, seconds, trace)
    device = check_devices(ctx, require_chip)
    setup_compile_cache()
    ctx.compiles.install()
    driver, reference = modules_of(config, workload)

    phase("devices found")
    state = driver.setup(ctx)  # data, weights, warm-up: all set-up
    setup_s = time.perf_counter() - _T0
    phase("set-up done")

    import jax

    c0 = ctx.compiles.requests
    trace_dir = None
    if trace:
        ctx.seconds = min(ctx.seconds, float(ctx.traffic.get(
            "trace_seconds", ctx.seconds)))
        trace_dir = keep_trace or tempfile.mkdtemp(
            prefix="trace-", dir=os.environ.get("TMPDIR") or None)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no per-Python-call events
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    try:
        ctx.window = driver.window(ctx, state)
    finally:
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    # programs built inside the window, cache hits included: there
    # should be none after the warm-up
    ctx.compiles_in_window = ctx.compiles.requests - c0
    phase("window closed")
    device["memory_peak_bytes"] = memory_peak_bytes(ctx.devices)

    # host copies of what is compared; the program's state is freed
    sample = driver.sample(ctx, state)
    del state
    phase("sampled and freed")
    numbers = reference.compare(ctx, sample)
    phase("compared")
    limits = workload["limits"]
    compared = {
        k: {"value": float(v), "limit": float(limits[k])}
        for k, v in numbers.items()
    }
    correct = bool(compared) and all(
        c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in compared.values()
    ) and ctx.window["failed"] == 0 and ctx.window["attempted"] > 0

    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(ctx.window["attempted"]),
        "failed": int(ctx.window["failed"]),
    }
    if trace:
        from benchmark import trace as trace_lib

        ctx.trace_summary = trace_lib.summarize(trace_dir, len(ctx.devices))
        result["metrics"] = per_layer_metrics(ctx, manifest)
        if ctx.trace_summary is not None:
            device["busy_s"] = ctx.trace_summary.busy_s
            device["window_s"] = ctx.trace_summary.window_s
            result["breakdown"] = ctx.trace_summary.breakdown()
        if keep_trace is None:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
        phase("trace reduced")
    else:
        metrics = dict(ctx.window["metrics"])
        metrics["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        result["metrics"] = {
            k: {"value": float(v), "unit": units[k]}
            for k, v in metrics.items()
        }
    result["device"] = device
    result["window"] = {"seconds": window_s, "steps": ctx.window.get("steps")}
    result["compared"] = compared
    return result


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None,
                   help="directory to leave the profiler's trace in")
    a = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "keystone_tpu")):
        print(f"benchmark.run drives the keystone_tpu checkout it ships "
              f"in; there is no keystone_tpu/ in {ROOT}", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    try:
        manifest = load_json(ROOT, "BENCHMARK.json")
        cell, config, workload = find_cell(manifest, a.workload)
        result = run_cell(
            manifest, cell, config, workload, seed=a.seed,
            seconds=a.seconds, trace=bool(a.trace), keep_trace=a.keep_trace,
        )
    except BenchFailure as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for k, c in result["compared"].items():
        print(f"compared {k}: value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
