"""flops_wls.py against shapes worked by hand, and the readers that take
their counts from it."""

import types

from benchmark import flops, flops_wls
from benchmark.readers import kernel_roofline_pct_of, mfu_pct_of

CFG = {"num_features": 4096, "block_size": 4096, "num_classes": 1000,
       "num_iter": 1}
N = 327680
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_sizes():
    assert flops_wls.sizes(CFG) == (4096, 1, 1000, 1)
    wide = dict(CFG, num_features=16384, num_iter=3)
    assert flops_wls.sizes(wide) == (4096, 4, 1000, 3)


def test_parts_by_hand():
    assert flops_wls.wls_gram(N, 4096) == 2 * N * 4096 ** 2
    assert flops_wls.wls_gram(N, 4096) == 10_995_116_277_760
    # X'R, the class sums and the own-residual sums
    assert flops_wls.wls_moments(N, 4096, 1000) == \
        2 * N * 4096 * 1000 + 3 * N * 4096
    assert flops_wls.wls_matvec(N, 4096, 1000) == 4 * N * 4096 * 1000
    assert flops_wls.wls_matvec(N, 4096, 1000) == 5_368_709_120_000
    direct = 2 * N * 4096 ** 2 + 1000 * 4096 ** 3 / 3
    assert abs(flops_wls.wls_direct(N, 4096, 1000) - direct) < 1.0
    assert 3.38e13 < direct < 3.40e13


def test_whole_fit_takes_the_smaller_solve():
    fixed = 2 * N * 4096 ** 2 + 2 * N * 4096 * 1000 + 3 * N * 4096
    direct = 2 * N * 4096 ** 2 + 1000 * 4096 ** 3 / 3
    per_iteration = 4 * N * 4096 * 1000 + 4 * 1000 * 4096 ** 2
    # one block, one pass: no residual update is counted
    assert abs(flops_wls.wls_fit(CFG, N) - (fixed + direct)) < 1.0
    # 8 iterations cost more than the direct solve, 5 cost less
    assert 8 * per_iteration > direct > 5 * per_iteration
    assert abs(flops_wls.wls_fit(CFG, N, 8) - (fixed + direct)) < 1.0
    assert abs(flops_wls.wls_fit(CFG, N, 5)
               - (fixed + 5 * per_iteration)) < 1.0
    # two blocks, two passes: four steps, three residual updates
    wide = dict(CFG, num_features=8192, num_iter=2)
    want = 4 * (fixed + direct) + 3 * 2 * N * 4096 * 1000
    assert abs(flops_wls.wls_fit(wide, N) - want) < 8.0


def test_matvec_roofline_is_compute_bound_at_these_shapes():
    ops = flops_wls.wls_matvec_step(CFG, N, 8)
    nbytes = flops_wls.wls_matvec_step_bytes(CFG, N, 8)
    assert ops == 8 * 4 * N * 4096 * 1000
    assert nbytes == 8 * 2 * 4 * N * 4096
    # 27.3 ms of operations against 13.1 ms of reads, an iteration
    assert abs(ops / 8 / 197e12 - 0.02725) < 1e-4
    assert abs(nbytes / 8 / 819e9 - 0.01311) < 1e-4
    assert flops.roofline_s(ops, nbytes, PEAKS) == ops / 197e12
    assert flops_wls.wls_matvec_step(CFG, N) == 0.0


def fake_ctx(registry_ratio):
    ctx = types.SimpleNamespace()
    ctx.config, ctx.peaks, ctx.devices = CFG, PEAKS, [object()]
    ctx.window = {"steps": 10, "work": 10 * N, "elapsed_s": 30.0}
    ctx.trace_summary = object()
    return ctx


def test_mfu_reader_passes_the_counted_iterations(monkeypatch):
    from benchmark.readers import counter_ratio

    monkeypatch.setattr(counter_ratio, "read", lambda ctx, n, d: 5.0)
    args = {"module": "flops_wls", "model": "wls_fit",
            "counters": {"iterations": ["a_total", "b_total"]}}
    got = mfu_pct_of.read(fake_ctx(5.0), **args)
    want = 100.0 * flops_wls.wls_fit(CFG, N, 5.0) * 10 / 30.0 / 197e12
    assert abs(got - want) < 1e-9 and 0.0 < got < 100.0
    # a program without the counters: the metric is left out, no error
    monkeypatch.setattr(counter_ratio, "read", lambda ctx, n, d: None)
    assert mfu_pct_of.read(fake_ctx(None), **args) is None
    assert kernel_roofline_pct_of.read(
        fake_ctx(None), "flops_wls", "x", "wls_matvec_step",
        "wls_matvec_step_bytes", counters=args["counters"]) is None
