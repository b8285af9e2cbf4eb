"""flops_cifar.py against shapes worked by hand, and the new reader."""

import types

from benchmark import flops, flops_cifar
from benchmark.readers import counter_ratio_per_step

CFG = {"image": [32, 32, 3], "patch_size": 6, "patch_steps": 1,
       "num_filters": 10000, "num_features": 80000, "block_size": 4096,
       "num_iter": 1, "num_classes": 10}
N = 12544
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_sizes():
    positions, p, f, d, widths, k = flops_cifar.sizes(CFG)
    assert (positions, p, f, d, k) == (729, 108, 10000, 80000, 10)
    assert widths == [4096] * 19 + [2176] and sum(widths) == d


def test_convolution_by_hand():
    # 2 x 729 positions x 108 values x 10,000 filters an image
    assert flops_cifar.conv(CFG, 1) == 1_574_640_000
    assert flops_cifar.conv_step(CFG, N) == N * 1_574_640_000
    assert 1.97e13 < flops_cifar.conv_step(CFG, N) < 1.98e13
    # images in, filters in, pooled features out: no map is counted
    assert flops_cifar.conv_step_bytes(CFG, N) == 4 * (
        N * 3072 + 10000 * 108 + N * 80000)
    # operations bind: 100 ms at the peak against 5 ms of bytes
    ops = flops_cifar.conv_step(CFG, N)
    assert flops.roofline_s(
        ops, flops_cifar.conv_step_bytes(CFG, N), PEAKS) == ops / 197e12
    assert abs(ops / 197e12 - 0.1003) < 1e-3


def test_solver_by_hand():
    grams = 2 * N * (19 * 4096 ** 2 + 2176 ** 2)
    sides = 2 * N * 80000 * 10  # right-hand sides
    updates = 2 * N * (80000 - 2176) * 10  # all blocks but the last
    factor = (19 * 4096 ** 3 + 2176 ** 3) / 3
    want = grams + sides + updates + factor
    assert abs(flops_cifar.solver(CFG, N) - want) < 16.0
    assert 8.1e12 < grams < 8.2e12 and 4.3e11 < factor < 4.4e11
    assert abs(flops_cifar.cifar_fit(CFG, N)
               - flops_cifar.conv(CFG, N) - want) < 16.0
    # two sweeps: every block's residual update but the very last
    twice = dict(CFG, num_iter=2)
    assert abs(flops_cifar.solver(twice, N)
               - (2 * (grams + sides + factor) + 2 * 2 * N * 80000 * 10
                  - 2 * N * 2176 * 10)) < 32.0


def test_counter_ratio_per_step(monkeypatch):
    from benchmark.readers import counter_ratio

    ctx = types.SimpleNamespace(window={"steps": 4, "work": 4 * N})
    monkeypatch.setattr(counter_ratio, "read", lambda *a: 1.0 / 64)
    assert counter_ratio_per_step.read(ctx, "a", "b") == 196.0
    monkeypatch.setattr(counter_ratio, "read", lambda *a: None)
    assert counter_ratio_per_step.read(ctx, "a", "b") is None
