"""flops_mnist.py against shapes worked by hand."""

import json
import os

from benchmark import flops, flops_mnist, run

CFG = json.load(open(os.path.join(
    run.HERE, "configs", "mnist-random-fft.json")))
N = 15000
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_sizes():
    d, pad, f, dd, widths, k = flops_mnist.sizes(CFG)
    assert (d, pad, f, dd, k) == (784, 1024, 200, 102400, 10)
    assert widths == [2048] * 50 and dd == f * pad // 2


def test_fft_bank_by_hand():
    # a real FFT of 1,024 points: 2.5 x 1,024 x 10 a branch and row
    assert flops_mnist.fft_bank(CFG, 1) == 200 * 25_600
    assert flops_mnist.fft_bank_bytes(CFG, N) == 4 * N * (784 + 102400)
    # bytes bind: 7.56 ms at the bandwidth against 0.39 ms of operations
    least = flops.roofline_s(flops_mnist.fft_bank(CFG, N),
                             flops_mnist.fft_bank_bytes(CFG, N), PEAKS)
    assert least == flops_mnist.fft_bank_bytes(CFG, N) / 819e9
    assert abs(least - 7.557e-3) < 1e-5


def test_solver_by_hand():
    # the Gram at its least (upper triangle with the diagonal), the
    # right-hand side and a Cholesky a block; every residual but the last
    grams = 50 * N * 2048 * 2049
    sides = 2 * N * 102400 * 10
    updates = 2 * N * (102400 - 2048) * 10
    factor = 50 * 2048 ** 3 / 3
    want = grams + sides + updates + factor
    assert abs(flops_mnist.solver(CFG, N) - want) < 16.0
    assert abs(flops_mnist.mnist_fit(CFG, N)
               - flops_mnist.fft_bank(CFG, N) - want) < 16.0
    assert 3.4e12 < flops_mnist.mnist_fit(CFG, N) < 3.5e12
