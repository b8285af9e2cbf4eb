"""flops_krr.py against shapes worked by hand, and the roofline metric's
count against the peaks."""

from benchmark import flops, flops_krr

CFG = {"image": [32, 32, 3], "augment_patch_size": 24, "patch_size": 6,
       "patch_steps": 1, "num_filters": 512, "num_features": 4096,
       "block_size": 5000, "num_epochs": 1, "num_classes": 10}
N = 125000
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_sizes():
    assert flops_krr.sizes(CFG) == (361, 108, 512, 4096, 10)
    assert flops_krr.widths(CFG, N) == [5000] * 25
    assert flops_krr.widths(CFG, 12000) == [5000, 5000, 2000]


def test_convolution_by_hand():
    # 2 x 361 positions x 108 values x 512 filters a crop
    assert flops_krr.conv(CFG, 1) == 39_923_712
    assert 4.9e12 < flops_krr.conv(CFG, N) < 5.0e12


def test_kernel_by_hand():
    # one column block: (125,000, 4,096) by (4,096, 5,000)
    assert flops_krr.kernel_block(CFG, N, 5000) == 2.0 * N * 5000 * 4096
    assert flops_krr.kernel(CFG, N) == 2.0 * N * N * 4096
    assert 1.27e14 < flops_krr.kernel_step(CFG, N) < 1.29e14
    # the rows and the block's rows in, the block out
    assert flops_krr.kernel_block_bytes(CFG, N, 5000) == 4 * (
        N * 4096 + 5000 * 4096 + N * 5000)
    assert flops_krr.kernel_step_bytes(CFG, N) == 25 * 4 * (
        N * 4096 + 5000 * 4096 + N * 5000)
    # operations bind: 0.65 s at the peak against 0.14 s of bytes, so
    # three bf16 passes bound the share at a third
    least = flops.roofline_s(flops_krr.kernel_step(CFG, N),
                             flops_krr.kernel_step_bytes(CFG, N), PEAKS)
    assert least == flops_krr.kernel_step(CFG, N) / 197e12
    assert abs(least - 0.6497) < 1e-3
    assert flops_krr.kernel_step_bytes(CFG, N) / 819e9 < 0.15
    # a ragged last block is counted at its own width
    assert flops_krr.kernel(CFG, 12000) == 2.0 * 12000 * 12000 * 4096


def test_sweeps_and_fit_by_hand():
    residuals = 25 * 2.0 * N * 5000 * 10
    diagonal = 25 * 2.0 * 5000 * 5000 * 10
    solves = 25 * 2.0 * 5000 * 5000 * 10
    factor = 25 * 5000 ** 3 / 3.0
    assert abs(flops_krr.sweeps(CFG, N)
               - (residuals + diagonal + solves + factor)) < 16.0
    assert 1.0e12 < factor < 1.1e12
    assert abs(flops_krr.krr_fit(CFG, N) - flops_krr.conv(CFG, N)
               - flops_krr.kernel(CFG, N) - flops_krr.sweeps(CFG, N)) < 16.0
    # the kernel matrix is 95% of a fit
    assert 0.94 < flops_krr.kernel(CFG, N) / flops_krr.krr_fit(CFG, N) < 0.96
    # a second epoch sweeps again; the kernel and the factors are
    # counted once (a path that caches them makes them once)
    twice = dict(CFG, num_epochs=2)
    assert abs(flops_krr.krr_fit(twice, N) - flops_krr.krr_fit(CFG, N)
               - (residuals + diagonal + solves)) < 16.0
