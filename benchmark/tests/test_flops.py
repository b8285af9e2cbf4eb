"""flops.py against shapes worked by hand."""

from benchmark import flops

CFG = {"dim": 440, "num_cosine_features": 4096, "numCosines": 4,
       "num_classes": 147, "numEpochs": 5}


def test_timit_gram():
    # 2 * 65536 * 4096 * 4096
    assert flops.timit_gram(65536, 4096) == 2 * 65536 * 4096 ** 2
    assert flops.timit_gram(65536, 4096) == 2_199_023_255_552
    assert flops.timit_gram_bytes(65536, 4096) == 4 * (65536 * 4096 + 4096 ** 2)


def test_timit_fit_by_hand():
    n = 65536
    features = 2 * n * 440 * 16384  # 9.449e11
    grams = 4 * 2 * n * 4096 ** 2  # 8.796e12
    factor = 4 * 4096 ** 3 / 3  # 9.16e10
    sweeps = 5 * 4 * 4 * n * 4096 * 147  # 3.157e12
    want = features + grams + factor + sweeps
    assert abs(flops.timit_fit(CFG, n) - want) < 1.0
    assert 1.29e13 < want < 1.31e13


def test_roofline_names_the_larger_bound():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    # the Gram of 65536 x 4096 is compute bound: 11.2 ms against 1.4 ms
    t = flops.roofline_s(flops.timit_gram(65536, 4096),
                         flops.timit_gram_bytes(65536, 4096), peaks)
    assert abs(t - 2_199_023_255_552 / 197e12) < 1e-12
    # bytes win where operations are few
    assert flops.roofline_s(1.0, 819e9, peaks) == 1.0
