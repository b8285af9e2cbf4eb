"""flops_voc.py against counts made by hand at the published widths."""

import pytest

from benchmark import flops, flops_voc, run

CFG = run.load_json(run.HERE, "configs", "voc-sift-fisher.json")


def test_shares_are_met_exactly_and_every_seed_meets_five_shapes():
    counts = flops_voc.images(CFG, 313)
    assert sum(c for _, c in counts) == 313
    assert counts == [((375, 500), 172), ((500, 375), 47), ((333, 500), 62),
                      ((500, 333), 16), ((400, 500), 16)]
    held = flops_voc.images(CFG, 48)
    assert sum(c for _, c in held) == 48 and all(c > 0 for _, c in held)


def test_descriptors_of_a_voc_image():
    # 500 wide, 375 high: per scale bin 4, 6, 8, 10 at step 3, bounds
    # 9, 6, 3, 0
    per_scale = [
        ((375 - 1 - b - 3 * s) // 3 + 1) * ((500 - 1 - b - 3 * s) // 3 + 1)
        for s, b in ((4, 9), (6, 6), (8, 3), (10, 0))]
    assert flops_voc.descriptors(CFG, 375, 500) == sum(per_scale)
    assert 70000 < sum(per_scale) < 76000
    # flops.py's frames agree where the image is square
    square = dict(CFG, image_size=256)
    assert [(b, n) for b, n, _ in flops_voc.frames(CFG, 256, 256)] \
        == flops.sift_frames(square)
    assert flops_voc.sift_image(CFG, 256, 256) == pytest.approx(
        flops.sift_bin_sample(square, 1) + sum(
            2 * 2.0 * (2 * -(-4 * b // 6) + 1) * 256 * 256 + 30.0 * 256 * 256
            for b, _ in flops.sift_frames(square)))


def test_fisher_statistics_are_8_d_k_a_descriptor():
    m = sum(c * flops_voc.descriptors(CFG, h, w)
            for (h, w), c in flops_voc.images(CFG, 313))
    assert flops_voc.fv_stats(CFG, 313) == 8.0 * m * 80 * 256
    # compute-bound on a v5e: bytes over peak bytes/s is the smaller
    peaks = run.load_json(run.HERE, "peaks.json")["TPU v5 lite"]
    assert flops_voc.fv_stats(CFG, 313) / peaks["flops_per_s"] \
        > flops_voc.fv_stats_bytes(CFG, 313) / peaks["bytes_per_s"]


def test_a_fit_grows_by_one_em_round_a_round():
    one = flops_voc.voc_fit(CFG, 313, 1.0)
    more = flops_voc.voc_fit(CFG, 313, 51.0)
    n_gmm = (1_000_000 // 313) * 313
    assert more - one == pytest.approx(50 * 8.0 * n_gmm * 80 * 256)
    # the ten Grams of n b (b + 1), not 2 n b b
    assert flops_voc.voc_fit(CFG, 313) > 10 * 313 * 4096 * 4097.0
    # by operations the EM and the Fisher vectors are the fit, and SIFT,
    # which takes most of its time, an eightieth of it; it is counted
    # once (the program's second pass is recomputation)
    sift = sum(c * flops_voc.sift_image(CFG, h, w)
               for (h, w), c in flops_voc.images(CFG, 313))
    assert sift / more < 0.025
    rest = 2.0 * 999722 * 128 * 128 + sum(
        c * 2.0 * 80 * 128 * flops_voc.descriptors(CFG, h, w)
        for (h, w), c in flops_voc.images(CFG, 313))
    assert flops_voc.voc_fit(CFG, 313, 0.0) - flops_voc.voc_fit(
        dict(CFG, num_features=0), 313, 0.0) > 0  # the sweep is counted
    assert flops_voc.voc_fit(dict(CFG, num_features=0), 313, 0.0) \
        == pytest.approx(sift + rest + flops_voc.fv_stats(CFG, 313)
                         + 255 * 2.0 * 999722 * 80
                         + 2 * 2.0 * 999722 * 80 * 256)
    assert 0.5 < (more - one) / more < 0.8
    assert 0.2 < flops_voc.fv_stats(CFG, 313) / more < 0.4
