"""End-to-end MnistRandomFFT on the virtual 8-device mesh (reference:
pipelines/images/mnist/MnistRandomFFT.scala)."""

import numpy as np
import pytest

from keystone_tpu.pipelines.images.mnist_random_fft import (
    MnistRandomFFTConfig,
    build_pipeline,
    run,
    synthetic_mnist,
)


def test_mnist_random_fft_end_to_end(mesh8):
    # n=256 < D=1024 is the interpolation regime: lam must be large enough
    # to regularize (the reference app runs n=60000 >> D)
    train, test = synthetic_mnist(n_train=256, n_test=64, seed=0)
    conf = MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=10.0)
    pipeline, metrics = run(train, test, conf)
    # well-separated synthetic blobs: near-perfect accuracy
    assert metrics.total_accuracy > 0.9


def test_mnist_fitted_pipeline_serves(mesh8):
    train, test = synthetic_mnist(n_train=256, n_test=8, seed=1)
    conf = MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=10.0)
    pipeline, _ = run(train, test, conf)
    fitted = pipeline.fit()
    batch = np.asarray(fitted.apply(test.data).array())
    one = fitted.jit()(test.data.array()[0])
    assert int(one) == int(batch[0])


def _scores_before_max(fitted, x):
    """The fitted predictor's class scores, read before MaxClassifier."""
    from keystone_tpu.ops.util.nodes import MaxClassifier
    from keystone_tpu.parallel.dataset import Dataset

    values = {fitted.source: Dataset.from_array(x)}
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        if isinstance(op, MaxClassifier):
            break
        out = values[node] = op.batch_transform(
            [values[dep] for dep in fitted.graph.dependencies[node]])
    return np.asarray(out.array(), np.float64)


def _sweep_f64(x, y, x_test, conf):
    """MnistRandomFFT's model by its definition in float64: the branches
    by numpy's FFT, one Gauss-Seidel sweep of centred block least squares,
    each (b, b) system solved exactly."""
    import scipy.linalg

    def features(z):
        return np.concatenate([
            np.maximum(np.fft.fft(
                np.asarray(z, np.float64) * (np.random.default_rng(
                    conf.seed + i).integers(0, 2, size=784) * 2.0 - 1.0),
                n=1024, axis=1).real[:, :512], 0.0)
            for i in range(conf.num_ffts)], axis=1)

    a, at = features(x), features(x_test)
    labels = 2.0 * np.eye(10)[np.asarray(y)] - 1.0
    mu, mu_y = a.mean(axis=0), labels.mean(axis=0)
    a, at, resid = a - mu, at - mu, labels - mu_y
    scores = np.zeros((x_test.shape[0], 10)) + mu_y
    for s in range(0, a.shape[1], conf.block_size):
        b = a[:, s:s + conf.block_size]
        w = scipy.linalg.solve(b.T @ b + conf.lam * np.eye(b.shape[1]),
                               b.T @ resid, assume_a="pos")
        resid -= b @ w
        scores += at[:, s:s + conf.block_size] @ w
    return scores


@pytest.mark.parametrize("lam", [0.0, 10.0])
def test_held_out_scores_match_a_float64_sweep(lam):
    """2 FFTs (1,024 features) in blocks of 512 over 1,100 rows, so that
    the blocks are regular at lam 0: the fitted pipeline's held-out
    scores against the float64 definition."""
    train, test = synthetic_mnist(n_train=1100, n_test=64, seed=3)
    conf = MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=lam, seed=3)
    fitted = build_pipeline(train, conf).fit()
    got = _scores_before_max(fitted, test.data.array())
    want = _sweep_f64(train.data.array(), train.labels.array(),
                      test.data.array(), conf)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-5
