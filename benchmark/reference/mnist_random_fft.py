"""Plain reference of the MnistRandomFFT configuration
(MnistRandomFFT.scala:21,40-49 with RandomSignNode.scala:10,
PaddedFFT.scala:13, LinearRectifier.scala:12, VectorCombiner and
BlockLinearMapper.scala:199-283).

Features: branch i's signs drawn again from the seed by the
configuration's rule (``numpy.random.default_rng(seed + i)``, integers 0
or 1 over the 784 pixels, as -1 or +1); the image times its signs,
zero-padded to the next power of two, an FFT in float64 (``scipy.fft``), the
real parts of the first half of the coefficients, rectified at 0. One
block of ``block_size`` columns (four branches) at a time, so that the
whole matrix is never formed; a few blocks ahead on worker threads, since
a block's features, Gram and factor do not depend on the sweep.

Model: one Gauss-Seidel sweep of mean-centred block least squares over
the blocks in order; each block's centred Gram and right-hand side in
float64 on the host, solved by Cholesky in float64 (where a block is
singular even in float64 — at lambda 0, a feature column that is never
positive — by ``eigh`` with eigenvalues raised to 1e-12 of the largest:
in float64 the pseudo-inverse, which the program's ridged fall-back
approaches); labels the ±1 indicators of the classes, centred; scores
= (phi - mu) W + mean(Y).

Backward error: the program's own block model W_b put into each block's
float64 normal equations, with the right-hand side of the residual its
own earlier blocks leave, |A_b W_b - r_b| / (|A_b| |W_b| + |r_b|)
(Frobenius), worst block. It reads how exactly each block's system was
solved, whatever the system's conditioning; the Gram's precision shows
there where the scores, dominated at lambda 0 by the float32
factorisation, do not.

Nothing is imported from keystone_tpu and nothing the program made is
read but what is compared: the program's held-out features and scores
and its block model.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import rel_err

EIGEN_CLAMP = 1e-12
AHEAD = 3  # blocks prepared on worker threads ahead of the sweep
FFT_WORKERS = 4  # threads of each FFT: AHEAD of them run at once


def pad_len(d: int) -> int:
    return int(2 ** np.ceil(np.log2(max(d, 1))))


def draw_signs(cfg: dict, seed: int, d: int) -> np.ndarray:
    """(num_ffts, d) float64 ±1, branch i from ``default_rng(seed + i)``."""
    return np.stack([
        np.random.default_rng(seed + i).integers(0, 2, size=d) * 2.0 - 1.0
        for i in range(int(cfg["num_ffts"]))
    ])


def branch_features(images: np.ndarray, signs: np.ndarray) -> np.ndarray:
    """(rows, branches x pad / 2) float64 for the branches of ``signs``
    (the input is real, so ``rfft`` gives the same first half of the
    coefficients as ``fft``, for half the work)."""
    import scipy.fft

    x = np.asarray(images, np.float64)
    n, d = x.shape
    pad = pad_len(d)
    signed = np.zeros((n, signs.shape[0], pad))
    np.multiply(x[:, None, :], signs[None], out=signed[..., :d])
    spec = scipy.fft.rfft(signed, axis=-1, overwrite_x=True,
                          workers=FFT_WORKERS)
    del signed
    out = np.empty((n, signs.shape[0], pad // 2))
    np.maximum(spec.real[..., : pad // 2], 0.0, out=out)
    return out.reshape(n, -1)


def factor(gram: np.ndarray):
    """rhs -> gram⁻¹ rhs, by Cholesky, or by the clamped ``eigh``."""
    import scipy.linalg

    try:
        c = scipy.linalg.cho_factor(gram, lower=True)
        return lambda rhs: scipy.linalg.cho_solve(c, rhs)
    except np.linalg.LinAlgError:
        w, v = np.linalg.eigh(gram)
        w = np.maximum(w, EIGEN_CLAMP * max(w[-1], 1.0))
        return lambda rhs: v @ ((v.T @ rhs) / w[:, None])


def gram_at(a: np.ndarray, precision: str) -> np.ndarray:
    """aᵀa of the float32 block at a lower precision (the controls)."""
    from benchmark.reference.precision import einsum_at

    a32 = np.asarray(a, np.float32)
    return np.asarray(einsum_at("nb,nc->bc", a32, a32, precision), np.float64)


def _ahead(fn, items, depth: int = AHEAD):
    """fn(item) for each item in order, ``depth`` of them under way."""
    items = list(items)
    with ThreadPoolExecutor(max_workers=depth) as pool:
        running = [pool.submit(fn, i) for i in items[:depth]]
        for j in range(len(items)):
            out = running[j].result()
            running[j] = None
            if j + depth < len(items):
                running.append(pool.submit(fn, items[j + depth]))
            yield out


def sweep(cfg: dict, seed: int, images: np.ndarray, y: np.ndarray,
          test_images: np.ndarray, gram_precision: str = "float64",
          models=None, on_block=None):
    """(scores of ``test_images`` under the model of one sweep on
    (images, y), backward errors). The sweep's blocks are solved with
    their Gram's product over the rows at ``gram_precision``; the
    backward errors, against the float64 normal equations, are the
    sweep's own (key ``None``) and those of each block model in
    ``models`` (key -> (features, classes) array). ``on_block(columns,
    held-out features)`` sees each block's reference features of the
    held-out rows."""
    d = images.shape[1]
    half = pad_len(d) // 2
    signs = draw_signs(cfg, seed, d)
    per = int(cfg["block_size"]) // half  # branches a block
    lam, k = float(cfg["lambda"]), int(cfg["num_classes"])
    n = images.shape[0]
    onehot = 2.0 * np.eye(k)[np.asarray(y)] - 1.0
    mu_y = onehot.mean(axis=0)
    models = {key: np.asarray(w, np.float64)
              for key, w in (models or {}).items()}
    resid = {key: onehot - mu_y for key in [None, *models]}
    worst = dict.fromkeys(resid, 0.0)
    scores = np.zeros((test_images.shape[0], k)) + mu_y

    def prepare(b0):
        part = signs[b0:b0 + per]
        a = branch_features(images, part)
        mu = a.mean(axis=0)
        eye = lam * np.eye(a.shape[1])
        low = None
        if gram_precision != "float64":  # the product, centred after
            low = gram_at(a, gram_precision) - n * np.outer(mu, mu) + eye
        a -= mu
        gram = a.T @ a + eye
        cols = slice(b0 * half, (b0 + len(part)) * half)
        return (cols, a, mu, gram, factor(gram if low is None else low),
                branch_features(test_images, part))

    for cols, a, mu, gram, solve, at in _ahead(
            prepare, range(0, signs.shape[0], per)):
        if on_block is not None:
            on_block(cols, at)
        norm_a = np.linalg.norm(gram)
        for key, r in resid.items():
            rhs = a.T @ r
            w = solve(rhs) if key is None else models[key][cols]
            if w.shape != rhs.shape or not np.all(np.isfinite(w)):
                worst[key] = float("inf")
                continue
            den = norm_a * np.linalg.norm(w) + np.linalg.norm(rhs)
            if den > 0:
                worst[key] = max(worst[key], float(
                    np.linalg.norm(gram @ w - rhs) / den))
            r -= a @ w
            if key is None:
                scores += (at - mu) @ w
    return scores, worst


def _worst(sample: dict, key: str, want: np.ndarray) -> float:
    outs = sample["outputs"]
    if not outs:
        return float("inf")
    return max(rel_err(o[key], want) for o in outs.values())


def compare(ctx, sample: dict) -> dict:
    """``features_rel_err``: the worst kept model's held-out features
    against the reference's float64 features (relative Frobenius, summed
    over the blocks); ``scores_rel_err``: its held-out scores against the
    reference sweep's on the reference's features;
    ``normal_eq_backward_err``: its block model's worst block's backward
    error in the float64 normal equations."""
    outs = sample["outputs"]
    sums = {key: [0.0, 0.0] for key in outs}

    def on_block(cols, want):
        for key, o in outs.items():
            got = np.asarray(o["features"][:, cols], np.float64)
            if got.shape != want.shape or not np.all(np.isfinite(got)):
                sums[key][0] = float("inf")
                continue
            sums[key][0] += float(np.sum((got - want) ** 2))
            sums[key][1] += float(np.sum(want ** 2))

    want, backward = sweep(
        ctx.config, ctx.seed, sample["images"], sample["y"],
        sample["test_images"], on_block=on_block,
        models={key: o["W"] for key, o in outs.items()})
    sample["reference_scores"] = want  # control() reads it again
    feats = [np.sqrt(s / w) if w else float("inf")
             for s, w in sums.values()]
    del backward[None]
    return {"features_rel_err": max(feats) if feats else float("inf"),
            "scores_rel_err": _worst(sample, "scores", want),
            "normal_eq_backward_err": max(backward.values(),
                                          default=float("inf"))}


def dft_features_at(images: np.ndarray, signs: np.ndarray,
                    precision: str) -> np.ndarray:
    """The branches as one product of the image and the signed cosine
    basis at ``precision`` (float32 accumulation), rectified."""
    from benchmark.reference.precision import matmul_at

    d = images.shape[1]
    pad = pad_len(d)
    jk = (np.arange(d)[:, None] * np.arange(pad // 2)[None, :]) % pad
    cos = np.cos(2.0 * np.pi * jk / pad)
    basis = (signs.T[:, :, None] * cos[:, None, :]).reshape(d, -1)
    out = matmul_at(np.asarray(images, np.float32),
                    basis.astype(np.float32), precision)
    return np.maximum(np.asarray(out, np.float64), 0.0)


def control(ctx, sample: dict) -> dict:
    """The reference one precision step down, put in the program's
    place and read by the measure it targets: the featurizer's product
    at ``high`` (three bf16 passes: the configuration's nearest
    precision below ``highest``) and at one bf16 pass
    (``features_rel_err``); the Gram's product over the rows at one bf16
    pass and at ``high`` (``normal_eq_backward_err``, and the scores
    beside it). Each but the Gram at ``high`` has to come out as not
    correct; that one reads under the program's own float32 solve
    (PERF.md section 2)."""
    images, test = sample["images"], sample["test_images"]
    args = (ctx.config, ctx.seed, images, sample["y"], test)
    signs = draw_signs(ctx.config, ctx.seed, images.shape[1])
    block = int(ctx.config["block_size"]) // (pad_len(images.shape[1]) // 2)
    diff = {"high": 0.0, "bfloat16": 0.0}
    norm = 0.0
    for b0 in range(0, signs.shape[0], block):
        want = branch_features(test, signs[b0:b0 + block])
        for precision in diff:
            got = dft_features_at(test, signs[b0:b0 + block], precision)
            diff[precision] += float(np.sum((got - want) ** 2))
        norm += float(np.sum(want ** 2))
    want = sample.get("reference_scores")
    if want is None:
        want = sweep(*args)[0]
    out = {precision + "_features": {"features_rel_err": float(
        np.sqrt(d / norm))} for precision, d in diff.items()}
    for precision in ("bfloat16", "high"):
        scores, backward = sweep(*args, gram_precision=precision)
        out[precision + "_gram"] = {
            "scores_rel_err": rel_err(scores, want),
            "normal_eq_backward_err": backward[None]}
    return out
