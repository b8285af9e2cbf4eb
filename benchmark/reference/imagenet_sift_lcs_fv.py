"""Plain reference of the flagship featurize-and-score path
(ImageNetSiftLcsFV.scala:29-151, vl_dsift's dense SIFT as VLFeat.cxx
drives it, LCSExtractor.scala, PCA.scala, FisherVector.scala).

uint8 image -> [x/255 -> gray -> dense SIFT at 4 scales -> signed sqrt]
and [LCS] -> each: PCA projection -> Fisher vector of a diagonal GMM ->
column-major flatten -> L2 -> signed sqrt -> L2 -> concatenated -> linear
model -> class scores.

Straightforward jax.numpy in float32: the smoothing, the triangular
spatial binning and the box filters are shifted sums (exact float32, no
sampling-matrix GEMMs, no kernels); the projections, the posteriors, the
statistics and the model are matrix products at the precision asked for
(``highest`` for the reference itself). Nothing is imported from
keystone_tpu and nothing it made is read: PCA, GMM and model are drawn
again from the configuration's seeded rule.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err
from benchmark.reference.precision import einsum_at

GRAY = (0.2989, 0.5870, 0.1140)


def draw_params(cfg: dict, seed: int) -> dict:
    """The seeded stand-ins for the fitted parameters, in the order the
    configuration's rule draws them: per branch (SIFT then LCS) a PCA
    (desc_dim, in_dim) ~ 0.1 N(0,1) and GMM means (desc_dim, vocab) ~
    N(0,1) with unit variances and equal weights; then the model
    (features, classes) ~ model_scale N(0,1) and its intercept."""
    rng = np.random.default_rng(seed)
    dd, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    out = {}
    for name, in_dim in (("sift", 128), ("lcs", 96)):
        out[name + "_pca"] = (
            rng.standard_normal((dd, in_dim)).astype(np.float32) * 0.1
        ).astype(np.float32)
        out[name + "_means"] = rng.standard_normal((dd, k)).astype(np.float32)
    feats = 2 * 2 * dd * k
    out["model"] = (rng.standard_normal(
        (feats, int(cfg["num_classes"]))) * float(cfg["model_scale"])
    ).astype(np.float32)
    out["intercept"] = rng.standard_normal(
        int(cfg["num_classes"])).astype(np.float32)
    return out


def _shift_sum(x, kernel, axis: int, pad_lo: int, pad_hi: int, mode: str):
    """sum_t kernel[t] * x[i + t - pad_lo] along ``axis``, same length."""
    import jax.numpy as jnp

    pads = [(0, 0)] * x.ndim
    pads[axis] = (pad_lo, pad_hi)
    xp = jnp.pad(x, pads, mode=mode)
    n = x.shape[axis]
    out = 0.0
    for t, w in enumerate(kernel):
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(t, t + n)
        out = out + float(w) * xp[tuple(sl)]
    return out


def _gaussian(sigma: float) -> np.ndarray:
    r = int(np.ceil(4.0 * sigma))
    xs = np.arange(-r, r + 1)
    k = np.exp(-(xs ** 2) / (2.0 * sigma * sigma))
    return (k / k.sum()).astype(np.float32)


def dense_sift(gray, cfg: dict):
    """(B, H, W) gray in [0, 1] -> (B, 128, m) quantised descriptors."""
    import jax.numpy as jnp

    step0, bin0 = int(cfg["sift_step"]), int(cfg["sift_bin"])
    scales, sstep = int(cfg["sift_scales"]), int(cfg["sift_scale_step"])
    B, H, W = gray.shape
    descs = []
    for s in range(scales):
        bin_size = bin0 + 2 * s
        step = step0 + s * sstep
        bound = (1 + 2 * scales) - 3 * s
        g = _gaussian(bin_size / 6.0)
        r = (len(g) - 1) // 2
        sm = _shift_sum(gray, g, 1, r, r, "edge")
        sm = _shift_sum(sm, g, 2, r, r, "edge")
        gy, gx = jnp.gradient(sm, axis=(1, 2))
        mag = jnp.sqrt(gx * gx + gy * gy)
        t = (jnp.arctan2(gy, gx) % (2.0 * jnp.pi)) / (2.0 * jnp.pi) * 8.0
        lo = jnp.floor(t)
        frac = t - lo
        b0 = lo.astype(jnp.int32) % 8
        b1 = (b0 + 1) % 8
        o = jnp.arange(8)[None, :, None, None]
        planes = mag[:, None] * (
            jnp.where(b0[:, None] == o, 1.0 - frac[:, None], 0.0)
            + jnp.where(b1[:, None] == o, frac[:, None], 0.0))  # (B,8,H,W)
        # triangular spatial binning (vl_imconvcoltri, zero padded), then
        # the bin centres of every frame, times the Gaussian window
        tri = np.maximum(0.0, (bin_size - np.abs(
            np.arange(-(bin_size - 1), bin_size))) / bin_size)
        extent = 3 * bin_size
        nfy = (H - 1 - bound - extent) // step + 1
        nfx = (W - 1 - bound - extent) // step + 1
        centres = (np.arange(4) - 1.5) * bin_size
        wf = np.exp(-0.5 * (centres / (1.5 * bin_size)) ** 2)
        iy = (bound + np.arange(nfy)[:, None] * step
              + np.arange(4)[None, :] * bin_size)  # (nfy, 4)
        ix = (bound + np.arange(nfx)[:, None] * step
              + np.arange(4)[None, :] * bin_size)
        p = _shift_sum(planes, tri, 2, bin_size - 1, bin_size - 1, "constant")
        p = p[:, :, iy.reshape(-1), :] * jnp.asarray(
            np.tile(wf, nfy), jnp.float32)[None, None, :, None]
        p = _shift_sum(p, tri, 3, bin_size - 1, bin_size - 1, "constant")
        p = p[:, :, :, ix.reshape(-1)] * jnp.asarray(
            np.tile(wf, nfx), jnp.float32)
        p = p.reshape(B, 8, nfy, 4, nfx, 4)
        raw = jnp.transpose(p, (0, 2, 4, 3, 5, 1)).reshape(B, nfy * nfx, 128)
        norms = jnp.linalg.norm(raw, axis=2)
        d = raw / jnp.maximum(norms, 1e-12)[..., None]
        d = jnp.minimum(d, 0.2)
        d = d / jnp.maximum(jnp.linalg.norm(d, axis=2), 1e-12)[..., None]
        descs.append(jnp.where((norms >= 0.005)[..., None], d, 0.0))
    d = jnp.concatenate(descs, axis=1)
    q = jnp.minimum(jnp.floor(d * 512.0), 255.0)
    return jnp.transpose(q, (0, 2, 1))


def lcs(img, cfg: dict):
    """(B, X, Y, C) float image -> (B, 96, keypoints): per keypoint and
    channel, mean and standard deviation of a 4x4 neighbourhood of
    sub-patches, box-filtered with the reference's asymmetric zero pad."""
    import jax.numpy as jnp

    stride, start, s = (int(cfg["lcs_stride"]), int(cfg["lcs_border"]),
                        int(cfg["lcs_patch"]))
    B, X, Y, C = img.shape
    box = np.full(s, 1.0 / s)
    lo, hi = (s - 1) // 2, s - 1 - (s - 1) // 2

    def boxed(z):
        z = _shift_sum(z, box, 1, lo, hi, "constant")
        return _shift_sum(z, box, 2, lo, hi, "constant")

    mean = boxed(img)
    sq = boxed(img * img)
    sd = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0))
    xs = np.arange(start, X - start, stride)
    ys = np.arange(start, Y - start, stride)
    offs = np.arange(-2 * s + s // 2 - 1, s + s // 2, s)
    px = (xs[None, :] + offs[:, None])  # (nb, nxk)
    py = (ys[None, :] + offs[:, None])

    def pick(z):  # -> (B, C, nbx, nby, nxk, nyk)
        z = z[:, px.reshape(-1)][:, :, py.reshape(-1)]
        z = z.reshape(B, len(offs), len(xs), len(offs), len(ys), C)
        return jnp.transpose(z, (0, 5, 1, 3, 2, 4))

    both = jnp.stack([pick(mean), pick(sd)], axis=4)
    return both.reshape(B, -1, len(xs) * len(ys))


def fisher(x, pca, means, precision):
    """(B, d_in, m) descriptors -> (B, 2*dd*k) normalised Fisher vector
    under unit variances and equal weights (the configuration's GMM)."""
    import jax.numpy as jnp

    k = means.shape[1]
    w = 1.0 / k
    z = einsum_at("kd,bdm->bkm", pca, x, precision)  # (B,dd,m)
    m = z.shape[2]
    zt = jnp.transpose(z, (0, 2, 1))  # (B, m, dd)
    half = jnp.full_like(means, 0.5)
    maha = (einsum_at("bmd,dk->bmk", zt * zt, half, precision)
            - einsum_at("bmd,dk->bmk", zt, means, precision)
            + 0.5 * jnp.sum(means * means, axis=0))
    dd = means.shape[0]
    llh = -0.5 * dd * np.log(2 * np.pi) + np.log(w) - maha
    llh = llh - jnp.max(llh, axis=2, keepdims=True)
    q = jnp.exp(llh)
    q = q / jnp.sum(q, axis=2, keepdims=True)
    q = jnp.where(q > 1e-4, q, 0.0)
    q = q / jnp.sum(q, axis=2, keepdims=True)
    s0 = jnp.mean(q, axis=1)  # (B, k)
    s1 = einsum_at("bdm,bmk->bdk", z, q, precision) / m
    s2 = einsum_at("bdm,bmk->bdk", z * z, q, precision) / m
    fv1 = (s1 - means * s0[:, None, :]) / np.sqrt(w)
    fv2 = (s2 - 2.0 * means * s1 + (means * means - 1.0) * s0[:, None, :]) \
        / np.sqrt(2.0 * w)
    fv = jnp.concatenate([fv1, fv2], axis=2)  # (B, dd, 2k)
    v = jnp.transpose(fv, (0, 2, 1)).reshape(fv.shape[0], -1)  # column-major

    def l2(a):
        return a / jnp.maximum(
            jnp.linalg.norm(a, axis=1, keepdims=True), 2.2e-16)

    v = l2(v)
    v = jnp.sign(v) * jnp.sqrt(jnp.abs(v))
    return l2(v)


def scores_of(images_u8, cfg: dict, params: dict, precision="highest",
              stages: dict = None):
    """(B, S, S, 3) uint8 -> (B, classes) scores. ``stages``, if given,
    is filled with the intermediate results."""
    import jax.numpy as jnp

    x = images_u8.astype(jnp.float32)
    gray = einsum_at("bhwc,c->bhw", x / 255.0,
                     jnp.asarray(GRAY, jnp.float32), precision)
    sift = dense_sift(gray, cfg)
    hell = jnp.sign(sift) * jnp.sqrt(jnp.abs(sift))
    f_sift = fisher(hell, params["sift_pca"], params["sift_means"], precision)
    desc_lcs = lcs(x, cfg)
    f_lcs = fisher(desc_lcs, params["lcs_pca"], params["lcs_means"], precision)
    feats = jnp.concatenate([f_sift, f_lcs], axis=1)
    out = einsum_at("bf,fc->bc", feats, params["model"], precision) \
        + params["intercept"]
    if stages is not None:
        stages.update(gray=gray, sift=sift, lcs=desc_lcs, f_sift=f_sift,
                      f_lcs=f_lcs, features=feats, scores=out)
    return out


BLOCK = 16  # images per reference call: ~0.4 GB of planes


def reference_scores(cfg: dict, seed: int, images: np.ndarray,
                     precision: str = "highest") -> np.ndarray:
    import jax
    import jax.numpy as jnp

    params = {k: jnp.asarray(v) for k, v in draw_params(cfg, seed).items()}
    fn = jax.jit(lambda im: scores_of(im, cfg, params, precision))
    out = []
    for s in range(0, len(images), BLOCK):
        out.append(np.asarray(fn(jnp.asarray(images[s:s + BLOCK]))))
    return np.concatenate(out)


def score_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The worst image's score error: max |got - want| over the classes,
    against that image's spread of reference scores (their standard
    deviation), so that an image is judged on the scale its ranking is
    decided on."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    err = np.max(np.abs(got - want), axis=1)
    return float(np.max(err / np.std(want, axis=1)))


def numbers(got: np.ndarray, want: np.ndarray) -> dict:
    return {"scores_rel_err": rel_err(got, want),
            "score_gap": score_gap(got, want)}


def compare(ctx, sample: dict) -> dict:
    want = reference_scores(ctx.config, ctx.seed, sample["images"])
    if not sample["outputs"]:
        return {"scores_rel_err": float("inf"), "score_gap": float("inf")}
    rows, got = sample["outputs"][0]
    return numbers(got, want[rows])


def control(ctx, sample: dict, precision: str = "bfloat16") -> dict:
    """The reference with its matrix products one precision step down,
    put in the program's place."""
    want = reference_scores(ctx.config, ctx.seed, sample["images"])
    low = reference_scores(ctx.config, ctx.seed, sample["images"],
                           precision=precision)
    return numbers(low, want)
