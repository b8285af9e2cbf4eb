"""Plain reference of VOCSIFTFisher fitted from images to model
(VOCSIFTFisher.scala:23-110: dense SIFT as VLFeat.cxx drives vl_dsift,
Sampling.scala, PCA.scala, GaussianMixtureModelEstimator.scala,
FisherVector.scala, NormalizeRows / SignedHellingerMapper,
BlockLeastSquaresEstimator.scala), and the staged comparison that decides
the cell's ``correct``.

Straightforward jax.numpy in float32 with every matrix product at
``highest``, float64 on the host where the reference's driver is float64
(the PCA's covariance and ``eigh``, the cumulative sums of the k-means++
draw, the (4096, 4096) block systems). Dense SIFT is
``reference/imagenet_sift_lcs_fv.py: dense_sift`` (shifted sums, no
kernels), which takes images that are not square, an image shape at a
time. Nothing is imported from keystone_tpu.

Why staged. The fit chains four estimators, and two of them cannot be
followed bit for bit by any second implementation: the k-means++ start
places each seed by a cumulative sum over 1e6 distances (a last-bit
difference moves a seed to its neighbour row), and the EM tests a
threshold every round. So each stage is held to the reference on the
program's own input to that stage, and every stage decides ``correct``:

1. ``pca_subspace_err``: the program's PCA basis against the reference's,
   fitted on the reference's own descriptors at the columns drawn again
   from the seed: the share of the variance the reference's basis
   captures that the program's misses (and its distance from orthonormal).
2. ``init_cdf_err``: each k-means++ seed the program took, against the
   reference's float64 D² distribution over its own sample (projected by
   the program's basis): how far the host's uniform for that seed lies
   from the seed's interval of the cumulative distribution.
3. ``gmm_rel_err`` and ``em_rounds_gap``: the reference's EM, started
   from those seeds, run for the program's count of rounds, against the
   program's means, variances and weights; and how many rounds apart the
   two stop by the published rules (tolerance, cluster floor, at most
   ``max_iterations``).
4. ``features_rel_err``: the held-out images' normalised Fisher vectors
   under the program's basis and mixture, from posteriors written out.
5. ``scores_rel_err``: the block sweep fitted on the program's training
   features, applied to its held-out features.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err
from benchmark.reference.imagenet_sift_lcs_fv import GRAY, dense_sift
from benchmark.reference.precision import einsum_at

IMAGE_BLOCK = 8  # images a SIFT call: ~50 MB of planes at 500 x 375
ROW_BLOCK = 1 << 16  # sample rows a block of posteriors: 64 MB at k 256


# -- descriptors and samples ------------------------------------------------

def by_shape(items) -> dict:
    groups: dict = {}
    for i, x in enumerate(items):
        groups.setdefault(x.shape, []).append(i)
    return groups


_SIFT: dict = {}


def sift_fn(cfg: dict):
    """uint8 (b, h, w, 3) -> (b, 128, m): one jitted function a
    configuration, kept, so that a process compiles each shape once."""
    import jax
    import jax.numpy as jnp

    key = tuple(int(cfg[k]) for k in (
        "sift_step", "sift_bin", "sift_scales", "sift_scale_step"))
    if key not in _SIFT:
        def sift(images):
            gray = einsum_at(
                "bhwc,c->bhw", images.astype(jnp.float32) / 255.0,
                jnp.asarray(GRAY, jnp.float32))
            return dense_sift(gray, cfg)
        _SIFT[key] = jax.jit(sift)
    return _SIFT[key]


def sift_blocks(cfg: dict, items):
    """(places, (b, 128, m) descriptors) a block of images of one shape;
    a short block is filled up with its last image, so that a shape
    compiles once."""
    import jax.numpy as jnp

    sift = sift_fn(cfg)
    for places in by_shape(items).values():
        for s in range(0, len(places), IMAGE_BLOCK):
            part = places[s:s + IMAGE_BLOCK]
            full = part + part[-1:] * (IMAGE_BLOCK - len(part))
            desc = sift(jnp.asarray(np.stack([items[i] for i in full])))
            yield part, desc[:len(part)]


def per_image(num_samples: int, num_images: int) -> int:
    return max(num_samples // max(num_images, 1), 1)


def draw_columns(seed: int, image: int, columns: int, count: int):
    """Image ``image``'s sampled columns: the stated rule."""
    return np.random.default_rng((seed, image)).integers(0, columns, count)


def sampled_descriptors(cfg: dict, seed: int, items) -> tuple:
    """(PCA sample, GMM sample before projection), each (N, 128): image
    i's columns drawn from ``default_rng((seed, i))`` for the PCA and
    ``default_rng((seed + 1, i))`` for the GMM, in the images' order."""
    import jax.numpy as jnp

    n = len(items)
    counts = (per_image(int(cfg["num_pca_samples"]), n),
              per_image(int(cfg["num_gmm_samples"]), n))
    out = ([None] * n, [None] * n)
    for places, desc in sift_blocks(cfg, items):
        m = desc.shape[2]
        for which, count in enumerate(counts):
            idx = np.stack([draw_columns(seed + which, i, m, count)
                            for i in places])
            cols = jnp.take_along_axis(
                desc, jnp.asarray(idx)[:, None, :], axis=2)  # (b, 128, s)
            for row, i in enumerate(places):
                out[which][i] = np.asarray(cols[row]).T
    return np.concatenate(out[0]), np.concatenate(out[1])


# -- PCA ----------------------------------------------------------------------

def pca_basis(sample: np.ndarray, dims: int,
              precision: str = "float64") -> np.ndarray:
    """(128, dims): eigenvectors of the centered covariance by falling
    eigenvalue, the largest entry of each made positive (PCA.scala).
    ``precision`` is that of the covariance's product: float64 on the
    host, or one of ``einsum_at``'s on float32 rows (the controls)."""
    x = sample.astype(np.float64)
    x = x - x.mean(axis=0)
    if precision == "float64":
        cov = x.T @ x
    else:
        import jax.numpy as jnp

        x32 = jnp.asarray(x.astype(np.float32))
        cov = np.asarray(
            einsum_at("nd,ne->de", x32, x32, precision), np.float64)
    _, vecs = np.linalg.eigh(cov)
    basis = vecs[:, ::-1][:, :dims]
    flip = np.where(basis.max(axis=0) == np.abs(basis).max(axis=0), 1.0, -1.0)
    return (basis * flip).astype(np.float32)


def subspace_err(got: np.ndarray, want: np.ndarray,
                 sample: np.ndarray) -> float:
    """How far ``got`` (128, dims) is from a PCA basis of ``sample``: the
    larger of the share of the variance that the reference's basis
    ``want`` captures and ``got`` misses, and the distance of ``got``'s
    columns from orthonormal. Not the distance of the two subspaces:
    where the spectrum is flat at the cut (these descriptors' is) the
    last directions are anyone's, and any of them is the PCA."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    x = sample.astype(np.float64)
    x = x - x.mean(axis=0)
    cov = x.T @ x
    captured = np.trace(got.T @ cov @ got)
    best = np.trace(want.T @ cov @ want)
    skew = np.linalg.norm(got.T @ got - np.eye(got.shape[1])) \
        / np.sqrt(got.shape[1])
    return float(max((best - captured) / best, skew))


# -- the mixture ---------------------------------------------------------------

def log_likelihoods(x, mu, var, w, precision="highest"):
    """(n, k) log w_k N(x; mu_k, var_k) of rows x (n, d); mu, var (k, d)."""
    import jax.numpy as jnp

    d = x.shape[1]
    maha = (einsum_at("nd,kd->nk", x * x, 0.5 / var, precision)
            - einsum_at("nd,kd->nk", x, mu / var, precision)
            + 0.5 * jnp.sum(mu * mu / var, axis=1))
    return (-0.5 * d * np.log(2 * np.pi) - 0.5 * jnp.sum(jnp.log(var), axis=1)
            + jnp.log(w) - maha)


def posteriors(llh, threshold: float):
    import jax.numpy as jnp

    q = jnp.exp(llh - jnp.max(llh, axis=1, keepdims=True))
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > threshold, q, 0.0)
    return q / jnp.sum(q, axis=1, keepdims=True)


def blocked(x, fn):
    """Sum of ``fn(block)`` (a tuple of arrays) over blocks of rows."""
    total = None
    for s in range(0, x.shape[0], ROW_BLOCK):
        part = fn(x[s:s + ROW_BLOCK])
        total = part if total is None else tuple(
            a + b for a, b in zip(total, part))
    return total


def _to_centre(xd, half, row, precision="highest"):
    """Half the squared distance of every row of ``xd`` to row ``row``,
    the product at ``precision``."""
    import jax

    if precision not in _TO_CENTRE:
        def to_centre(xd, half, row):
            import jax.numpy as jnp

            c = jax.lax.dynamic_index_in_dim(xd, row, keepdims=False)
            return half - einsum_at("nd,d->n", xd, c, precision) \
                + 0.5 * jnp.sum(c * c)
        _TO_CENTRE[precision] = jax.jit(to_centre)
    return _TO_CENTRE[precision](xd, half, row)


_TO_CENTRE: dict = {}


def kmeanspp_seeds(x: np.ndarray, first: int, uniforms: np.ndarray,
                   precision: str = "highest") -> np.ndarray:
    """The k-means++ seeds that the draws ``first`` and ``uniforms`` give
    over the rows ``x``: each next seed the row at which the cumulative
    D² distribution (float64 sums of distances whose product is at
    ``precision``) passes its uniform."""
    import jax.numpy as jnp

    xd = jnp.asarray(x)
    half = 0.5 * jnp.sum(xd * xd, axis=1)
    seeds, dist = [int(first)], None
    for u in uniforms:
        new = np.asarray(
            _to_centre(xd, half, seeds[-1], precision), np.float64)
        dist = new if dist is None else np.minimum(dist, new)
        cdf = np.cumsum(np.maximum(dist, 0.0))
        at = int(np.searchsorted(cdf, u * cdf[-1], side="right"))
        seeds.append(min(at, x.shape[0] - 1))
    return np.asarray(seeds)


def seeds_cdf_err(x: np.ndarray, seeds: np.ndarray, uniforms: np.ndarray,
                  first: int) -> float:
    """How far the k-means++ seeds lie from the float64 D² draw over the
    rows ``x``: for seed j + 1, the distance of ``uniforms[j]`` from the
    seed's interval of the cumulative distribution after j + 1 seeds (0
    inside it); the first seed must be ``first``."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    seeds = np.asarray(seeds)
    if seeds.shape != (len(uniforms) + 1,) or seeds.min() < 0 \
            or seeds.max() >= n or int(seeds[0]) != first:
        return float("inf")
    xd = jnp.asarray(x)
    half = 0.5 * jnp.sum(xd * xd, axis=1)

    dist, worst = None, 0.0
    for j, u in enumerate(uniforms):
        new = np.asarray(_to_centre(xd, half, int(seeds[j])), np.float64)
        dist = new if dist is None else np.minimum(dist, new)
        cdf = np.cumsum(np.maximum(dist, 0.0))
        at = int(seeds[j + 1])
        lo = (cdf[at - 1] if at else 0.0) / cdf[-1]
        hi = cdf[at] / cdf[-1]
        worst = max(worst, lo - u, u - hi)
    return float(worst)


def gmm_start(x, seeds, gmm_cfg: dict) -> tuple:
    """(mu, var, w, var_lb) (k, d): one Lloyd round from the seeds, then
    the moments of the nearest-mean clusters and the variance floor."""
    import jax
    import jax.numpy as jnp

    k = len(seeds)

    @jax.jit
    def sums(block, centres):
        d2 = (0.5 * jnp.sum(block * block, axis=1, keepdims=True)
              - einsum_at("nd,kd->nk", block, centres)
              + 0.5 * jnp.sum(centres * centres, axis=1))
        hot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=jnp.float32)
        return (jnp.sum(hot, axis=0), einsum_at("nk,nd->kd", hot, block),
                einsum_at("nk,nd->kd", hot, block * block))

    centres = x[jnp.asarray(seeds)]
    mass, s1, _ = blocked(x, lambda b: sums(b, centres))
    centres = s1 / jnp.maximum(mass, 1.0)[:, None]
    mass, s1, s2 = blocked(x, lambda b: sums(b, centres))
    inv = 1.0 / jnp.maximum(mass, 1.0)
    mu = inv[:, None] * s1
    var = inv[:, None] * s2 - mu * mu
    mean = jnp.mean(x, axis=0)
    var_global = jnp.mean(x * x, axis=0) - mean * mean
    var_lb = jnp.maximum(float(gmm_cfg["small_variance_threshold"])
                         * var_global,
                         float(gmm_cfg["absolute_variance_threshold"]))
    return mu, jnp.maximum(var, var_lb), mass / x.shape[0], var_lb


def em(x, start: tuple, gmm_cfg: dict, updates: int,
       precision: str = "highest") -> tuple:
    """The published EM from ``start``, the E-step's products (the
    Mahalanobis terms and the sums over the rows) at ``precision``.
    Returns (the model after
    ``updates`` M-steps; the round in which the published rules stop it:
    the cost rose by less than ``stop_tolerance`` of itself, or a cluster
    fell under ``min_cluster_size``, both tested before the round's
    update, or ``max_iterations`` rounds were made; and which)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    tol = float(gmm_cfg["stop_tolerance"])
    floor = float(gmm_cfg["min_cluster_size"])
    threshold = float(gmm_cfg["weight_threshold"])
    most = int(gmm_cfg["max_iterations"])
    mu, var, w, var_lb = start

    @jax.jit
    def estep(block, mu, var, w):
        llh = log_likelihoods(block, mu, var, w, precision)
        q = posteriors(llh, threshold)
        return (jnp.sum(jax.scipy.special.logsumexp(llh, axis=1)),
                jnp.sum(q, axis=0),
                einsum_at("nk,nd->kd", q, block, precision),
                einsum_at("nk,nd->kd", q, block * block, precision))

    kept = (mu, var, w) if updates == 0 else None
    prev, stopped = None, None
    for i in range(1, most + 1):
        lse, q_sum, s1, s2 = blocked(x, lambda b: estep(b, mu, var, w))
        cost = float(lse) / n
        if stopped is None:
            if prev is not None and cost - prev < tol * abs(prev):
                stopped = (i, "tolerance")
            elif bool(jnp.any(q_sum < floor)):
                stopped = (i, "cluster_floor")
        if stopped is not None and kept is not None:
            break
        prev = cost
        w = q_sum / n
        mu = s1 / q_sum[:, None]
        var = jnp.maximum(s2 / q_sum[:, None] - mu * mu, var_lb)
        if i == updates:
            kept = (mu, var, w)
    if stopped is None:
        stopped = (most, "max_iter")
    return kept or (mu, var, w), stopped[0], stopped[1]


# -- features and the model ------------------------------------------------------

def fisher_features(cfg: dict, items, basis, mu, var, w,
                    precision="highest", normalise_twice: bool = True):
    """(n, 2 d k) L2 / signed-sqrt / L2 normalised Fisher vectors of the
    images under the (128, d) basis and the (k, d) mixture, posteriors
    written out (FisherVector.scala:33-52, the Sanchez formulas).
    ``precision`` is that of the statistics' products."""
    import jax
    import jax.numpy as jnp

    threshold = float(cfg["gmm"]["weight_threshold"])

    def l2(a):
        return a / jnp.maximum(
            jnp.linalg.norm(a, axis=1, keepdims=True), 2.2e-16)

    @jax.jit
    def one(desc):  # (128, m)
        z = einsum_at("dk,dm->km", basis, desc).T  # (m, d)
        m = z.shape[0]
        q = posteriors(log_likelihoods(z, mu, var, w, precision), threshold)
        s0 = jnp.sum(q, axis=0) / m
        s1 = einsum_at("mk,md->kd", q, z, precision) / m
        s2 = einsum_at("mk,md->kd", q, z * z, precision) / m
        fv1 = (s1 - mu * s0[:, None]) / (jnp.sqrt(var) * jnp.sqrt(w)[:, None])
        fv2 = (s2 - 2.0 * mu * s1 + (mu * mu - var) * s0[:, None]) \
            / (var * jnp.sqrt(2.0 * w)[:, None])
        # the (d, 2k) matrix [fv1ᵀ | fv2ᵀ], flattened column-major
        v = l2(jnp.concatenate([fv1, fv2], axis=0).reshape(1, -1))
        v = jnp.sign(v) * jnp.sqrt(jnp.abs(v))
        return (l2(v) if normalise_twice else v)[0]

    out = [None] * len(items)
    for places, desc in sift_blocks(cfg, items):
        for row, i in enumerate(places):
            out[i] = np.asarray(one(desc[row]))
    return np.stack(out)


def block_scores(cfg: dict, feats, labels, test_feats,
                 precision: str = "highest") -> np.ndarray:
    """BlockLeastSquaresEstimator(block, 1, lambda) on centered features
    and labels, one pass over the blocks in order, each block's system
    solved in float64; the scores of ``test_feats``. ``precision`` is
    that of every product over the rows."""
    import jax.numpy as jnp
    import scipy.linalg

    block, lam = int(cfg["block_size"]), float(cfg["lambda"])
    a_all, t_all = jnp.asarray(feats), jnp.asarray(test_feats)
    y = jnp.asarray(labels)
    mu_y = jnp.mean(y, axis=0)
    resid = y - mu_y
    scores = jnp.zeros((t_all.shape[0], y.shape[1]), jnp.float32) + mu_y
    for _ in range(int(cfg["num_iter"])):
        for s in range(0, a_all.shape[1], block):
            a = a_all[:, s:s + block]
            mean = jnp.mean(a, axis=0)
            a = a - mean
            g64 = np.asarray(
                einsum_at("nb,nc->bc", a, a, precision), np.float64)
            g64 += lam * np.eye(g64.shape[0])
            rhs = np.asarray(
                einsum_at("nb,nk->bk", a, resid, precision), np.float64)
            sol = scipy.linalg.cho_solve(
                scipy.linalg.cho_factor(g64, lower=True), rhs)
            wb = jnp.asarray(sol.astype(np.float32))
            resid = resid - einsum_at("nb,bk->nk", a, wb, precision)
            scores = scores + einsum_at(
                "nb,bk->nk", t_all[:, s:s + block] - mean, wb, precision)
    return np.asarray(scores)


# -- the comparison ---------------------------------------------------------------

NUMBERS = ("pca_subspace_err", "init_cdf_err", "gmm_rel_err",
           "em_rounds_gap", "features_rel_err", "scores_rel_err")


def kmeanspp_draws(seed: int, n: int, k: int) -> tuple:
    """(first seed's row, the k - 1 uniforms): the host generator's part
    of a k-means++ start, in the order the seeding loop draws them."""
    rng = np.random.default_rng(seed)
    return int(rng.integers(0, n)), rng.random(k - 1)


def stage_numbers(cfg: dict, sample: dict, got: dict, shared: dict) -> dict:
    """One kept model against the reference, stage by stage. ``shared``
    carries what does not depend on the model (the reference's samples
    and PCA) from one kept model to the next, and under ``first`` what
    the first kept model's stages were held to, for ``control``."""
    import jax.numpy as jnp

    seed, k = int(sample["seed"]), int(cfg["vocab_size"])
    dims = int(cfg["desc_dim"])
    if "pca_sample" not in shared:
        shared["pca_sample"], shared["gmm_raw"] = sampled_descriptors(
            cfg, seed, sample["items"])
        shared["basis"] = pca_basis(shared["pca_sample"], dims)
    out = {"pca_subspace_err": subspace_err(
        got["pca"], shared["basis"], shared["pca_sample"])}
    basis = np.asarray(got["pca"], np.float32)
    if basis.shape != (128, dims):
        return {name: float("inf") for name in NUMBERS}
    basis_d = jnp.asarray(basis)
    x = einsum_at("nd,dk->nk", jnp.asarray(shared["gmm_raw"]), basis_d)
    first, uniforms = kmeanspp_draws(seed, x.shape[0], k)
    out["init_cdf_err"] = seeds_cdf_err(
        np.asarray(x), got["seeds"], uniforms, first)
    if not np.isfinite(out["init_cdf_err"]):
        return {name: float("inf") for name in NUMBERS}
    start = gmm_start(x, np.asarray(got["seeds"]), cfg["gmm"])
    updates = int(got["iterations"]) - (got["reason"] != "max_iter")
    model, rounds, _ = em(x, start, cfg["gmm"], updates)
    out["em_rounds_gap"] = float(abs(rounds - int(got["iterations"])))
    out["gmm_rel_err"] = gmm_err(
        (np.asarray(got["means"]).T, np.asarray(got["variances"]).T,
         got["weights"]), model)
    mu_p, var_p, w_p = (jnp.asarray(np.asarray(got["means"]).T),
                        jnp.asarray(np.asarray(got["variances"]).T),
                        jnp.asarray(got["weights"]))
    feats = fisher_features(
        cfg, sample["test_items"], basis_d, mu_p, var_p, w_p)
    out["features_rel_err"] = rel_err(got["features"], feats)
    scores = block_scores(
        cfg, got["train_features"], sample["labels"], got["features"])
    out["scores_rel_err"] = rel_err(got["scores"], scores)
    shared.setdefault("first", {
        "x": x, "start": start, "updates": updates, "model": model,
        "rounds": rounds, "mixture": (basis_d, mu_p, var_p, w_p),
        "features": feats, "scores": scores, "got": got})
    return out


def gmm_err(got: tuple, want: tuple) -> float:
    """The worst relative error of (means, variances, weights)."""
    return max(rel_err(np.asarray(g), np.asarray(w))
               for g, w in zip(got, want))


def compare(ctx, sample: dict) -> dict:
    """The worst of the kept models at every stage."""
    if not sample["outputs"]:
        return {name: float("inf") for name in NUMBERS}
    shared = sample.setdefault("shared", {})
    each = [stage_numbers(ctx.config, sample, got, shared)
            for got in sample["outputs"].values()]
    return {name: max(e[name] for e in each) for name in NUMBERS}


def control(ctx, sample: dict) -> dict:
    """Each stage's upper reading: the reference with one thing wrong,
    put in the program's place and read by that stage's own measure
    against the reference as ``compare`` ran it for the first kept
    model. Where the configuration's precision carries the stage, the
    wrong thing is the nearest precision below it, the stage's products
    over the rows at ONE bf16 pass (``bfloat16_*``); where the measure
    cannot see a precision (the PCA's: a covariance summed over 1e6 rows
    averages the rounding away; the stopping rules'), a fault it can see
    (``half_sample_pca``, ``tolerance_x10``). ``bfloat16_statistics`` is
    the cell's control: it has to come out as not correct by
    ``features_rel_err``."""
    cfg = ctx.config
    shared = sample.get("shared") or {}
    if "first" not in shared:
        compare(ctx, sample)
        shared = sample["shared"]
    if "first" not in shared:
        return {}
    kept = shared["first"]
    dims, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    pca_sample = shared["pca_sample"]
    x = np.asarray(kept["x"])
    out = {name: {"pca_subspace_err": subspace_err(
        basis, shared["basis"], pca_sample)} for name, basis in (
            ("bfloat16_gram", pca_basis(pca_sample, dims, "bfloat16")),
            ("half_sample_pca", pca_basis(pca_sample[::2], dims)))}
    first, uniforms = kmeanspp_draws(int(sample["seed"]), x.shape[0], k)
    out["bfloat16_distances"] = {"init_cdf_err": seeds_cdf_err(
        x, kmeanspp_seeds(x, first, uniforms, "bfloat16"), uniforms, first)}
    low, rounds, _ = em(
        kept["x"], kept["start"], cfg["gmm"], kept["updates"], "bfloat16")
    out["bfloat16_em"] = {
        "gmm_rel_err": gmm_err(low, kept["model"]),
        "em_rounds_gap": float(abs(rounds - kept["rounds"]))}
    loose = dict(cfg["gmm"],
                 stop_tolerance=10.0 * float(cfg["gmm"]["stop_tolerance"]))
    _, rounds, _ = em(kept["x"], kept["start"], loose, kept["updates"])
    out["tolerance_x10"] = {
        "em_rounds_gap": float(abs(rounds - kept["rounds"]))}
    out["bfloat16_statistics"] = {"features_rel_err": rel_err(
        fisher_features(cfg, sample["test_items"], *kept["mixture"],
                        precision="bfloat16"), kept["features"])}
    got = kept["got"]
    out["bfloat16_solver"] = {"scores_rel_err": rel_err(block_scores(
        cfg, got["train_features"], sample["labels"], got["features"],
        "bfloat16"), kept["scores"])}
    return out
