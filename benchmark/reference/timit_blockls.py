"""Plain reference of the TIMIT configuration (TimitPipeline.scala:37-100).

cos(x Wᵀ + b) random features (W ~ gamma·N(0,1), b ~ U(0, 2π), one seeded
draw per branch), concatenated; mean-centred block coordinate descent
least squares (BlockLeastSquaresEstimator: Gauss-Seidel sweeps over
blocks of ``block`` columns, the (b, b) system solved on the host in
float64 as the reference's driver does); scores = (phi - mu) W + mean(Y).

Straightforward jax.numpy in float32 with every matrix product at
``highest`` precision (reference/precision.py), on ONE device, in chunks of rows, the features of
a chunk and block recomputed where they are needed, so that four chips'
rows fit one chip. Nothing here is imported from keystone_tpu, and
nothing the program made is read: the random features are drawn again
from the configuration's rule.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err
from benchmark.reference.precision import matmul_at

CHUNK = 65536


def draw_features(cfg: dict, seed: int) -> tuple:
    """(W, b) of every branch, stacked: W (D, dim), b (D,)."""
    ws, bs = [], []
    for i in range(int(cfg["numCosines"])):
        rng = np.random.default_rng(seed + i)
        shape = (int(cfg["num_cosine_features"]), int(cfg["dim"]))
        if cfg["rfType"] == "cauchy":
            w = rng.standard_cauchy(shape) * float(cfg["gamma"])
        else:
            w = rng.standard_normal(shape) * float(cfg["gamma"])
        bs.append(rng.uniform(0.0, 2.0 * np.pi, shape[0]))
        ws.append(w)
    return (np.concatenate(ws).astype(np.float32),
            np.concatenate(bs).astype(np.float32))


def fit_and_score(cfg: dict, seed: int, x: np.ndarray, y: np.ndarray,
                  x_test: np.ndarray, precision: str = "highest") -> np.ndarray:
    """Scores of ``x_test`` under the model fitted on (x, y)."""
    import jax
    import jax.numpy as jnp
    import scipy.linalg

    dev = jax.devices()[0]
    w_all, b_all = draw_features(cfg, seed)
    n, k = x.shape[0], int(cfg["num_classes"])
    block = int(cfg["num_cosine_features"])
    epochs, lam = int(cfg["numEpochs"]), float(cfg["lambda"])
    d_total = w_all.shape[0]
    blocks = [(s, min(s + block, d_total)) for s in range(0, d_total, block)]
    put = lambda a: jax.device_put(a, dev)  # noqa: E731
    chunks = [(put(x[s:s + CHUNK]), put(y[s:s + CHUNK]))
              for s in range(0, n, CHUNK)]
    wb = [(put(w_all[a:b]), put(b_all[a:b])) for a, b in blocks]

    def mm(a, b):
        return matmul_at(a, b, precision)

    phi = jax.jit(lambda xc, w, b: jnp.cos(mm(xc, w.T) + b))
    onehot = jax.jit(lambda yc: 2.0 * jax.nn.one_hot(yc, k) - 1.0)
    # means
    mu = [sum(jnp.sum(phi(xc, w, b), axis=0) for xc, _ in chunks) / n
          for w, b in wb]
    mu_y = sum(jnp.sum(onehot(yc), axis=0) for _, yc in chunks) / n
    resid = [onehot(yc) - mu_y for _, yc in chunks]

    @jax.jit
    def gram_part(xc, w, b, m):
        a = phi(xc, w, b) - m
        return mm(a.T, a)

    @jax.jit
    def add_back(xc, w, b, m, r, wblk):
        a = phi(xc, w, b) - m
        r = r + mm(a, wblk)
        return r, mm(a.T, r)

    @jax.jit
    def take_off(xc, w, b, m, r, wblk):
        return r - mm(phi(xc, w, b) - m, wblk)

    factors = []
    for (w, b), m in zip(wb, mu):
        g = sum(gram_part(xc, w, b, m) for xc, _ in chunks)
        g64 = np.asarray(g, np.float64)
        g64 += lam * np.eye(g64.shape[0])
        factors.append(scipy.linalg.cho_factor(g64, lower=True))
    model = [jnp.zeros((b - a, k), jnp.float32) for a, b in blocks]
    for _ in range(epochs):
        for j, ((w, b), m) in enumerate(zip(wb, mu)):
            rhs = 0.0
            for c, (xc, _) in enumerate(chunks):
                resid[c], part = add_back(xc, w, b, m, resid[c], model[j])
                rhs = rhs + part
            sol = scipy.linalg.cho_solve(
                factors[j], np.asarray(rhs, np.float64))
            model[j] = put(sol.astype(np.float32))
            for c, (xc, _) in enumerate(chunks):
                resid[c] = take_off(xc, w, b, m, resid[c], model[j])
    xt = put(x_test)
    scores = mu_y + sum(
        mm(phi(xt, w, b) - m, wj)
        for (w, b), m, wj in zip(wb, mu, model))
    return np.asarray(scores)


def compare(ctx, sample: dict) -> dict:
    """The worst of the kept models' held-out scores against the
    reference's, as a relative Frobenius error."""
    want = fit_and_score(ctx.config, ctx.seed, sample["x"], sample["y"],
                         sample["x_test"])
    if not sample["outputs"]:
        return {"scores_rel_err": float("inf")}
    return {"scores_rel_err": max(
        rel_err(got, want) for got in sample["outputs"].values())}


def control(ctx, sample: dict, precision: str = "high") -> dict:
    """The reference one precision step down (``high``: three bf16
    passes, for a configuration that states float32 at ``highest``), put
    in the program's place: it has to come out as not correct."""
    want = fit_and_score(ctx.config, ctx.seed, sample["x"], sample["y"],
                         sample["x_test"])
    low = fit_and_score(ctx.config, ctx.seed, sample["x"], sample["y"],
                        sample["x_test"], precision=precision)
    return {"scores_rel_err": rel_err(low, want)}
