"""Benchmarks for the five BASELINE.md tracked configs, on the live TPU.

Prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": x | null}
vs_baseline > 1 means faster than the reference 16-node r3.4xlarge Spark
cluster; null where the reference published no number for the config
(BASELINE.md: only the TIMIT/Amazon solver rows have published times).
Solver rows additionally carry "tflops" (achieved TFLOP/s from the
analytic FLOP count of the measured program) so MFU is tracked per
round (v5e peak is ~197 bf16 TFLOP/s).

Tracked configs (BASELINE.md "Tracked configs"):
  - TimitPipeline      -> timit_block_ls_1024_solve(+_amortized)
  - MnistRandomFFT     -> mnist_random_fft_featurize_solve
  - RandomPatchCifar   -> random_patch_cifar_featurize imgs/sec (the
    app's real whitened-filter path) + solve
  - NewsgroupsPipeline -> newsgroups_train
  - ImageNetSiftLcsFV  -> imagenet_sift_lcs_fv examples/sec/chip
    (featurize-only north star) + imagenet_sift_lcs_fv_end_to_end
    (featurize -> weighted BCD fit -> top-5: the BASELINE.json metric)
  - flagship solvers   -> weighted_block_ls_4096_solve, krr_block_solve

Timing discipline: np.asarray(...) on a result forces real execution;
each metric queues its whole computation and syncs once, and carries
``device_ms`` = wall minus the measured ``sync_roundtrip`` (the
*_amortized metric additionally amortizes that fixed sync cost away).
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

TIMIT_BASELINE_MS = 33_521.0  # scripts/solver-comparisons-final.csv:14
AMAZON_EXACT_BASELINE_MS = 186_149.0  # …csv:2 (Exact, 1024 features)
AMAZON_BEST_BASELINE_MS = 33_704.0  # …csv:4 (LS-LBFGS, their fastest)


_ROWS = []  # every emitted row, for the --markdown table


def emit(metric: str, value: float, unit: str, vs=None, tflops=None,
         extra=None) -> None:
    row = {
        "metric": metric,
        "value": round(value, 2) if value is not None else None,
        "unit": unit,
        "vs_baseline": round(vs, 2) if vs else None,
    }
    if tflops is not None:
        row["tflops"] = round(tflops, 2)
    if extra:
        row.update(extra)
    _ROWS.append(row)
    print(json.dumps(row), flush=True)


def measure(run_once, reps: int = 3):
    """Best-of-``reps`` + spread for a single-sync measured callable
    (VERDICT r3 weak #8: single-shot rows are dominated by sync
    round-trip jitter; best-of-k with the spread reported makes
    round-over-round deltas attributable). Returns (best_ms, extra)."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_once()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times), {
        "spread_ms": round(max(times) - min(times), 2),
        "reps": reps,
    }


_RT_MS = None


def sync_rt_ms() -> float:
    """Measured host↔device round-trip latency (best of 7 syncs of an
    already-materialized scalar). Every single-sync row's wall time is
    ``device + RT``; rows carry ``device_ms = wall − RT`` so the
    program's own cost is TRACKED, not argued in PROFILE notes
    (VERDICT r4 weak #2/#3). Measured once per bench process and
    emitted as its own row."""
    global _RT_MS
    if _RT_MS is None:
        np.asarray(jnp.zeros(()))  # warm the trivial program
        times = []
        for i in range(7):
            # a FRESH tiny computation per rep: re-reading an
            # already-materialized array is served from the host-side
            # buffer cache and measures ~0
            x = jnp.full((), float(i))
            t0 = time.perf_counter()
            np.asarray(x)
            times.append((time.perf_counter() - t0) * 1e3)
        _RT_MS = min(times)
        emit("sync_roundtrip", _RT_MS, "ms",
             extra={"spread_ms": round(max(times) - min(times), 2)})
    return _RT_MS


def solver_extras(best_ms: float, flop: float, extra: dict) -> dict:
    """Attach the RT-corrected device-side time and TFLOP/s to a solver
    row (the sync round trip and the program were previously conflated
    in the tracked number)."""
    rt = sync_rt_ms()
    device_ms = max(best_ms - rt, 1e-3)
    extra = dict(extra)
    extra.update(
        device_ms=round(device_ms, 2),
        tflops_device=round(flop / device_ms / 1e9, 2),
        rt_ms=round(rt, 1),
    )
    return extra


def bench_timit() -> None:
    """BlockLS solve on the TIMIT shape: 2.25M frames x 1024 features,
    147 classes, one BCD pass (reference row: 33,521 ms on the cluster)."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.parallel.dataset import Dataset

    N, D, K, BLOCK = 2_251_569, 1024, 147, 1024
    mesh = mesh_lib.make_mesh()
    with mesh_lib.use_mesh(mesh):
        nshards = mesh_lib.n_data_shards(mesh)
        n = -(-N // nshards) * nshards

        @jax.jit
        def gen(key):
            kx, kw = jax.random.split(key)
            mask = (jnp.arange(n) < N).astype(jnp.bfloat16)[:, None]
            X = jax.random.normal(kx, (n, D), jnp.bfloat16) * mask
            W = jax.random.normal(kw, (D, K), jnp.bfloat16) * 0.1
            Y = jax.lax.dot_general(
                X, W, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return X, Y

        X, Y = gen(jax.random.PRNGKey(0))
        X = jax.device_put(X, mesh_lib.data_sharding(mesh))
        Y = jax.device_put(Y, mesh_lib.data_sharding(mesh))
        np.asarray(X[:1, :1])
        Xd = Dataset.from_array(X, n=N)
        Yd = Dataset.from_array(Y, n=N)

        # FLOPs of the measured program (num_iter=1, one 1024 block):
        # first_pass skips the zero-model contrib matmul and last_pass
        # skips the dead residual update, leaving gram (2·N·D²) +
        # rhs (2·N·D·K).
        flop = 2 * N * D * D + 2 * N * D * K

        est = BlockLeastSquaresEstimator(block_size=BLOCK, num_iter=1, lam=0.1)
        np.asarray(est.fit(Xd, Yd).W)  # warm compile + force exec
        single_ms, extra = measure(
            lambda: np.asarray(est.fit(Xd, Yd).W), reps=3
        )

        reps = 8
        t0 = time.perf_counter()
        last = None
        for _ in range(reps):
            last = est.fit(Xd, Yd)
        np.asarray(last.W)
        amortized_ms = (time.perf_counter() - t0) * 1e3 / reps

    emit("timit_block_ls_1024_solve", single_ms, "ms",
         TIMIT_BASELINE_MS / single_ms, tflops=flop / single_ms / 1e9,
         extra=solver_extras(single_ms, flop, extra))
    emit("timit_block_ls_1024_solve_amortized", amortized_ms, "ms",
         TIMIT_BASELINE_MS / amortized_ms,
         tflops=flop / amortized_ms / 1e9, extra={"reps": reps})


TIMIT_LBFGS_BASELINE_MS = 70_396.0  # …csv:15 (LS-LBFGS, 1024 features)


def bench_timit_lbfgs() -> None:
    """Fused device L-BFGS at the TIMIT shape (2.25M x 1024, 147
    classes, 20 iterations — reference row: 70,396 ms on the cluster,
    scripts/solver-comparisons-final.csv:15). The whole optimization
    (two-loop recursion + Armijo line search) runs as ONE device
    program (ops/learning/lbfgs.py run_lbfgs_device)."""
    from keystone_tpu.ops.learning.lbfgs import DenseLBFGSwithL2
    from keystone_tpu.parallel.dataset import Dataset

    N, D, K = 2_251_569, 1024, 147

    @jax.jit
    def gen(key):
        kx, kw = jax.random.split(key)
        X = jax.random.normal(kx, (N, D), jnp.bfloat16)
        W = jax.random.normal(kw, (D, K), jnp.bfloat16) * 0.1
        Y = jax.lax.dot_general(
            X, W, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return X, Y

    X, Y = gen(jax.random.PRNGKey(0))
    Xd = Dataset.from_array(X, n=N)
    Yd = Dataset.from_array(Y, n=N)
    est = DenseLBFGSwithL2(
        num_iterations=20, reg_param=1e-4, fit_intercept=False
    )

    # LOWER bound: one value+grad per iteration (forward 2NDK + backward
    # 2NDK); Armijo re-evaluations on top are data-dependent
    flop = est.num_iterations * 4 * N * D * K

    np.asarray(est.fit(Xd, Yd).W[:1, :1])  # warm
    ms, extra = measure(
        lambda: np.asarray(est.fit(Xd, Yd).W[:1, :1]), reps=3
    )
    emit("timit_lbfgs_1024_solve", ms, "ms",
         TIMIT_LBFGS_BASELINE_MS / ms, tflops=flop / ms / 1e9,
         extra=solver_extras(ms, flop, extra))


def bench_amazon() -> None:
    """Amazon reviews solver row at the reference experiment's shape:
    65M examples x 1024 hashed-TF features, ~0.5% dense (nnz=5/row),
    binary labels (scripts/constantEstimator.R:34-36). The ELL one-pass
    normal-equations solver (ops/learning/sparse_ell.py) replaces BOTH
    reference solvers for this least-squares workload, so one measured
    fit compares against the Exact row (186,149 ms) and against their
    fastest solver, LS-LBFGS (33,704 ms)."""
    from keystone_tpu.ops.learning import (
        EllLeastSquaresEstimator, ell_dataset,
    )
    from keystone_tpu.parallel.dataset import Dataset

    N, D, NNZ, K = 65_000_000, 1024, 5, 2

    @jax.jit
    def gen(key):
        ki, kv, kb = jax.random.split(key, 3)
        return (
            jax.random.randint(ki, (N, NNZ), 0, D, jnp.int32),
            jax.random.normal(kv, (N, NNZ), jnp.bfloat16),
            jax.random.normal(kb, (N, K), jnp.bfloat16),
        )

    idx, vals, Y = gen(jax.random.PRNGKey(0))
    ds = ell_dataset(idx, vals)
    labels = Dataset.from_array(Y)
    est = EllLeastSquaresEstimator(d=D, lam=1e-2)

    # tile-densified Gram + AᵀY over the dense (chunk, d) tiles: the
    # solver really performs the dense-equivalent matmuls on the MXU
    flop = 2 * N * D * (D + K)

    np.asarray(est.fit(ds, labels).W[0, 0])  # warm
    ms, extra = measure(
        lambda: np.asarray(est.fit(ds, labels).W[0, 0]), reps=3
    )
    extra = solver_extras(ms, flop, extra)
    emit("amazon_ls_1024_solve", ms, "ms", AMAZON_BEST_BASELINE_MS / ms,
         tflops=flop / ms / 1e9, extra=extra)
    emit("amazon_exact_1024_solve", ms, "ms",
         AMAZON_EXACT_BASELINE_MS / ms, tflops=flop / ms / 1e9,
         extra=extra)


AMAZON_BLOCK_16384_BASELINE_MS = 13_631_976.0  # …csv:11 (Block, 16384)
AMAZON_LBFGS_16384_BASELINE_MS = 52_290.0  # …csv:12 (LS-LBFGS, 16384)


def bench_amazon_16384(n: int = 65_000_000) -> None:
    """Amazon reviews at the reference's HEADLINE config — 16384 hashed
    features (scripts/solver-comparisons-final.csv:11-12: Block
    13,631,976 ms, LS-LBFGS 52,290 ms, both reaching 11.4% train
    error). One ELL normal-equations pass + (16384,16384) solve: the
    exact solution (Block-quality) in one data pass. The Gram is
    2·N·D² ≈ 3.5e16 dense-equivalent FLOPs — a many-minute
    single-chip program, so the row is OPT-IN (``--amazon-16384``),
    timed as ONE fit (reps=1; the scan program is length-dependent, so
    there is no cheap warm pass), run once per round and recorded in
    PERF. Two emits mirror the 1024-feature rows:
    vs the solver with matching solution quality (Block) and vs the
    reference's fastest solver at this width (LS-LBFGS)."""
    from keystone_tpu.ops.learning import (
        EllLeastSquaresEstimator, ell_dataset,
    )
    from keystone_tpu.parallel.dataset import Dataset

    D, NNZ, K = 16_384, 5, 2
    # dense (chunk, 16384) bf16 tile = 512 MB; the 1M default would be
    # a 32 GB tile
    CHUNK = 16_384

    @jax.jit
    def gen(key):
        ki, kv, kb = jax.random.split(key, 3)
        return (
            jax.random.randint(ki, (n, NNZ), 0, D, jnp.int32),
            jax.random.normal(kv, (n, NNZ), jnp.bfloat16),
            jax.random.normal(kb, (n, K), jnp.bfloat16),
        )

    idx, vals, Y = gen(jax.random.PRNGKey(0))
    ds = ell_dataset(idx, vals)
    labels = Dataset.from_array(Y)
    est = EllLeastSquaresEstimator(d=D, lam=1e-2, chunk=CHUNK)

    flop = 2 * n * D * (D + K)
    t0 = time.perf_counter()
    W = est.fit(ds, labels).W
    np.asarray(W[0, 0])
    ms = (time.perf_counter() - t0) * 1e3
    assert bool(np.isfinite(np.asarray(W).sum())), "non-finite W"
    extra = solver_extras(ms, flop, {"reps": 1, "n": n})
    emit("amazon_exact_16384_solve", ms, "ms",
         AMAZON_BLOCK_16384_BASELINE_MS / ms, tflops=flop / ms / 1e9,
         extra=extra)
    emit("amazon_ls_16384_solve", ms, "ms",
         AMAZON_LBFGS_16384_BASELINE_MS / ms, tflops=flop / ms / 1e9,
         extra=extra)


def bench_mnist() -> None:
    """MnistRandomFFT at MNIST scale (60k x 784, 24 FFT branches -> 24,576
    features) — featurize + one-pass BlockLS, end to end."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import RandomFFTFeatures
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators
    from keystone_tpu.parallel.dataset import Dataset

    N, D, NUM_FFTS, K = 60_000, 784, 24, 10
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, K, N).astype(np.int32))
    labels = ClassLabelIndicators(K).apply_batch(Dataset.from_array(y))
    fft_bank = RandomFFTFeatures.create(D, NUM_FFTS, seed=0)

    def featurize(ds):
        out = fft_bank.apply_batch(ds)
        return Dataset.from_array(
            out.padded().astype(jnp.bfloat16), n=ds.n
        )

    est = BlockLeastSquaresEstimator(block_size=4096, num_iter=1, lam=0.1)

    def run_once():
        feats = featurize(Dataset.from_array(X))
        model = est.fit(feats, labels)
        np.asarray(model.W)

    run_once()  # warm
    ms, extra = measure(run_once, reps=3)
    emit("mnist_random_fft_featurize_solve", ms, "ms", extra=extra)


def bench_cifar() -> None:
    """RandomPatchCifar at the app's REAL featurization path — whitened
    random-patch filter bank (Windower patches -> normalize -> ZCA ->
    filters, pipelines/images/random_patch_cifar.py build_filters, ref
    RandomPatchCifar.scala:45-57), then conv + rectify + pool over the
    CIFAR train set with the whole chunk loop inside ONE jitted
    lax.map program (no per-chunk Python dispatch or host concat), and
    the 4096-feature BlockLS solve."""
    from keystone_tpu.ops.images import (
        Convolver, Pooler, SymmetricRectifier,
    )
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images.random_patch_cifar import (
        RandomCifarConfig, build_filters, synthetic_cifar,
    )

    N, SIZE, F = 10_000, 32, 512
    conf = RandomCifarConfig(num_filters=F)
    train, _ = synthetic_cifar(n_train=2_000)
    filters, whitener = build_filters(train.images, conf)

    conv = Convolver(
        filters, SIZE, SIZE, 3, whitener=whitener, normalize_patches=True
    )
    rect = SymmetricRectifier(alpha=conf.alpha)
    pool = Pooler(conf.pool_stride, conf.pool_size)

    rng = np.random.default_rng(0)
    imgs = jnp.asarray(
        rng.standard_normal((N, SIZE, SIZE, 3)).astype(np.float32) * 20
        + 128
    )
    CHUNK = 500  # conv intermediate is (CHUNK, 27, 27, 2F) — HBM-bounded

    @jax.jit
    def featurize(imgs_chunked):
        def one(chunk):
            z = conv._convolve.__wrapped__(conv, chunk)
            z = rect.apply(z)
            z = pool._pool.__wrapped__(pool, z)
            return jnp.transpose(z, (0, 2, 1, 3)).reshape(z.shape[0], -1)
        return jax.lax.map(one, imgs_chunked)

    chunked = imgs.reshape(N // CHUNK, CHUNK, SIZE, SIZE, 3)
    out = featurize(chunked)  # warm
    np.asarray(out[:1, :1, :1])
    state = {}

    def run_once():
        state["out"] = featurize(chunked)
        np.asarray(state["out"][:1, :1, :1])

    ms, extra = measure(run_once, reps=3)
    out = state["out"]
    emit("random_patch_cifar_featurize", N / (ms / 1e3), "imgs/sec",
         extra=extra)

    feats = Dataset.from_array(
        out.reshape(N, -1).astype(jnp.bfloat16), n=N
    )
    y = jnp.asarray(rng.integers(0, 10, N).astype(np.int32))
    labels = ClassLabelIndicators(10).apply_batch(Dataset.from_array(y))
    est = BlockLeastSquaresEstimator(block_size=4096, num_iter=1, lam=10.0)
    np.asarray(est.fit(feats, labels).W)  # warm
    ms, extra = measure(
        lambda: np.asarray(est.fit(feats, labels).W), reps=3
    )
    emit("random_patch_cifar_solve", ms, "ms", extra=extra)


def bench_newsgroups() -> None:
    """NewsgroupsPipeline train path on synthetic 20-class docs:
    tokenize -> 1..2-grams -> TF -> CommonSparseFeatures(10k) ->
    NaiveBayes (host featurization + device solve)."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.text.newsgroups import (
        NewsgroupsConfig, build_pipeline,
    )
    from keystone_tpu.parallel.dataset import Dataset

    rng = np.random.default_rng(0)
    vocab = [f"w{i:04d}" for i in range(2000)]
    docs, ys = [], []
    for i in range(2000):
        c = i % 20
        words = rng.choice(vocab[c * 80: c * 80 + 200], size=60)
        docs.append(" ".join(words))
        ys.append(c)
    train = LabeledData(
        data=Dataset.from_items(docs),
        labels=Dataset.from_array(jnp.asarray(np.asarray(ys, np.int32))),
    )
    conf = NewsgroupsConfig(n_grams=2, common_features=10_000)

    def run_once():
        pipe = build_pipeline(train, conf)
        preds = pipe.apply(train.data).get()
        np.asarray(preds.padded()[:1])

    run_once()  # warm
    ms, extra = measure(run_once, reps=3)
    emit("newsgroups_train", ms, "ms", extra=extra)


def bench_weighted_ls() -> None:
    """The flagship's ACTUAL solver: BlockWeightedLeastSquaresEstimator
    (mixture-weighted BCD) at the ImageNetSiftLcsFV training shape per
    chip — FV-dim features (2 branches x 2·descDim·vocabSize = 8192),
    block size 4096 (ImageNetSiftLcsFV.scala:139-142), 128 classes,
    262k examples (the reference published no time for this solver ->
    vs_baseline null; this row exists so the flagship's own solver has
    a measured number, VERDICT r2 missing #3).

    Superseded, and not the record: the benchmark's cell
    ``weighted-bcd-fit`` (BENCHMARK.json, PR 27) runs this solver through
    the application's own ``fit_classifier`` at the application's
    settings (4096 float32 features, 1,000 classes, lambda 6e-5, w 0.25),
    which this row's 128 classes, 8,192 bf16 features and other lambda
    and w are not."""
    from keystone_tpu.ops.learning import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators
    from keystone_tpu.parallel.dataset import Dataset

    N, D, C, BLOCK = 262_144, 8192, 128, 4096

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (N, D), jnp.bfloat16)
        y = jax.random.randint(ky, (N,), 0, C, jnp.int32)
        return X, y

    X, y = gen(jax.random.PRNGKey(0))
    Xd = Dataset.from_array(X, n=N)
    labels = ClassLabelIndicators(C).apply_batch(Dataset.from_array(y))

    est = BlockWeightedLeastSquaresEstimator(
        block_size=BLOCK, num_iter=1, lam=1e-3, mixture_weight=0.5,
        convergence_check="off",  # the check syncs inside fit; the bench
        # reads + asserts the same diagnostics AFTER the timed region
    )
    np.asarray(est.fit(Xd, labels).W[:1, :1])  # warm
    state = {}

    def run_once():
        state["model"] = est.fit(Xd, labels)
        np.asarray(state["model"].W[:1, :1])

    ms, extra = measure(run_once)
    model = state["model"]
    pcg_rel = float(model.solver_info["pcg_max_rel_residual"])
    pcg_iters = int(model.solver_info["pcg_iterations"])
    assert pcg_rel < 1e-5, f"under-converged PCG in bench: {pcg_rel}"
    extra.update(pcg_max_rel_residual=pcg_rel, pcg_iterations=pcg_iters)

    # FLOPs of the measured (auto->PCG) path — a LOWER bound counting
    # only its guaranteed dense passes: pop cov 2·N·b² + residual delta
    # 2·N·b·C per block. The CG matvecs/preconditioner solves on top are
    # iteration-count-dependent and excluded, so true utilization is
    # somewhat higher than the emitted tflops.
    nb = D // BLOCK
    flop = nb * (2 * N * BLOCK**2 + 2 * N * BLOCK * C)
    emit("weighted_block_ls_4096_solve", ms, "ms", tflops=flop / ms / 1e9,
         extra=solver_extras(ms, flop, extra))


def bench_krr() -> None:
    """KernelRidgeRegression block Gauss-Seidel solve at the
    RandomPatchCifarKernel shape: 48k train rows, 1024-dim features,
    RBF kernel, 4096-row blocks, 10 classes, one epoch
    (KernelRidgeRegression.scala:86-235; no published reference time ->
    vs_baseline null)."""
    from keystone_tpu.ops.learning.kernel import (
        GaussianKernelGenerator, KernelRidgeRegression,
    )
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators
    from keystone_tpu.parallel.dataset import Dataset

    N, D, K, BLOCK = 49_152, 1024, 10, 4096

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (N, D), jnp.float32)
        y = jax.random.randint(ky, (N,), 0, K, jnp.int32)
        return X, y

    X, y = gen(jax.random.PRNGKey(0))
    Xd = Dataset.from_array(X, n=N)
    labels = ClassLabelIndicators(K).apply_batch(Dataset.from_array(y))

    est = KernelRidgeRegression(
        kernel_generator=GaussianKernelGenerator(gamma=1e-3),
        lam=1e-2, block_size=BLOCK, num_epochs=1,
    )
    np.asarray(est.fit(Xd, labels).model[:1, :1])  # warm

    def run_once():
        np.asarray(est.fit(Xd, labels).model[:1, :1])

    ms, extra = measure(run_once)

    # per block: RBF block gen 2·N·b·D + residual K_colᵀW 2·N·b·K +
    # (b,b) Cholesky b³/3
    nb = N // BLOCK
    flop = nb * (2 * N * BLOCK * D + 2 * N * BLOCK * K + BLOCK**3 // 3)
    emit("krr_block_solve", ms, "ms", tflops=flop / ms / 1e9,
         extra=solver_extras(ms, flop, extra))

    # cached-kernel mode at 3 epochs (the reference's cacheKernel,
    # KernelMatrix.scala:50): K(:, B) built once + one batched diagonal
    # Cholesky bank, so epochs 2+ cost only residual + triangular
    # solves (~40 ms/epoch device vs ~142 regenerating). Flops credited
    # honestly for the cached schedule: one kernel gen, one chol bank,
    # E× (residual + 2 tri-solve pairs).
    EPOCHS = 3
    est_c = KernelRidgeRegression(
        kernel_generator=GaussianKernelGenerator(gamma=1e-3),
        lam=1e-2, block_size=BLOCK, num_epochs=EPOCHS, cache_kernel=True,
    )
    np.asarray(est_c.fit(Xd, labels).model[:1, :1])  # warm

    def run_cached():
        np.asarray(est_c.fit(Xd, labels).model[:1, :1])

    ms_c, extra_c = measure(run_cached)
    flop_c = nb * (2 * N * BLOCK * D + BLOCK**3 // 3) + EPOCHS * nb * (
        2 * N * BLOCK * K + 4 * BLOCK * BLOCK * K
    )
    extra_c = solver_extras(ms_c, flop_c, extra_c)
    extra_c["epochs"] = EPOCHS
    emit("krr_cached_3epoch_solve", ms_c, "ms", tflops=flop_c / ms_c / 1e9,
         extra=extra_c)


def _fixture_images(n: int, size: int, return_n_base: bool = False):
    """Real ImageNet fixture images (the reference's test tar), resized
    to ``size``² and tiled to ``n`` — SIFT work is data-dependent
    (contrast-threshold zeroing, gradient statistics), so benching on
    uniform noise mismeasures it (VERDICT r2 weak #7). Falls back to
    textured synthetic images if the fixture tar is unavailable."""
    tar = "/root/reference/src/test/resources/images/imagenet/n15075141.tar"
    labels = "/root/reference/src/test/resources/images/imagenet-test-labels"
    base = []
    try:
        from keystone_tpu.loaders.image_loaders import ImageNetLoader

        for item in ImageNetLoader(tar, labels).items():
            img = jnp.asarray(np.asarray(item.image, np.float32))
            base.append(np.asarray(jax.image.resize(
                img, (size, size, 3), method="bilinear"
            )))
    except Exception as e:
        import sys
        print(f"fixture images unavailable ({e}); falling back to "
              "synthetic textures — imagenet rows are NOT comparable "
              "to fixture-image rounds", file=sys.stderr, flush=True)
    if not base:
        rng = np.random.default_rng(0)
        x, y = np.meshgrid(np.arange(size), np.arange(size))
        for freq in (3.0, 5.0, 9.0, 17.0):
            img = 128 + 90 * np.sin(x / freq) * np.cos(y / freq)
            base.append(
                np.repeat(img[:, :, None], 3, 2).astype(np.float32)
                + rng.normal(0, 8, (size, size, 3))
            )
    reps = -(-n // len(base))
    out = np.stack((base * reps)[:n]).astype(np.float32)
    return (out, len(base)) if return_n_base else out


def _build_fv_pipeline(rng, desc_dim, vocab):
    """The ImageNetSiftLcsFV featurization pipeline (shared by the
    featurize-only and end-to-end benches) — the same warm-start chain
    the serving gateway's flagship mode builds, so fit and serve
    measure ONE featurize implementation."""
    from keystone_tpu.serving.featurize import flagship_pipeline

    return flagship_pipeline(rng, desc_dim, vocab)


def bench_imagenet_fv() -> None:
    """North star (featurize): ImageNetSiftLcsFV featurization
    examples/sec/chip — dense multi-scale SIFT + LCS, PCA to 64 dims,
    16-component GMM Fisher Vectors, Hellinger + L2 normalization, at
    256x256 ImageNet-like resolution (reference pipeline:
    ImageNetSiftLcsFV.scala:106-138)."""
    from keystone_tpu.parallel.dataset import Dataset

    SIZE, N = 256, 512
    CHUNK = 128  # bounds the (chunk, 128, ~13k) descriptor intermediates;
    # the chunk loop keeps the dispatch stream pipelined so the one
    # sync amortizes over all N examples (throughput, not latency). Measured against CHUNK=256 on v5e: 872 vs 749 ex/s —
    # the doubled intermediates cost more in HBM pressure than the
    # halved dispatch count saves
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(_fixture_images(N, SIZE))
    # the deployment path: freeze the (estimator-free) pipeline and
    # lower the whole featurize graph into ONE compiled program per
    # chunk shape (FittedPipeline.jit_batch) instead of ~15 per-node
    # dispatches through the graph executor per chunk
    featurize = _build_fv_pipeline(rng, 64, 16).fit().jit_batch()

    def run_once():
        last = None
        for s in range(0, N, CHUNK):
            last = featurize(imgs[s : s + CHUNK])
        np.asarray(last[:1, :1])

    run_once()  # warm
    ms, extra = measure(run_once, reps=3)
    emit("imagenet_sift_lcs_fv_featurize", N / (ms / 1e3),
         "examples/sec/chip", extra=extra)


def bench_imagenet_e2e() -> None:
    """North star (END TO END, the BASELINE.json metric): featurize ->
    BlockWeightedLeastSquaresEstimator(4096) fit -> top-5 prediction,
    examples/sec/chip over the full train pass (reference:
    ImageNetSiftLcsFV.scala:82-148 — featurize + weighted BCD solve +
    TopKClassifier(5))."""
    from keystone_tpu.ops.learning import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators, TopKClassifier
    from keystone_tpu.parallel.dataset import Dataset

    SIZE, N, C = 256, 512, 100
    CHUNK = 128
    rng = np.random.default_rng(0)
    # the tiling in _fixture_images is cyclic, so base_id is the
    # example index mod the ACTUAL tiling period (np.unique would both
    # miscount under byte-identical fixture images and sort ~400 MB of
    # rows); per-example noise makes every image — and its features —
    # unique within its cluster
    base_imgs, n_bases = _fixture_images(N, SIZE, return_n_base=True)
    assert n_bases <= C, (
        f"fixture tar holds {n_bases} base images > indicator width {C}"
        " — raise C or subsample the bases"
    )
    base_id = np.arange(N) % n_bases
    imgs = jnp.asarray(
        base_imgs + rng.normal(0, 3.0, (N, SIZE, SIZE, 3)).astype(np.float32)
    )
    # labels = base-image identity (VERDICT r3 weak #3): a genuinely
    # learnable signal for one BCD pass — clusters are margin-separable
    # in FV space — while the indicator width stays C=100 so the solver
    # does the full flagship-shape work. (Random labels are unlearnable
    # from ~5 examples/class by one pass, and a feature-derived linear
    # teacher collapses to the ~4 feature clusters; both were measured.)
    y = jnp.asarray(base_id.astype(np.int32))
    featurize = _build_fv_pipeline(rng, 64, 16).fit().jit_batch()
    est = BlockWeightedLeastSquaresEstimator(
        block_size=4096, num_iter=1, lam=1e-3, mixture_weight=0.5,
        convergence_check="off",
    )
    top5 = TopKClassifier(5)

    def feature_pass():
        return jnp.concatenate(
            [featurize(imgs[s : s + CHUNK]) for s in range(0, N, CHUNK)],
            axis=0,
        )

    # featurize-health check on the warm pass, outside the timed
    # region: distinct base images must map to well-separated feature
    # clusters (collapsed/constant features fail this long before they
    # fail the accuracy floor)
    F_warm = np.asarray(feature_pass(), np.float32)
    if n_bases > 1:
        cents = np.stack([
            F_warm[base_id == b].mean(0) for b in range(n_bases)
        ])
        within = float(np.mean([
            np.linalg.norm(F_warm[base_id == b] - cents[b], axis=1).mean()
            for b in range(n_bases)
        ]))
        inter = np.linalg.norm(
            cents[:, None, :] - cents[None, :, :], axis=2
        )
        min_inter = float(inter[~np.eye(n_bases, dtype=bool)].min())
        assert min_inter > 2.0 * within, (
            f"feature clusters collapsed: min inter-centroid "
            f"{min_inter:.3f} vs within-cluster spread {within:.3f}"
        )
    # rank-richness: centroid separation alone is blind to rank
    # collapse (separated collinear centroids would pass). Globally the
    # spectrum is DOMINATED by the ~4-cluster structure (global stable
    # rank ≈ 2 on healthy features — measured), so measure richness on
    # the WITHIN-CLUSTER deviations: per-example noise must excite many
    # feature directions (healthy FV: stable rank ≫ 5; a rank-collapsed
    # featurize gives ~1)
    if n_bases > 1:
        Fw = F_warm - cents[base_id]
    else:
        Fw = F_warm - F_warm.mean(0)
    sv = np.linalg.svd(Fw, compute_uv=False)
    stable_rank = float((sv ** 2).sum() / max(sv[0] ** 2, 1e-30))
    assert stable_rank > 5.0, (
        f"within-cluster feature stable rank {stable_rank:.2f} — "
        "featurize output has collapsed to a low-rank subspace"
    )
    state = {}

    def run_once():
        feats = Dataset.from_array(feature_pass(), n=N)
        labels = ClassLabelIndicators(C).apply_batch(Dataset.from_array(y))
        model = est.fit(feats, labels)
        preds = top5.apply_batch(model.apply_batch(feats))
        state["top5"] = np.asarray(preds.padded()[:N])

    run_once()  # warm the fit/apply programs
    ms, extra = measure(run_once, reps=2)
    yh = np.asarray(y)
    top5_err = float(np.mean([
        yh[i] not in state["top5"][i] for i in range(N)
    ]))
    top1_err = float(np.mean(state["top5"][:, 0] != yh))
    # margin-separable clusters: a real error means the pipeline or
    # solver broke, not that the workload is hard
    assert top1_err < 0.05, f"e2e top-1 train error {top1_err}"
    extra.update(top1_err=round(top1_err, 4), top5_err=round(top5_err, 4))
    emit("imagenet_sift_lcs_fv_end_to_end", N / (ms / 1e3),
         "examples/sec/chip", extra=extra)




def bench_imagenet_e2e_hard(mix_lo: float = 0.30,
                            mix_hi: float = 0.50) -> None:
    """HARD variant of the end-to-end row (VERDICT r4 next #7). Two
    deliberate changes vs the easy row, each fixing a way 0.0 error
    could be vacuous:

    * **Held-out evaluation.** With D=8192 ≫ n, ridge interpolates ANY
      training labels — train error is structurally 0 however hard the
      workload (measured: σ=140 pixel noise still gave 0.000 train
      top-1). Error here is measured on a disjoint validation split
      drawn from the same generator.
    * **Cross-class blending, not iid noise.** Fisher Vectors pool
      thousands of descriptors, so iid pixel noise averages out
      (σ∈{30,80,140} all measured 0.000). Each example instead blends
      its base image with a DIFFERENT base at α ~ U(mix_lo, mix_hi):
      approaching α=0.5 the example is genuinely ambiguous, so even a
      perfect featurize carries an irreducible, α-tunable error.

    The row carries its own negative control — the same solver on a
    collapsed featurize (all-zero features, the real bring-up failure
    mode the e2e centroid guard once caught), whose intercept-only
    ranking sits at ~0.8 val top-1. 'The featurize carries signal' is
    the measured gap between the healthy band and that control. With
    ~5 effective classes inside a 100-wide indicator, top-5 is
    trivially near 0 — top-1 is the banded metric; top-5 is reported.
    """
    from keystone_tpu.ops.learning import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators, TopKClassifier
    from keystone_tpu.parallel.dataset import Dataset

    SIZE, C = 256, 100
    N_TRAIN, N_VAL = 512, 256
    N = N_TRAIN + N_VAL
    CHUNK = 128
    rng = np.random.default_rng(1)
    base_imgs, n_bases = _fixture_images(N, SIZE, return_n_base=True)
    base_id = np.arange(N) % n_bases
    partner = (
        base_id + 1 + rng.integers(0, n_bases - 1, N)
    ) % n_bases
    alpha = rng.uniform(mix_lo, mix_hi, N).astype(np.float32)[
        :, None, None, None
    ]
    bases = base_imgs[:n_bases]
    imgs = jnp.asarray(
        (1.0 - alpha) * bases[base_id]
        + alpha * bases[partner]
        + rng.normal(0, 4.0, (N, SIZE, SIZE, 3)).astype(np.float32)
    )
    y = base_id.astype(np.int32)
    featurize = _build_fv_pipeline(rng, 64, 16).fit().jit_batch()
    est = BlockWeightedLeastSquaresEstimator(
        block_size=4096, num_iter=1, lam=1e-3, mixture_weight=0.5,
        convergence_check="off",
    )
    top5 = TopKClassifier(5)
    labels = ClassLabelIndicators(C).apply_batch(
        Dataset.from_array(jnp.asarray(y[:N_TRAIN]))
    )

    def errors(model, F, ys):
        ds = Dataset.from_array(F, n=F.shape[0])
        preds = np.asarray(
            top5.apply_batch(model.apply_batch(ds)).padded()[: F.shape[0]]
        )
        t5 = float(np.mean([ys[i] not in preds[i] for i in range(len(ys))]))
        t1 = float(np.mean(preds[:, 0] != ys))
        return t1, t5

    def fit_and_val_errors(F_all):
        model = est.fit(
            Dataset.from_array(F_all[:N_TRAIN], n=N_TRAIN), labels
        )
        return errors(model, F_all[N_TRAIN:], y[N_TRAIN:])

    def feature_pass():
        return jnp.concatenate(
            [featurize(imgs[s : s + CHUNK]) for s in range(0, N, CHUNK)],
            axis=0,
        )

    state = {}

    def run_once():
        state["errs"] = fit_and_val_errors(feature_pass())

    run_once()  # warm
    ms, m_extra = measure(run_once, reps=2)
    dt = ms / 1e3
    v1, v5 = state["errs"]

    # negative control: collapsed features -> intercept-only ranking
    F_zero = jnp.zeros((N, 2 * 2 * 64 * 16), jnp.float32)
    c1, c5 = fit_and_val_errors(F_zero)

    # calibrated on the fixture images at U(0.30, 0.50) blending (v5e,
    # r5): healthy val top-1 lands meaningfully off 0.0 but far under
    # the collapsed control's ~0.8; a featurize losing its signal
    # drifts toward the control and trips the ceiling
    assert 0.01 <= v1 <= 0.55, (
        f"hard-workload val top-1 {v1:.3f} outside the healthy band "
        f"[0.01, 0.55] — below floor means the blend degenerated to "
        f"separable (raise mix range); above ceiling means the "
        f"featurize lost its signal (control top-1 is {c1:.3f})"
    )
    assert c1 >= 0.7, (
        f"negative control (collapsed features) val top-1 {c1:.3f} "
        "< 0.7 — the control no longer separates broken from healthy"
    )
    assert c1 - v1 >= 0.2, (
        f"healthy ({v1:.3f}) and collapsed ({c1:.3f}) val top-1 are "
        "too close — the row lost its discriminating power"
    )
    m_extra.update(
        val_top1_err=round(v1, 4), val_top5_err=round(v5, 4),
        mix_lo=mix_lo, mix_hi=mix_hi, n_train=N_TRAIN, n_val=N_VAL,
        control_top1_err=round(c1, 4), control_top5_err=round(c5, 4),
    )
    emit("imagenet_sift_lcs_fv_end_to_end_hard", N / dt,
         "examples/sec/chip", extra=m_extra)


IMAGENET_FIXTURE_TAR = (
    "/root/reference/src/test/resources/images/imagenet/n15075141.tar"
)
IMAGENET_FIXTURE_LABELS = (
    "/root/reference/src/test/resources/images/imagenet-test-labels"
)


def _vm_rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def bench_imagenet_stream_input(n_images: int = 100_000) -> None:
    """Out-of-core input pipeline at ImageNet scale (VERDICT r3 missing
    #1): cycle the reference fixture tar to ``n_images`` images through
    the streaming loader (JPEG draft decode at 256², bounded decode
    window) into device batches with a light featurize step, asserting
    FLAT host RSS — an eager load of this stream would be
    n·256²·3·4B ≈ 75 GB at the default 100k."""
    import os

    from keystone_tpu.loaders.streaming import StreamingImageNetLoader
    from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
    from keystone_tpu.parallel.dataset import Dataset

    if not (
        os.path.exists(IMAGENET_FIXTURE_TAR)
        and os.path.exists(IMAGENET_FIXTURE_LABELS)
    ):
        import sys

        print("fixture tar/labels unavailable; skipping stream-input "
              "bench", file=sys.stderr, flush=True)
        return
    SIZE, BATCH = 256, 256
    # count the fixture tar once, then cycle enough times
    probe = StreamingImageNetLoader(
        IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS
    )
    per_cycle = sum(1 for _ in probe._iter_raw())
    if per_cycle == 0:
        import sys

        print("fixture tar has no labeled members; skipping stream-input "
              "bench", file=sys.stderr, flush=True)
        return
    cycles = -(-n_images // per_cycle)
    loader = StreamingImageNetLoader(
        IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS,
        decode_size=SIZE, cycle=cycles, limit=n_images,
        decode_threads=8,
    )
    scaler, gray = PixelScaler(), GrayScaler()

    @jax.jit
    def light_featurize(imgs_u8):
        # scale -> NTSC grayscale -> per-image stats: enough device work
        # to prove the host pipeline feeds the chip without the row
        # re-measuring SIFT (imagenet_sift_lcs_fv_featurize does that)
        g = gray.apply(scaler.apply(imgs_u8.astype(jnp.float32)))
        return jnp.mean(g.reshape(g.shape[0], -1), axis=1)

    seen = 0
    rss0, peak = None, 0.0
    acc = None
    t0 = time.perf_counter()
    for imgs, labs, n_valid in loader.batches(BATCH):
        # device feed = 64² uint8 thumbnails: this row measures the HOST
        # input pipeline (decode throughput + flat RSS), so the upload
        # is kept small enough not to be the stage it times.
        thumb = np.ascontiguousarray(
            imgs[:, ::4, ::4, :]
        ).astype(np.uint8)
        stats = light_featurize(jnp.asarray(thumb))
        acc = stats if acc is None else acc + stats
        seen += n_valid
        if rss0 is None:
            rss0 = _vm_rss_mb()
        elif (seen // BATCH) % 50 == 0:
            peak = max(peak, _vm_rss_mb())
    np.asarray(acc[:1])
    dt = time.perf_counter() - t0
    peak = max(peak, _vm_rss_mb())
    growth = peak - rss0
    assert seen >= n_images, (seen, n_images)
    # The guard: the pipeline must not MATERIALIZE the dataset. Eager
    # load here would be seen·256²·3·4B (~75 GB at 100k). Host-side the
    # pipeline is strictly flat — tests/parallel/test_streaming.py
    # asserts <120 MB growth, and a host-only 100k run oscillates
    # around ~500 MB total RSS. The device client may retain
    # upload-related buffers on top of that, so the assertion here is
    # the order-of-magnitude materialization bound (10% of the eager
    # footprint) and the strict host-side bound in the test suite
    # guards the fine-grained leak classes. The measured growth is
    # reported in the row either way.
    eager_mb = seen * SIZE * SIZE * 3 * 4 / 1e6
    # min(… eager/2) keeps the guard meaningful for small --stream-images
    # runs, where a flat 1 GB floor would exceed the eager footprint
    allowance = max(0.10 * eager_mb, min(1000.0, 0.5 * eager_mb))
    assert growth < allowance, (
        f"streaming input pipeline RSS grew {growth:.0f} MB over "
        f"{seen} images (allowance {allowance:.0f} MB; eager would be "
        f"{eager_mb:.0f} MB) — it is materializing"
    )
    emit("imagenet_stream_input", seen / dt, "imgs/sec",
         extra={"images": seen, "rss_growth_mb": round(growth, 1)})


def bench_imagenet_stream_featurize(n_images: int = 1536) -> None:
    """INTEGRATED host→chip path (VERDICT r4 next #1): the streaming
    loader (native libjpeg draft decode) feeding the FULL SIFT+LCS
    Fisher Vector chain through the SAME fused serving engine the
    gateway runs (``StreamingImageLoader.featurized_batches`` over a
    ``compiled()`` flagship featurize — raw uint8 on the H2D wire, cast
    + featurize in one per-bucket XLA program), with decode, upload,
    and compute overlapped through the async dispatch stream.

    Reports the sustained ex/s plus each stage's standalone rate —
    decode (host, imgs/s and imgs/s/core), upload (H2D of uint8
    chunks), compute (device-resident featurize) — and
    ``overlap_efficiency`` = sustained / min(stage rates): ~1.0 means
    the pipeline loses nothing to serialization. Whichever stage is
    narrow on the host it runs on (upload, decode or compute), the row
    proves overlap against that bound; the assertion tightens to the
    VERDICT criterion — sustained within ~10% of compute-only —
    whenever decode+upload capacity exceeds compute.
    Host RSS stays bounded — the loader never materializes the stream.
    The stage probes are standalone sync-bounded measurements; their
    composition through the async dispatch stream is approximate
    (deeply pipelined transfers can BEAT the standalone upload probe,
    so overlap_efficiency may exceed 1.0 — measured 1.0-1.6 here). The
    assertion is one-sided: sustained must not fall below 0.8x the
    model; exceeding it only means the model is conservative.
    Reference capability: loaders/ImageLoaderUtils.scala:22-47 decodes
    on executors in parallel while the driver schedules compute."""
    import os

    if not (
        os.path.exists(IMAGENET_FIXTURE_TAR)
        and os.path.exists(IMAGENET_FIXTURE_LABELS)
    ):
        import sys

        print("fixture tar/labels unavailable; skipping stream-featurize "
              "bench", file=sys.stderr, flush=True)
        return
    from keystone_tpu.loaders.streaming import StreamingImageNetLoader

    SIZE, CHUNK = 256, 128
    rng = np.random.default_rng(0)
    # the FIT-path featurize rides the serving engine: the frozen
    # flagship chain compiled() into bucketed programs — identical
    # staging, fusion, and h2d accounting to the gateway's
    # device-featurize lane (one featurize implementation, fit & serve)
    engine = _build_fv_pipeline(rng, 64, 16).fit().compiled(
        buckets=(CHUNK,), aot_store=False
    )

    def feed(u8_chunk):
        # uint8 on the wire (4x less H2D), cast + featurize fused in
        # the engine's bucket program
        return engine.apply(u8_chunk)

    def make_loader(limit, **kw):
        probe = StreamingImageNetLoader(
            IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS
        )
        per_cycle = sum(1 for _ in probe._iter_raw())
        return StreamingImageNetLoader(
            IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS,
            decode_size=SIZE, cycle=-(-limit // per_cycle), limit=limit,
            **kw,
        )

    # -- stage rates (each standalone) ----------------------------------
    n_probe = 4 * CHUNK
    t0 = time.perf_counter()
    chunks = [
        u8 for u8, _, _ in make_loader(n_probe).batches(CHUNK, np.uint8)
    ]
    decode_rate = n_probe / (time.perf_counter() - t0)
    cores = os.cpu_count() or 1

    dev = jax.devices()[0]
    up = jax.device_put(chunks[0], dev)
    np.asarray(up[:1, :1, :1, 0])  # warm
    best_up = float("inf")  # transfer jitter is large; best-of-2
    for _ in range(2):
        t0 = time.perf_counter()
        for c in chunks:
            up = jax.device_put(c, dev)
        np.asarray(up[:1, :1, :1, 0])
        best_up = min(best_up, time.perf_counter() - t0)
    upload_rate = n_probe / best_up

    resident = jax.device_put(chunks[0], dev)
    np.asarray(feed(resident)[:1, :1])  # warm compile
    t0 = time.perf_counter()
    out = None
    for _ in range(len(chunks)):
        out = feed(resident)
    np.asarray(out[:1, :1])
    compute_rate = n_probe / (time.perf_counter() - t0)

    # -- integrated sustained run (best-of-2: transfer jitter) ----------
    sustained, growth = 0.0, 0.0
    for _ in range(2):
        seen = 0
        rss0, peak = None, 0.0
        out = None
        t0 = time.perf_counter()
        for out, labs, n_valid in make_loader(n_images).featurized_batches(
            engine, CHUNK
        ):
            # async H2D + async dispatch inside the engine; the next
            # loop iteration decodes while the chip works this chunk
            seen += n_valid
            if rss0 is None:
                rss0 = _vm_rss_mb()
            else:
                peak = max(peak, _vm_rss_mb())
        np.asarray(out[:1, :1])
        dt = time.perf_counter() - t0
        peak = max(peak, _vm_rss_mb())
        assert seen >= n_images, (seen, n_images)
        if seen / dt > sustained:
            sustained = seen / dt
            growth = peak - (rss0 or 0.0)

    bottleneck = min(
        ("decode", decode_rate), ("upload", upload_rate),
        ("compute", compute_rate), key=lambda kv: kv[1],
    )
    # What a perfectly-overlapped pipeline can sustain HERE: compute
    # runs on the chip, but decode and the Python-side upload
    # marshalling run on host cores — with one core they serialize
    # against each other, so the host-side bound is harmonic, not min.
    if cores >= 2:
        host_bound = min(decode_rate, upload_rate)
        floor = 0.8
    else:
        host_bound = 1.0 / (1.0 / decode_rate + 1.0 / upload_rate)
        # single-core hosts: the upload stage drifts between the
        # standalone probe and the 3-minute integrated window, so a
        # tight floor flags host noise, not broken overlap; 0.55 still
        # trips on actual serialization regressions (e.g. a per-batch
        # sync)
        floor = 0.55
    expected = min(compute_rate, host_bound)
    efficiency = sustained / expected
    assert efficiency > floor, (
        f"integrated pipeline runs at {sustained:.0f} ex/s but perfect "
        f"overlap would sustain {expected:.0f} (stages: decode "
        f"{decode_rate:.0f}, upload {upload_rate:.0f}, compute "
        f"{compute_rate:.0f}; {cores} host core(s)) — overlap is "
        f"broken (efficiency {efficiency:.2f} <= {floor})"
    )
    if expected == compute_rate:
        # the VERDICT criterion proper: host feeds the chip
        assert sustained > 0.9 * compute_rate, (
            f"decode+upload capacity exceeds compute yet sustained "
            f"{sustained:.0f} < 90% of compute-only {compute_rate:.0f}"
        )
    m = engine.metrics
    emit("imagenet_stream_featurize", sustained, "examples/sec/chip",
         extra={
             "images": seen,
             "decode_rate": round(decode_rate, 1),
             "decode_rate_per_core": round(decode_rate / cores, 1),
             "host_cores": cores,
             "upload_rate": round(upload_rate, 1),
             "compute_rate": round(compute_rate, 1),
             "bottleneck": bottleneck[0],
             "expected_rate": round(expected, 1),
             "overlap_efficiency": round(efficiency, 3),
             "rss_growth_mb": round(growth, 1),
             # the fused engine's own wire accounting: raw uint8
             # pixels per image staged, vs the 4x f32 alternative
             "h2d_bytes_per_image": round(
                 m.h2d_bytes.total / m.examples.total, 1
             ),
             "h2d_reduction_vs_f32": 4.0,
             "engine_compiles": m.compiles.total,
         })


def bench_stream_decode_scaling(n_images: int = 1024) -> None:
    """Decode-pool scaling curve (VERDICT r4 next #6): host-only decode
    imgs/s at decode_processes ∈ {0 (thread pool), 2, 4, ...} up to the
    core count. On a 1-core host the process rows are SKIPPED (emitted
    with skipped=true) — spawn+IPC overhead measures scheduling noise,
    not scaling — so the 'scales with cores' claim becomes a measured
    curve the moment multi-core hardware runs this bench. Thread/process
    output parity is pinned by tests/parallel/test_streaming.py."""
    import os

    if not (
        os.path.exists(IMAGENET_FIXTURE_TAR)
        and os.path.exists(IMAGENET_FIXTURE_LABELS)
    ):
        import sys

        print("fixture tar/labels unavailable; skipping decode-scaling "
              "bench", file=sys.stderr, flush=True)
        return
    from keystone_tpu.loaders.streaming import StreamingImageNetLoader

    SIZE = 256
    probe = StreamingImageNetLoader(
        IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS
    )
    per_cycle = sum(1 for _ in probe._iter_raw())
    cores = os.cpu_count() or 1
    # {0, 2, 4} always appear (skipped rows included, so the curve's
    # shape is visible in every BENCH artifact); larger pools only
    # where the host could actually exercise them
    pools = [0, 2, 4] + [p for p in (8, 16) if p <= cores]
    for procs in pools:
        name = f"stream_decode_procs_{procs}"
        if procs > 0 and (cores < 2 or procs > cores):
            emit(name, None, "imgs/sec", extra={
                "skipped": True,
                "reason": f"host has {cores} core(s); a {procs}-process "
                "decode pool is unmeasurable here",
            })
            continue
        loader = StreamingImageNetLoader(
            IMAGENET_FIXTURE_TAR, IMAGENET_FIXTURE_LABELS,
            decode_size=SIZE, cycle=-(-n_images // per_cycle),
            limit=n_images, decode_processes=procs,
        )
        t0 = time.perf_counter()
        seen = sum(nv for _, _, nv in loader.batches(128, np.uint8))
        dt = time.perf_counter() - t0
        assert seen >= n_images
        emit(name, seen / dt, "imgs/sec",
             extra={"host_cores": cores,
                    "per_core": round(seen / dt / max(procs, 1), 1)})


def _gen_host_blocks(n, d, block, k, seed=0):
    """Host-RAM bf16 feature blocks + labels planted on block 0 (the
    teacher lives entirely in the first block, so a fit's W must
    concentrate there — a correctness signal that needs no full-matrix
    cross-check at scales where none is computable)."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    blocks = []
    for s in range(0, d, block):
        w = min(block, d - s)
        blocks.append(
            rng.standard_normal((n, w), dtype=np.float32)
            .astype(ml_dtypes.bfloat16)
        )
    W1 = rng.standard_normal((blocks[0].shape[1], k)).astype(np.float32)
    W1 *= 0.1
    # chunked host matmul: Y depends only on block 0
    Y = np.empty((n, k), np.float32)
    step = 65536
    b0 = blocks[0]
    for r in range(0, n, step):
        Y[r : r + step] = b0[r : r + step].astype(np.float32) @ W1
    Y += 0.05 * rng.standard_normal((n, k), dtype=np.float32)
    return blocks, Y, W1


def bench_hostblocks_overlap() -> None:
    """Out-of-aggregate-HBM training (VERDICT r4 next #2): BlockLS on a
    host-RAM-resident feature matrix (Dataset.from_host_blocks), each
    slab double-buffered onto the chip per pass. Reports the fit wall
    time against its two standalone components — transfer-only (all
    slabs device_put + sync) and compute-only (the same fit with X
    device-resident) — and overlap_efficiency =
    max(transfer, compute) / wall: 1.0 means the smaller component is
    fully hidden under the larger. Where transfer dominates the row
    proves compute hides under transfer; where the fit is compute-bound
    it proves the reverse. Reference capability:
    BlockLinearMapper.scala:50-73 (cluster-RAM feature cache),
    AutoCacheRule.scala:559-602 (memory-budgeted caching)."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.parallel.dataset import Dataset

    N, D, K, BLOCK = 131_072, 2048, 128, 1024
    blocks, Y, _ = _gen_host_blocks(N, D, BLOCK, K)
    gb = sum(b.nbytes for b in blocks) / 2**30
    Yd = Dataset.from_array(jnp.asarray(Y))
    est = BlockLeastSquaresEstimator(block_size=BLOCK, num_iter=1, lam=0.1)

    host_ds = Dataset.from_host_blocks(blocks)
    np.asarray(est.fit(host_ds, Yd).W[:1, :1])  # warm compiles

    # transfer-only: every slab H2D, one sync
    t0 = time.perf_counter()
    last = None
    for b in blocks:
        last = jax.device_put(b)
    np.asarray(last[:1, :1])
    t_transfer = time.perf_counter() - t0

    # compute-only: same fit, X already device-resident
    dev_ds = Dataset.from_array(
        jnp.concatenate([jnp.asarray(b) for b in blocks], axis=1)
    )
    np.asarray(est.fit(dev_ds, Yd).W[:1, :1])  # warm
    t0 = time.perf_counter()
    np.asarray(est.fit(dev_ds, Yd).W[:1, :1])
    t_compute = time.perf_counter() - t0

    t0 = time.perf_counter()
    model = est.fit(host_ds, Yd)
    np.asarray(model.W[:1, :1])
    wall = time.perf_counter() - t0

    efficiency = max(t_transfer, t_compute) / wall
    assert efficiency > 0.7, (
        f"host-blocks fit took {wall:.1f}s but its larger standalone "
        f"component is only {max(t_transfer, t_compute):.1f}s (transfer "
        f"{t_transfer:.1f}, compute {t_compute:.1f}) — H2D/compute "
        f"overlap is broken"
    )
    emit("hostblocks_block_ls_solve", wall * 1e3, "ms", extra={
        "features_gb": round(gb, 2),
        "transfer_only_s": round(t_transfer, 2),
        "compute_only_s": round(t_compute, 2),
        "overlap_efficiency": round(efficiency, 3),
    })


def bench_hostblocks_xl(hbm_gb: float = 16.0) -> None:
    """The ≥2x-HBM proof (opt-in: ``--hostblocks-xl``): fit a feature
    matrix TWICE the chip's HBM from host RAM on the single chip —
    1M x 16384 bf16 = 32 GiB vs v5e-lite 16 GiB — streaming each 2 GiB
    slab through the double-buffered BCD pass. The planted teacher
    lives in block 0, so the learned W must concentrate there: a
    correctness check that costs O(D*K) host math instead of another
    full pass. Not part of the default bench (a 32 GiB upload per
    pass); run once per round and recorded in PERF. Small-scale equivalence with the in-HBM fit is
    pinned by tests/parallel/test_host_blocks.py."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.parallel.dataset import Dataset

    N, D, K, BLOCK = 1_048_576, 16_384, 147, 1024
    t0 = time.perf_counter()
    blocks, Y, W1 = _gen_host_blocks(N, D, BLOCK, K)
    gen_s = time.perf_counter() - t0
    gb = sum(b.nbytes for b in blocks) / 2**30
    hbm_multiple = gb / hbm_gb
    assert hbm_multiple >= 2.0, (gb, hbm_gb)
    print(json.dumps({
        "note": "hostblocks_xl generated",
        "features_gib": round(gb, 1),
        "hbm_multiple": round(hbm_multiple, 2),
        "gen_s": round(gen_s, 1),
    }), flush=True)

    est = BlockLeastSquaresEstimator(block_size=BLOCK, num_iter=1, lam=1.0)
    t0 = time.perf_counter()
    model = est.fit(
        Dataset.from_host_blocks(blocks),
        Dataset.from_array(jnp.asarray(Y)),
    )
    W = np.asarray(model.W)
    wall = time.perf_counter() - t0

    assert np.all(np.isfinite(W)), "non-finite model from XL fit"
    w0 = W[: blocks[0].shape[1]]
    cos = float(
        np.sum(w0 * W1)
        / (np.linalg.norm(w0) * np.linalg.norm(W1) + 1e-30)
    )
    off_ratio = float(
        np.linalg.norm(W[blocks[0].shape[1]:])
        / (np.linalg.norm(w0) + 1e-30)
    )
    assert cos > 0.9, f"teacher block not recovered: cos={cos:.3f}"
    assert off_ratio < 0.5, (
        f"weight mass leaked off the teacher block: {off_ratio:.3f}"
    )
    emit("hostblocks_xl_2x_hbm_solve", wall * 1e3, "ms", extra={
        "features_gib": round(gb, 1),
        "hbm_multiple": round(hbm_multiple, 2),
        "effective_h2d_mb_s": round(gb * 1024 / wall, 1),
        "teacher_cos": round(cos, 4),
        "off_block_ratio": round(off_ratio, 4),
    })


def bench_imagenet_real(data_dir: str, labels_path: str,
                        val_dir: str = None, desc_dim: int = 64,
                        vocab: int = 16, num_classes: int = 1000,
                        size: int = 256, batch: int = 128) -> None:
    """REAL-DATA parity mode (VERDICT r3 weak #3): when an ImageNet tar
    directory is mounted, stream it through the full SIFT+LCS Fisher
    Vector pipeline, fit the 4096-block weighted BCD solver, and report
    reference-comparable top-1/top-5 error (train set, plus val when
    ``val_dir`` is given). See README "Real-data parity runbook".

    Run: python bench.py --imagenet-data DIR --imagenet-labels FILE
         [--imagenet-val DIR]

    ``size``/``batch`` exist so the suite can drive this exact code
    path on the 5-image reference fixture tar at CPU-friendly shapes
    (tests/pipelines/test_real_parity_mode.py) — the plumbing is
    exercised every run, so it works the day real ImageNet is mounted.
    """
    from keystone_tpu.loaders.streaming import StreamingImageNetLoader
    from keystone_tpu.ops.learning import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops.util.nodes import ClassLabelIndicators, TopKClassifier
    from keystone_tpu.parallel.dataset import Dataset

    SIZE, BATCH = size, batch
    rng = np.random.default_rng(0)
    # fixed-shape batches -> the whole featurize graph as ONE compiled
    # program (same fast path as the synthetic FV benches)
    featurize = _build_fv_pipeline(rng, desc_dim, vocab).fit().jit_batch()

    def featurize_stream(directory):
        loader = StreamingImageNetLoader(
            directory, labels_path, decode_size=SIZE, decode_threads=8,
        )
        feats, ys = [], []
        for imgs, labs, n_valid in loader.batches(BATCH):
            out = featurize(jnp.asarray(imgs))
            feats.append(out[:n_valid].astype(jnp.bfloat16))
            ys.extend(labs[:n_valid])
        return (
            jnp.concatenate(feats, axis=0),
            jnp.asarray(np.asarray(ys, np.int32)),
        )

    t0 = time.perf_counter()
    X, y = featurize_stream(data_dir)
    n = X.shape[0]
    labels = ClassLabelIndicators(num_classes).apply_batch(
        Dataset.from_array(y)
    )
    est = BlockWeightedLeastSquaresEstimator(
        block_size=4096, num_iter=1, lam=1e-3, mixture_weight=0.5,
        convergence_check="off",
    )
    model = est.fit(Dataset.from_array(X, n=n), labels)
    top5 = TopKClassifier(5)

    def errors(Xs, ys):
        preds = np.asarray(
            top5.apply_batch(
                model.apply_batch(Dataset.from_array(Xs, n=Xs.shape[0]))
            ).padded()[: Xs.shape[0]]
        )
        yh = np.asarray(ys)
        t5 = float(np.mean([yh[i] not in preds[i] for i in range(len(yh))]))
        t1 = float(np.mean(preds[:, 0] != yh))
        return t1, t5

    t1, t5 = errors(X, y)
    dt = time.perf_counter() - t0
    extra = {"train_top1_err": round(t1, 4), "train_top5_err": round(t5, 4),
             "n_train": int(n)}
    if val_dir:
        Xv, yv = featurize_stream(val_dir)
        v1, v5 = errors(Xv, yv)
        extra.update(val_top1_err=round(v1, 4), val_top5_err=round(v5, 4),
                     n_val=int(Xv.shape[0]))
    emit("imagenet_real_end_to_end", n / dt, "examples/sec/chip",
         extra=extra)


def bench_serving() -> None:
    """Serving fast path (serving/engine.py + batching.py) and request
    plane (gateway/): cold-vs-warm dispatch latency on one shape,
    bucketed throughput across every batch size with a compile-count
    ceiling, micro-batched p99, gateway-plane p99 under the same load
    (`serving_gateway_p99`), and the forced live-engine-swap blip with
    zero failures asserted (`serving_swap_blip`) — vs_baseline null
    (the reference published no serving numbers; the wiring exists so
    future rounds ratio against these rows)."""
    from keystone_tpu.serving.bench import run_serving_benches

    run_serving_benches(emit)


def write_markdown(path: str) -> None:
    """Render every emitted row as the README performance table — the
    table is GENERATED from bench output, never hand-edited (VERDICT r3
    weak #4)."""
    lines = [
        "| metric | value | unit | TFLOP/s | device ms | device TFLOP/s"
        " | vs baseline | spread (ms) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in _ROWS:
        if r.get("unit") == "error":
            lines.append(
                f"| {r['metric']} | FAILED | — | — | — | — | — | — |"
            )
            continue
        if r.get("skipped"):
            lines.append(
                f"| {r['metric']} | skipped | — | — | — | — | — | — |"
            )
            continue
        lines.append(
            "| {m} | {v:,.2f} | {u} | {tf} | {dms} | {dtf} | {vs} | {sp} |"
            .format(
                m=r["metric"], v=r["value"], u=r["unit"],
                tf=r.get("tflops", "—") or "—",
                dms=r.get("device_ms", "—"),
                dtf=r.get("tflops_device", "—"),
                vs=r.get("vs_baseline") or "—",
                sp=r.get("spread_ms", "—"),
            )
        )
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {path}", flush=True)


def main() -> None:
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--markdown", metavar="PATH",
                    help="also write the rows as a markdown table")
    ap.add_argument("--only", metavar="SUBSTR",
                    help="run only benches whose name contains SUBSTR")
    ap.add_argument("--stream-images", type=int, default=100_000,
                    help="image count for the streaming input row")
    ap.add_argument("--hostblocks-xl", action="store_true",
                    help="run ONLY the 2x-HBM host-blocks fit (slow: "
                    "32 GiB H2D; see bench_hostblocks_xl)")
    ap.add_argument("--amazon-16384", action="store_true",
                    help="run ONLY the Amazon 16384-feature exact "
                    "solve (slow: ~3.5e16-FLOP Gram; recorded in PERF)")
    ap.add_argument("--imagenet-data", metavar="DIR",
                    help="real ImageNet train tar dir -> parity mode")
    ap.add_argument("--imagenet-labels", metavar="FILE",
                    help="WNID->class map for --imagenet-data")
    ap.add_argument("--imagenet-val", metavar="DIR",
                    help="validation tar dir for parity mode")
    ap.add_argument("--desc-dim", type=int, default=64,
                    help="PCA descriptor dim for parity mode")
    ap.add_argument("--vocab", type=int, default=16,
                    help="GMM vocab size for parity mode")
    ap.add_argument("--num-classes", type=int, default=1000,
                    help="class count for parity mode")
    args = ap.parse_args()

    # persistent XLA executable cache: reruns (and the driver's
    # end-of-round run) skip the compiles
    from keystone_tpu.parallel.runtime import setup_compilation_cache

    setup_compilation_cache(min_compile_time_secs=1.0)

    if args.hostblocks_xl:
        bench_hostblocks_xl()
        if args.markdown:
            write_markdown(args.markdown)
        return

    if args.amazon_16384:
        bench_amazon_16384()
        if args.markdown:
            write_markdown(args.markdown)
        return

    if args.imagenet_data:
        if not args.imagenet_labels:
            ap.error("--imagenet-data requires --imagenet-labels")
        bench_imagenet_real(
            args.imagenet_data, args.imagenet_labels, args.imagenet_val,
            desc_dim=args.desc_dim, vocab=args.vocab,
            num_classes=args.num_classes,
        )
        if args.markdown:
            write_markdown(args.markdown)
        return

    def bench_stream_input():
        bench_imagenet_stream_input(args.stream_images)

    bench_stream_input.__name__ = "bench_imagenet_stream_input"

    benches = [
        bench_timit,
        bench_timit_lbfgs,
        bench_amazon,
        bench_mnist,
        bench_cifar,
        bench_newsgroups,
        bench_weighted_ls,
        bench_krr,
        bench_imagenet_fv,
        bench_imagenet_e2e,
        bench_imagenet_e2e_hard,
        bench_stream_input,
        bench_imagenet_stream_featurize,
        bench_stream_decode_scaling,
        bench_hostblocks_overlap,
        bench_serving,
    ]
    benches = [
        b for b in benches if not args.only or args.only in b.__name__
    ]
    failed = []
    for b in benches:
        try:
            b()
        except Exception as e:
            print(f"{b.__name__} failed: {e}", file=sys.stderr, flush=True)
            # explicit failure row: a broken bench must be
            # distinguishable from a not-run bench in the round's
            # BENCH JSON (ADVICE r3); the remaining benches still run
            emit(b.__name__, None, "error", extra={"error": str(e)[:300]})
            failed.append(b.__name__)
    if args.markdown:
        write_markdown(args.markdown)
    if failed:
        raise SystemExit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
