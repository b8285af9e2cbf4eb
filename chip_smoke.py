#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the flagship ``ImageNetSiftLcsFV`` application once, fit then
serve, at the application's own widths (256x256x3 uint8 images, SIFT +
LCS -> PCA 64 -> 16-word Fisher vectors = 4096 features, the
4096-wide weighted block solver, 1000 classes, top-5) through the entry
points a user calls, in ONE process that holds the chip for every phase:

  device   fail unless jax reports a TPU whose peaks and HBM limit the
           repo knows;
  kernels  lower each Pallas kernel through Mosaic at the shapes this
           configuration produces, under the vmap the extractors use,
           and compare with its own interpret=True result;
  chunks   the chunk programs of a shape group at VOC's image size, at
           the planner's chunk, against the same rows four at a time;
  fit      write a seeded synthetic ImageNet-layout data set (one tar of
           JPEGs per synset + a WNID->class file), build native/*.so
           from source, then run the application the way bin/run-pipeline
           does: runtime.initialize() -> ImageNetLoader -> run();
  serve    the fitted predictor behind Gateway -> GatewayServer on an
           ephemeral port, raw uint8 on the wire, two buckets, pipelined
           lanes, donation on; every HTTP response is checked against the
           fit path's top-5 for the same image.

Only the image count is cut (see N_TRAIN). Weights come from SEED. Every
line names the device; the last line of stdout is one JSON object.
Exits non-zero, printing no result, at the first failing phase — in
particular when jax finds no TPU. Phase seconds are set-up facts of one
cold or warm run, not speeds.

    python3 chip_smoke.py          # on the chip: chiprun -- python3 chip_smoke.py
    python3 chip_smoke.py chunks   # the device's phase and the named ones
"""

from __future__ import annotations

import io
import json
import logging
import logging.handlers
import os
import shutil
import sys
import tarfile
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
if not os.path.isdir(os.path.join(ROOT, "keystone_tpu")):
    print(
        "chip_smoke.py drives the keystone_tpu checkout it ships in; "
        f"there is no keystone_tpu/ beside {__file__}",
        file=sys.stderr,
    )
    raise SystemExit(2)
sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 0
IMG = 256
NUM_CLASSES = 1000
# run() keeps every node's output for every image on the device until
# it returns (~20 MB per 256x256 image, ~27 MB at the Hellinger node's
# peak), so one 16 GB chip holds about 500 images through it. 384 + 96
# peaked at 13.3 GB cold and 15.9 GB with a warm compile cache (the
# host runs further ahead of the device), too near the 16.9 GB limit:
# two dispatch chunks of 128 train; 96 held out, the optimizer's
# sample size, so its programs are reused. PERF.md has the bytes.
N_TRAIN = 256
N_TEST = 96
# requests: N_SERVE single-image POSTs, then N_MULTI POSTs of MULTI
# images each — instances of one POST are admitted back to back and
# spread over the lanes least-loaded first, so each lane sees
# MULTI / LANES of them in one window and dispatches its larger bucket
# whatever the timing
N_SERVE = 64
N_MULTI = 2
MULTI = 8
BUCKETS = (2, 8)
LANES = 2
CLIENTS = 8
KERNEL_BATCH = 4
TOL = 1e-4  # the extractors' parity tolerance (tests/ops)

_tag = "device pending"
_log_buffer = logging.handlers.MemoryHandler(
    capacity=10_000, flushLevel=logging.CRITICAL + 1
)


def say(msg: str) -> None:
    print(f"[chip_smoke {_tag}] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


class CompileCounter:
    """XLA compile requests and persistent-cache hits, read from
    jax.monitoring; programs actually compiled = requests - hits."""

    def __init__(self) -> None:
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.requests += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    @property
    def compiled(self) -> int:
        with self._lock:
            return self.requests - self.hits


COUNTER = CompileCounter()


def run_phase(name: str, fn, state: dict) -> None:
    """One phase: one summary line with its seconds and compile count."""
    c0, h0 = COUNTER.compiled, COUNTER.hits
    t0 = time.perf_counter()
    detail = fn(state)
    say(
        f"phase={name} ok secs={time.perf_counter() - t0:.1f} "
        f"compiles={COUNTER.compiled - c0} cache_hits={COUNTER.hits - h0} "
        f"{detail}"
    )


def memory_report() -> str:
    import jax

    from keystone_tpu.observability.device import device_memory_stats

    parts = []
    for d in jax.devices():
        st = device_memory_stats(d) or {}
        parts.append(
            f"dev{d.id}:peak_bytes_in_use={st.get('peak_bytes_in_use')}"
            f",peak_bytes_reserved={st.get('peak_bytes_reserved')}"
        )
    return " ".join(parts)


def where(a) -> str:
    """``.sharding`` of an array, short: its type, its partition spec
    when it has one, and its device ids."""
    spec = getattr(a.sharding, "spec", None)
    return (
        f"{type(a.sharding).__name__}"
        f"{'' if spec is None else tuple(spec)}"
        f"{sorted(d.id for d in a.devices())}"
    ).replace(" ", "")


# -- device ------------------------------------------------------------------


def phase_device(state: dict) -> str:
    global _tag
    import jax

    from keystone_tpu.observability.device import (
        device_memory_stats,
        peaks_for,
    )
    from keystone_tpu.parallel import runtime

    # the order keystone_tpu.__main__ uses: join (or decline) the
    # multi-host runtime before the backend exists
    decision = runtime.initialize()
    devs = jax.devices()
    d0 = devs[0]
    _tag = f'{d0.platform} "{d0.device_kind}" x{len(devs)}'
    # early INFO lines (initialize's decision) now have a device to name
    stream = logging.StreamHandler(sys.stdout)
    stream.setFormatter(
        logging.Formatter(f"[chip_smoke {_tag}] %(name)s: %(message)s")
    )
    _log_buffer.setTarget(stream)
    _log_buffer.flush()
    root = logging.getLogger("keystone_tpu")
    root.removeHandler(_log_buffer)
    root.addHandler(stream)
    state["device"] = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devs),
    }
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"platform is {d0.platform!r}, not 'tpu': jax found no "
            "accelerator (this smoke does not fall back to the CPU)"
        )
    peaks = peaks_for(d0.device_kind)
    if None in peaks:
        raise SmokeFailure(
            f"observability.device.peaks_for({d0.device_kind!r}) = "
            f"{peaks}: a device the peaks table does not know"
        )
    stats = device_memory_stats(d0) or {}
    if "bytes_limit" not in stats:
        raise SmokeFailure(
            f"memory_stats() of {d0.device_kind!r} has no bytes_limit: "
            f"{stats}"
        )
    cache = runtime.setup_compilation_cache()
    return (
        f"initialize={decision} peak_flops={peaks[0]:.3g} "
        f"peak_membw={peaks[1]:.3g} bytes_limit={stats['bytes_limit']} "
        f"compile_cache={cache}"
    )


# -- kernels -----------------------------------------------------------------


def _mosaic_vs_interpret(name: str, make_fn, args) -> str:
    """Lower ``make_fn(interpret=None)`` (the backend's own choice —
    Mosaic on a TPU), require a Mosaic custom call in it, run it, and
    compare with ``make_fn(interpret=True)`` on the same inputs."""
    import jax
    import numpy as np

    lowered = jax.jit(make_fn(None)).lower(*args)
    if "tpu_custom_call" not in lowered.as_text():
        raise SmokeFailure(f"{name}: lowering holds no Mosaic custom call")
    got = jax.tree_util.tree_leaves(lowered.compile()(*args))
    want = jax.tree_util.tree_leaves(jax.jit(make_fn(True))(*args))
    worst = 0.0
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if not np.isfinite(g).all():
            raise SmokeFailure(f"{name}: non-finite output")
        np.testing.assert_allclose(
            g, w, rtol=TOL, atol=TOL, err_msg=f"{name} vs interpret"
        )
        worst = max(worst, float(np.max(np.abs(g - w))))
    return f"{name}:maxdiff={worst:.2g}"


def phase_kernels(state: dict) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.ops.images import fv_pallas
    from keystone_tpu.ops.images import pallas_kernels as pk
    from keystone_tpu.ops.images.lcs import LCSExtractor, _lcs_sampling_matrix
    from keystone_tpu.ops.images.sift import SIFTExtractor, _sampling_matrix
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
    )

    if pk.auto_interpret(None):
        raise SmokeFailure("auto_interpret() chose the interpreter on a TPU")
    conf = ImageNetSiftLcsFVConfig()
    rng = np.random.default_rng(SEED)
    B = KERNEL_BATCH
    results = []

    # sift_bin_sample at the four scales SIFTExtractor.apply walks
    sift = SIFTExtractor(scale_step=conf.sift_scale_step)
    mag = jnp.asarray(rng.random((B, IMG, IMG)).astype(np.float32))
    ori = jnp.asarray((rng.random((B, IMG, IMG)) * 8).astype(np.float32))
    n_desc = 0
    for scale in range(sift.num_scales):
        bin_size = sift.bin + 2 * scale
        step = sift.step + scale * sift.scale_step
        bound = (1 + 2 * sift.num_scales) - 3 * scale
        nf = (IMG - 1 - bound - 3 * bin_size) // step + 1
        n_desc += nf * nf
        a = _sampling_matrix(IMG, nf, bin_size, step, bound)
        ayt, ax = jnp.asarray(a.T.copy()), jnp.asarray(a)
        results.append(_mosaic_vs_interpret(
            f"sift_bin_sample[s{scale},{4 * nf}x{4 * nf}]",
            lambda interp, ayt=ayt, ax=ax: jax.vmap(
                lambda m, o: pk.sift_bin_sample(
                    m, o, ayt, ax, interpret=interp
                )
            ),
            (mag, ori),
        ))
    # the shapes above are re-derived here; hold them to the extractor
    sift_cols = jax.eval_shape(
        sift.apply, jax.ShapeDtypeStruct((IMG, IMG, 1), jnp.float32)
    ).shape[1]
    if sift_cols != n_desc:
        raise SmokeFailure(
            f"kernel-phase SIFT geometry ({n_desc} descriptors) is not "
            f"the extractor's ({sift_cols})"
        )

    # plane_sandwich at LCSExtractor's shapes: image and image^2 planes
    s = conf.lcs_patch
    keys = np.arange(conf.lcs_border, IMG - conf.lcs_border, conf.lcs_stride)
    offs = np.arange(-2 * s + s // 2 - 1, s + s // 2, s)
    a = _lcs_sampling_matrix(IMG, keys, offs, s)
    at, b = jnp.asarray(a.T.copy()), jnp.asarray(a)
    planes = jnp.asarray(
        (rng.random((B, 6, IMG, IMG)) * 255.0).astype(np.float32)
    )
    results.append(_mosaic_vs_interpret(
        f"plane_sandwich[{at.shape[0]}x{b.shape[1]}]",
        lambda interp: jax.vmap(
            lambda p: pk.plane_sandwich(p, at, b, interpret=interp)
        ),
        (planes,),
    ))
    lcs_cols = jax.eval_shape(
        LCSExtractor(conf.lcs_stride, conf.lcs_border, conf.lcs_patch).apply,
        jax.ShapeDtypeStruct((IMG, IMG, 3), jnp.float32),
    ).shape[1]
    if lcs_cols != len(keys) ** 2:
        raise SmokeFailure(
            f"kernel-phase LCS geometry ({len(keys) ** 2} keypoints) is "
            f"not the extractor's ({lcs_cols})"
        )

    # fisher_vector_stats_pallas at the smallest vocabulary the
    # estimator's switch sends to it (k >= 32), over both branches'
    # descriptor counts
    d, k = conf.desc_dim, 32
    means = jnp.asarray(rng.standard_normal((d, k)).astype(np.float32))
    variances = jnp.asarray((0.5 + rng.random((d, k))).astype(np.float32))
    weights = jnp.full((k,), 1.0 / k, jnp.float32)
    for m in (sift_cols, lcs_cols):
        x = jnp.asarray(rng.standard_normal((B, d, m)).astype(np.float32))
        results.append(_mosaic_vs_interpret(
            f"fisher_vector_stats_pallas[d{d},k{k},m{m}]",
            lambda interp: jax.vmap(
                lambda xi: fv_pallas.fisher_vector_stats_pallas(
                    xi, means, variances, weights, 1e-4, interpret=interp
                )
            ),
            (x,),
        ))
    return f"mosaic==interpret within {TOL:g}: " + " ".join(results)


# -- fit ---------------------------------------------------------------------


def _texture(c: int, rng) -> "np.ndarray":
    """One 256x256x3 uint8 image of class ``c``: a class-dependent
    oriented texture and tint plus noise, spread over 1000 classes."""
    import numpy as np

    x, y = np.meshgrid(np.arange(IMG), np.arange(IMG))
    theta = (c * 0.61803398875) % np.pi
    u = x * np.cos(theta) + y * np.sin(theta)
    v = y * np.cos(theta) - x * np.sin(theta)
    fx = 2.0 + 0.45 * (c % 40)
    fy = 2.5 + 0.9 * (c // 40)
    base = np.sin(u / fx) * np.cos(v / fy)
    tint = 0.7 + 0.3 * np.sin(c * np.array([0.37, 0.59, 0.83]))
    img = 128 + 90 * base[:, :, None] * tint + rng.normal(0, 8, (IMG, IMG, 3))
    return img.clip(0, 255).astype(np.uint8)


def write_dataset(out_dir: str, n_train: int, n_test: int) -> tuple:
    """ImageNet layout from SEED: ``train/<wnid>.tar`` and
    ``test/<wnid>.tar`` of 256x256 JPEGs, ``labels.txt`` mapping every
    one of the 1000 WNIDs to its class. Train images cover ``n_train``
    classes once each (a seeded permutation); held-out images are new
    draws of the first ``n_test`` of those classes."""
    import numpy as np
    from PIL import Image

    shutil.rmtree(out_dir, ignore_errors=True)
    rng = np.random.default_rng(SEED)
    wnids = [f"n{10_000_000 + c:08d}" for c in range(NUM_CLASSES)]
    labels = os.path.join(out_dir, "labels.txt")
    os.makedirs(out_dir)
    with open(labels, "w") as f:
        f.writelines(f"{w} {c}\n" for c, w in enumerate(wnids))
    classes = rng.permutation(NUM_CLASSES)
    for split, chosen in (
        ("train", classes[:n_train]), ("test", classes[:n_test])
    ):
        os.makedirs(os.path.join(out_dir, split))
        for c in chosen:
            buf = io.BytesIO()
            Image.fromarray(_texture(int(c), rng)).save(
                buf, format="JPEG", quality=90
            )
            path = os.path.join(out_dir, split, f"{wnids[c]}.tar")
            with tarfile.open(path, "w") as tf:
                info = tarfile.TarInfo(f"{wnids[c]}_{split}0.JPEG")
                info.size = buf.tell()
                buf.seek(0)
                tf.addfile(info, buf)
    return (
        os.path.join(out_dir, "train"), os.path.join(out_dir, "test"), labels
    )


def fit_path_top5(fitted, images) -> "np.ndarray":
    """Top-5 through the fitted pipeline's per-node batch path — the
    path run() evaluates held-out images on — one dispatch chunk of
    images at a time: every node's output for the chunk stays on the
    device until the chunk is done."""
    import numpy as np

    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.workflow.api import BUCKET_CHUNK

    return np.concatenate([
        np.asarray(
            fitted.apply(
                Dataset.from_items(images[s : s + BUCKET_CHUNK])
            ).array()
        )
        for s in range(0, len(images), BUCKET_CHUNK)
    ])


def top5_error(top5, labels) -> float:
    import numpy as np

    return 1.0 - float(np.mean([a in p for a, p in zip(labels, top5)]))


class PlacementProbe:
    """Records where run()'s arrays land by wrapping, for the length of
    a ``with`` block, the two methods they pass through: the bucketed
    featurizers' image batches and the solver's Gram operands (the
    4096-wide feature matrix and the indicator labels)."""

    def __init__(self) -> None:
        self.seen: dict = {}

    def __enter__(self) -> "PlacementProbe":
        import jax

        from keystone_tpu.ops.learning.weighted_ls import (
            BlockWeightedLeastSquaresEstimator as Solver,
        )
        from keystone_tpu.workflow.api import Transformer

        self._patched = (Transformer, Solver)
        self._saved = (Transformer._bucketed_batch, Solver.fit)
        bucketed, fit = self._saved
        seen = self.seen

        def probed_bucketed(node, ds):
            out = bucketed(node, ds)
            leaf = jax.tree_util.tree_leaves(out.first())[0]
            seen.setdefault(
                f"{type(node).__name__}.batch_item{tuple(leaf.shape)}",
                where(leaf),
            )
            return out

        def probed_fit(est, data, labels):
            x, y = data.padded(), labels.padded()
            seen[f"solver.X{tuple(x.shape)}"] = where(x)
            seen[f"solver.Y{tuple(y.shape)}"] = where(y)
            return fit(est, data, labels)

        Transformer._bucketed_batch = probed_bucketed
        Solver.fit = probed_fit
        return self

    def __exit__(self, *exc) -> None:
        transformer, solver = self._patched
        transformer._bucketed_batch, solver.fit = self._saved

    def __str__(self) -> str:
        return " ".join(f"{k}:{v}" for k, v in self.seen.items())


def phase_fit(state: dict, n_train: int = N_TRAIN, n_test: int = N_TEST) -> str:
    import numpy as np

    from keystone_tpu import native
    from keystone_tpu.loaders.image_loaders import ImageNetLoader
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as app
    from keystone_tpu.workflow.executor import PipelineEnv

    # the checkout ships native/*.cc only; first use builds the .so
    had = [
        f for f in os.listdir(os.path.join(ROOT, "native"))
        if f.endswith(".so")
    ]
    built = native.status()
    if built["build_error"] or built["io"] != "native":
        raise SmokeFailure(f"native build failed: {built}")
    t0 = time.perf_counter()
    train_dir, test_dir, labels = write_dataset(OUT_DIR, n_train, n_test)
    say(
        f"fit: data set {n_train}+{n_test} JPEGs, {NUM_CLASSES} classes, "
        f"under {os.path.relpath(OUT_DIR, ROOT)} in "
        f"{time.perf_counter() - t0:.1f}s; native .so before start {had}, "
        f"now io={built['io']} jpeg={built['jpeg']}"
    )
    # exactly what the application's main() builds from its defaults
    conf = app.ImageNetSiftLcsFVConfig(train_dir, test_dir, labels)
    train = ImageNetLoader(conf.train_location, conf.label_path)
    test = ImageNetLoader(conf.test_location, conf.label_path)
    if (train.n, test.n) != (n_train, n_test):
        raise SmokeFailure(f"loader read {train.n}+{test.n} images")
    t0 = time.perf_counter()
    with PlacementProbe() as placement:
        predictor, test_err = app.run(train, test, conf)
    say(
        f"fit: run() {time.perf_counter() - t0:.1f}s held-out top-5 error "
        f"{test_err:.4f}; placement {placement}; {memory_report()}"
    )
    # estimator fits are in the saved prefix state: this refits nothing
    fitted = predictor.fit()
    # ...and the state's cached feature matrices are no longer needed
    del predictor
    PipelineEnv.get_or_create().reset()

    train_images = [li.image for li in train.items()]
    train_labels = [li.label for li in train.items()]
    test_images = [li.image for li in test.items()]
    test_labels = [li.label for li in test.items()]
    train_err = top5_error(fit_path_top5(fitted, train_images), train_labels)
    # D >= n: the ridge solve interpolates, so a high TRAIN error means
    # a broken solver, not a hard task
    if not train_err < 0.5:
        raise SmokeFailure(f"train top-5 error {train_err:.4f} >= 0.5")
    test_top5 = fit_path_top5(fitted, test_images)
    if not np.isclose(top5_error(test_top5, test_labels), test_err):
        raise SmokeFailure(
            "fit-path top-5 disagrees with run(): error "
            f"{top5_error(test_top5, test_labels):.4f} vs {test_err:.4f}"
        )
    if test_top5.shape != (n_test, 5):
        raise SmokeFailure(f"top-5 output has shape {test_top5.shape}")
    state.update(
        fitted=fitted, test_images=test_images, test_top5=test_top5
    )
    return (
        f"n_train={n_train} n_test={n_test} classes={NUM_CLASSES} "
        f"features=4096 train_top5_err={train_err:.4f} "
        f"heldout_top5_err={test_err:.4f} {memory_report()}"
    )


# -- serve -------------------------------------------------------------------


def _post_predict(url: str, body: bytes) -> list:
    req = urllib.request.Request(
        url + "/predict", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())["predictions"]


def phase_serve(
    state: dict, n_serve: int = N_SERVE, n_multi: int = N_MULTI
) -> str:
    import numpy as np

    from keystone_tpu.gateway.http import GatewayServer
    from keystone_tpu.gateway.lifecycle import Gateway

    fitted = state["fitted"]
    n_images = n_serve + n_multi * MULTI
    images = [
        np.asarray(img).astype(np.uint8).tolist()
        for img in state["test_images"][:n_images]
    ]
    if len(images) < n_images:
        raise SmokeFailure(f"{n_images} requests need as many test images")
    want = state["test_top5"][:n_images]
    # request i covers images spans[i]; bodies are encoded before the
    # request window opens
    spans = [(i, i + 1) for i in range(n_serve)] + [
        (s, s + MULTI) for s in range(n_serve, n_images, MULTI)
    ]
    bodies = [
        json.dumps({"instances": images[a:b]}).encode() for a, b in spans
    ]
    # wired as serve-gateway wires them; the whole fitted pipeline
    # (cast + featurize + model + top-5) is each bucket's program, so
    # raw uint8 rides the wire and the staging buffers. The AOT store
    # stays off.
    gateway = Gateway(
        fitted,
        buckets=BUCKETS,
        n_lanes=LANES,
        pipeline_depth=2,
        warmup_example=np.zeros((IMG, IMG, 3), np.uint8),
        aot_store=None,
    )
    server = GatewayServer(gateway, port=0, input_dtype=np.uint8).start()
    try:
        engines = [lane.engine for lane in gateway.pool.lanes]
        if not all(e.donate for e in engines):
            raise SmokeFailure("input donation is off on this backend")
        url = server.url().rstrip("/")
        built0 = COUNTER.requests
        got: list = [None] * n_images
        errors: list = []

        def client(requests) -> None:
            for i in requests:
                try:
                    a, b = spans[i]
                    got[a:b] = _post_predict(url, bodies[i])
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(f"request {i}: {e!r}")

        t0 = time.perf_counter()
        # the single-image requests from CLIENTS concurrent threads,
        # then the multi-image ones alone, one after another
        threads = [
            threading.Thread(
                target=client, args=(range(k, n_serve, CLIENTS),)
            )
            for k in range(CLIENTS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        client(range(n_serve, len(bodies)))
        window_s = time.perf_counter() - t0
        if errors or any(t.is_alive() for t in threads):
            raise SmokeFailure(f"requests failed: {errors[:3]}")
        # programs built in the window, compile-cache hits included
        window_builds = COUNTER.requests - built0
        wrong = [
            i for i in range(n_images) if list(got[i]) != list(want[i])
        ]
        if wrong:
            i = wrong[0]
            raise SmokeFailure(
                f"{len(wrong)}/{n_images} served top-5 differ from the "
                f"fit path, e.g. image {i}: {got[i]} vs {list(want[i])}"
            )
        if window_builds:
            raise SmokeFailure(
                f"{window_builds} programs were built inside the "
                "request window (expected 0 after warm-up)"
            )
        with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
            metrics = resp.read().decode()
        for series in (
            "keystone_serving_dispatches_total",
            "keystone_serving_pipeline_windows_total",
            "keystone_device_info",
        ):
            if series not in metrics:
                raise SmokeFailure(f"/metrics has no {series}")
        # windows served per lane and bucket, against the staging
        # buffers the lanes' pools ever allocated: more windows than
        # buffers means buffers were recycled under the async H2D
        dispatches = {
            e.name: e.metrics.summary()["dispatches_per_bucket"]
            for e in engines
        }
        windows = sum(sum(d.values()) for d in dispatches.values())
        allocations = sum(
            lane.batcher._pipeline.pool.allocations
            for lane in gateway.pool.lanes
        )
        if not windows > allocations:
            raise SmokeFailure(
                f"{windows} windows never recycled a staging buffer "
                f"({allocations} allocated)"
            )
        idle = [
            b for b in BUCKETS
            if not any(d.get(str(b)) for d in dispatches.values())
        ]
        if idle:
            raise SmokeFailure(f"buckets {idle} served no window")
        # where each lane's batches, outputs and the model's weights are
        lanes = []
        for e in engines:
            staged = e.upload_staged(
                np.zeros((e.buckets[0], IMG, IMG, 3), np.uint8)
            )
            out = e.compute_staged(staged, e.buckets[0], e.buckets[0])
            lanes.append(f"{e.name}:batch={where(staged)},out={where(out)}")
        weights = next(
            op.W for op in fitted.graph.operators.values()
            if hasattr(op, "W")
        )
    finally:
        gateway.close()
        server.stop()
    return (
        f"requests={n_serve}x1+{n_multi}x{MULTI} images={n_images} "
        "all_top5_equal_fit_path=True "
        f"window_secs={window_s:.1f} window_compiles={window_builds} "
        f"buckets={BUCKETS} lanes={LANES} pipeline_depth=2 donate=True "
        f"dispatches={json.dumps(dispatches, separators=(',', ':'))} "
        f"staging_allocations={allocations} "
        f"weights{tuple(weights.shape)}:{where(weights)} "
        f"{' '.join(lanes)} {memory_report()}"
    )


# -- chunks ------------------------------------------------------------------

CHUNK_IMAGES = 32  # of 375 x 500: twice what a shape group's chunk takes
CHUNK_SHAPE = (375, 500)


def phase_chunks(state: dict) -> str:
    """A shape group's chunk programs at VOC's image size (dense SIFT, the
    PCA projection, the 256-word Fisher kernel: the run ``voc-fit``
    makes) against the same rows FOUR at a time: the planner's chunk
    (``parallel/chunks.py: planned_rows``, held to ``PROGRAM_BYTES``)
    must give every image the rows the small program gives it. Programs
    over 24 and more such images computed wrong rows for some of them on
    a v5e (PERF.md section 6, PR 37): whoever lifts the cap fails here
    first. Then, reported and not failed: the Fisher statistics' time by
    the kernel and by the XLA program, and each function ALONE over all
    32 images at once, on inputs the small programs made: which function
    goes wrong beyond the cap, and for which rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.ops.images.fisher_vector import _FisherRows
    from keystone_tpu.ops.images.sift import _SiftRows
    from keystone_tpu.ops.learning.pca import _project_columns
    from keystone_tpu.parallel import chunks

    n, (h, w) = CHUNK_IMAGES, CHUNK_SHAPE
    key = jax.random.PRNGKey(SEED)
    coarse = jax.random.uniform(key, (n, h // 5, w // 5, 1))
    images = jax.image.resize(coarse, (n, h, w, 1), "linear") \
        + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (n, h, w, 1))
    basis = jnp.asarray(np.linalg.qr(np.random.default_rng(SEED)
                                     .standard_normal((128, 80)))[0],
                        jnp.float32)
    sift = _SiftRows(3, 4, 4, 0)
    fns = [sift, _project_columns, _FisherRows(True, 1e-4)]

    def small(fn, arr, x):  # four rows a program
        return jnp.concatenate([
            chunks.rows_program(fn)(arr, x[s:s + 4]) for s in range(0, n, 4)])

    desc = small(sift, (), images)
    reduced = small(_project_columns, basis, desc)
    words = reduced[0][:, :: reduced.shape[2] // 256][:, :256]  # (80, 256)
    var = jnp.broadcast_to(
        jnp.var(reduced[0], axis=1, keepdims=True), words.shape)
    gmm = (words, var, jnp.full((256,), 1.0 / 256, jnp.float32))
    arrays = [(), basis, gmm]
    want = small(fns[2], gmm, reduced)

    chunk, _ = chunks.planned_rows(fns, arrays, images, keep=True)
    got = jnp.concatenate([
        chunks.take_chunk(fns, chunk, arrays, images, s)
        for s in range(0, n, chunk)])

    def per_row(a, b):
        a, b = np.asarray(a).reshape(n, -1), np.asarray(b).reshape(n, -1)
        return np.linalg.norm(a - b, axis=1) / np.maximum(
            np.linalg.norm(b, axis=1), 1e-30)

    err = per_row(got, want)
    report = {"planned_chunk": int(chunk),
              "planned_vs_four_at_a_time": float(err.max())}
    if not (np.isfinite(err).all() and err.max() < 1e-3):
        raise SmokeFailure(
            f"chunks: {chunk} images a program differ from four a program "
            f"in rows {np.flatnonzero(~(err < 1e-3)).tolist()} "
            f"(worst {err.max():.3g})")
    # the Fisher statistics by the kernel and by the plain XLA program
    # (``GMMFisherVectorEstimator._choice`` follows this reading): four
    # images a call, host clock round block_until_ready, the best of five
    for name, fused in (("kernel", True), ("xla", False)):
        program = chunks.rows_program(_FisherRows(fused, 1e-4))
        jax.block_until_ready(program(gmm, reduced[:4]))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(program(gmm, reduced[:4]))
            best = min(best, time.perf_counter() - t0)
        report[f"fisher_{name}_ms_per_image"] = round(best / 4 * 1e3, 4)
    # beyond the cap: each function alone over all 32 rows at once
    for name, fn, arr, x, right in (
            ("sift", sift, (), images, desc),
            ("project", _project_columns, basis, desc, reduced),
            ("fisher", fns[2], gmm, reduced, want)):
        try:
            e = per_row(chunks.rows_program(fn)(arr, x), right)
            report[f"{name}_{n}_at_once"] = {
                "worst": float(np.nan_to_num(e, nan=np.inf).max()),
                "wrong_rows": np.flatnonzero(~(e < 1e-3)).tolist()}
        except Exception as e:  # noqa: BLE001 — reported, not failed
            report[f"{name}_{n}_at_once"] = {"raised": repr(e)[:300]}
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chunks.json"), "w") as f:
        json.dump(report, f, indent=1)
    return " ".join(f"{k}={v}" for k, v in report.items())


# -- main --------------------------------------------------------------------


def main() -> int:
    # INFO from start-up decisions (distributed runtime, compile cache,
    # native build, loader decode path); warnings from everything else
    logging.getLogger("keystone_tpu").addHandler(_log_buffer)
    for name in ("parallel.runtime", "native", "loaders"):
        logging.getLogger(f"keystone_tpu.{name}").setLevel(logging.INFO)
    COUNTER.install()
    state: dict = {}
    t_start = time.perf_counter()
    phases = (
        ("device", phase_device),
        ("kernels", phase_kernels),
        ("chunks", phase_chunks),
        ("fit", phase_fit),
        ("serve", phase_serve),
    )
    only = set(sys.argv[1:])  # phases by name; the device's always runs
    if only - {name for name, _ in phases}:
        print(f"chip_smoke.py: no such phase among {sorted(only)}",
              file=sys.stderr)
        return 2
    for name, fn in phases:
        if only and name != "device" and name not in only:
            continue
        try:
            run_phase(name, fn, state)
        except Exception as e:  # noqa: BLE001 — any failure fails the smoke
            traceback.print_exc()
            say(f"phase={name} FAILED: {e}")
            return 1
    say(
        f"all phases ok in {time.perf_counter() - t_start:.1f}s, "
        f"{COUNTER.compiled} programs compiled, {COUNTER.hits} read from "
        "the compile cache"
    )
    print(json.dumps({"ok": True, "device": state["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
