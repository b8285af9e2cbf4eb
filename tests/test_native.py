"""Native IO library tests (vs the numpy fallbacks)."""

import numpy as np
import pytest

from keystone_tpu.native import native_available, read_cifar, read_csv_f32


def test_native_csv_matches_numpy(tmp_path):
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((50, 7)).astype(np.float32)
    p = tmp_path / "data.csv"
    np.savetxt(p, arr, delimiter=",")
    got = read_csv_f32(str(p))
    expect = np.loadtxt(p, delimiter=",", dtype=np.float32, ndmin=2)
    np.testing.assert_allclose(got, expect, rtol=1e-5)


def test_native_cifar_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    n, dim, c = 5, 32, 3
    labels = rng.integers(0, 10, n).astype(np.uint8)
    planes = rng.integers(0, 256, (n, c, dim, dim)).astype(np.uint8)
    records = np.concatenate(
        [labels[:, None], planes.reshape(n, -1)], axis=1
    )
    p = tmp_path / "cifar.bin"
    records.tofile(p)
    got_labels, got_images = read_cifar(str(p), c, dim)
    np.testing.assert_array_equal(got_labels, labels.astype(np.int32))
    expect = planes.transpose(0, 2, 3, 1).astype(np.float32)
    np.testing.assert_allclose(got_images, expect)


def test_native_library_built():
    # the shared library builds in this environment (g++ is baked in)
    assert native_available()


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_csv_edge_cases_agree_with_numpy(tmp_path):
    """The C++ parser and the numpy fallback must agree on whitespace,
    scientific notation, negative zero, and trailing newlines."""
    cases = {
        "plain": "1.5,2.5\n-3.25,4e-2\n",
        "scientific": "1e10,-2.5E-3\n+0.0,-0.0\n",
        "no_trailing_newline": "9,8\n7,6",
        "blank_trailing_lines": "1,2\n3,4\n\n\n",
        "spaces_around_values": " 1.0 , 2.0 \n 3.0 , 4.0 \n",
        "single_row": "5,6,7\n",
        "single_col": "1\n2\n3\n",
    }
    for name, text in cases.items():
        p = tmp_path / f"{name}.csv"
        p.write_text(text)
        got = read_csv_f32(str(p))
        expect = np.loadtxt(p, delimiter=",", dtype=np.float32, ndmin=2)
        np.testing.assert_allclose(got, expect, rtol=1e-6, err_msg=name)
        assert got.shape == expect.shape, name


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_csv_ragged_falls_back(tmp_path):
    """Ragged rows must not silently mis-parse: the wrapper falls back to
    numpy, which raises its usual error."""
    p = tmp_path / "ragged.csv"
    p.write_text("1,2,3\n4,5\n")
    with pytest.raises(ValueError):
        read_csv_f32(str(p))


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_csv_large_file(tmp_path):
    """The C++ layer's reason to exist is large-file throughput (measured
    ~2x np.loadtxt warm on one core); this asserts correctness at that
    scale — wall-clock assertions are too flake-prone for CI."""
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((40_000, 128)).astype(np.float32)
    p = tmp_path / "big.csv"
    np.savetxt(p, arr, delimiter=",", fmt="%.6e")

    got = read_csv_f32(str(p))
    expect = np.loadtxt(p, delimiter=",", dtype=np.float32, ndmin=2)
    np.testing.assert_allclose(got, expect, rtol=1e-5)


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_native_cifar_truncated_record_ignored(tmp_path):
    """A trailing partial record (torn write) is ignored, matching the
    numpy fallback's floor-division record count."""
    rng = np.random.default_rng(2)
    n, dim, c = 3, 8, 3
    rec = np.concatenate(
        [
            rng.integers(0, 10, (n, 1)).astype(np.uint8),
            rng.integers(0, 256, (n, c * dim * dim)).astype(np.uint8),
        ],
        axis=1,
    )
    p = tmp_path / "trunc.bin"
    with open(p, "wb") as f:
        f.write(rec.tobytes())
        f.write(b"\x01\x02\x03")  # partial 4th record
    labels, images = read_cifar(str(p), c, dim)
    assert labels.shape == (n,)
    assert images.shape == (n, dim, dim, c)


# -- JPEG fast path ---------------------------------------------------------


from jpeg_fixtures import jpeg_bytes as _make_jpeg_bytes  # noqa: E402


def test_jpeg_native_library_built():
    from keystone_tpu.native import jpeg_native_available

    # libjpeg + headers are baked into this image; the decoder must build
    assert jpeg_native_available()


def test_jpeg_native_matches_pil_draft_path(tmp_path):
    """native/jpeg.cc tracks the PIL draft-decode + BILINEAR-resize
    fallback within quantization tolerance (both decode the same DCT at
    draft scale and use triangle-filter resampling; PIL rounds to uint8
    after resize, the native path keeps float — so ±1 level plus a small
    mean bound, across down- and up-scaling targets)."""
    from keystone_tpu.loaders.streaming import _decode_payload
    from keystone_tpu.native import jpeg_decode_f32

    for seed, (w, h) in enumerate([(333, 251), (64, 80), (512, 384)]):
        data = _make_jpeg_bytes(w, h, seed)
        for target in (32, 96, 256):
            nat = jpeg_decode_f32(data, target)
            pil, how = _decode_payload((data, target), use_native=False)
            assert how == "pil"
            assert nat is not None and pil is not None
            assert nat.shape == pil.shape == (target, target, 3)
            d = np.abs(nat - pil)
            assert d.max() <= 2.0, (seed, target, d.max())
            assert d.mean() < 0.5, (seed, target, d.mean())


def test_jpeg_native_grayscale_expands_to_rgb():
    import io as _io

    from PIL import Image as PILImage

    from keystone_tpu.native import jpeg_decode_f32

    arr = (np.arange(64 * 64).reshape(64, 64) % 256).astype(np.uint8)
    buf = _io.BytesIO()
    PILImage.fromarray(arr, mode="L").save(buf, format="JPEG")
    out = jpeg_decode_f32(buf.getvalue(), 32)
    assert out is not None and out.shape == (32, 32, 3)
    # grayscale: all three channels identical
    np.testing.assert_array_equal(out[..., 0], out[..., 1])
    np.testing.assert_array_equal(out[..., 0], out[..., 2])


def test_jpeg_native_corrupt_returns_none_and_loader_falls_back(tmp_path):
    from keystone_tpu.native import jpeg_decode_f32

    assert jpeg_decode_f32(b"not a jpeg at all", 32) is None
    # truncated stream: header ok, body gone
    data = _make_jpeg_bytes(100, 100, 3)
    assert jpeg_decode_f32(data[: len(data) // 4], 32) is None


def test_jpeg_native_batch_matches_single():
    from keystone_tpu.native import jpeg_decode_batch_f32, jpeg_decode_f32

    blobs = [_make_jpeg_bytes(120, 90, s) for s in range(4)]
    blobs.insert(2, b"corrupt")  # one bad slot must not poison the rest
    imgs, ok = jpeg_decode_batch_f32(blobs, 48, num_threads=2)
    assert ok.tolist() == [True, True, False, True, True]
    for i, b in enumerate(blobs):
        if not ok[i]:
            continue
        np.testing.assert_array_equal(imgs[i], jpeg_decode_f32(b, 48))


def test_streaming_native_decode_matches_pil_decode(tmp_path):
    """The streaming loader's native and PIL decode paths agree within
    decode tolerance on the same tar (the pool-parity test pins the two
    POOLS to identical bytes; this pins the two DECODERS)."""
    import tarfile

    from keystone_tpu.loaders.streaming import StreamingImageLoader

    tar = tmp_path / "imgs.tar"
    with tarfile.open(tar, "w") as tf:
        for i in range(6):
            p = tmp_path / f"m_{i}.JPEG"
            p.write_bytes(_make_jpeg_bytes(90 + 7 * i, 70 + 5 * i, i))
            tf.add(str(p), arcname=f"m_{i}.JPEG")

    def mk(native, how):
        loader = StreamingImageLoader(
            [str(tar)], lambda name: 0, decode_size=64,
            use_native_decode=native,
        )
        out = list(loader.items())
        # the loader reports the decode path it actually took
        assert dict(loader.decode_counts) == {how: 6}
        return out

    nat, pil = mk(True, "native"), mk(False, "pil")
    assert len(nat) == len(pil) == 6
    for (n1, _, a1), (n2, _, a2) in zip(nat, pil):
        assert n1 == n2
        assert np.abs(a1 - a2).max() <= 2.0


def test_failed_build_is_reported_not_hidden(monkeypatch, caplog, tmp_path):
    """A failing make leaves the numpy fallback in charge, but the
    failure is logged with make's stderr and kept for status()."""
    import logging
    import subprocess

    from keystone_tpu import native

    def failing_make():
        raise subprocess.CalledProcessError(
            2, ["make"], stderr=b"io.cc:1: error: no such compiler"
        )

    monkeypatch.setattr(native, "_build_once", failing_make)
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "absent.so"))
    monkeypatch.setattr(
        native, "_JPEG_LIB_PATH", str(tmp_path / "absent_jpeg.so")
    )
    for name in ("_lib", "_jpeg_lib", "_build_error"):
        monkeypatch.setattr(native, name, None)
    for name in ("_tried", "_jpeg_tried"):
        monkeypatch.setattr(native, name, False)
    with caplog.at_level(logging.WARNING, logger="keystone_tpu.native"):
        st = native.status()
    assert st["io"] == "numpy" and st["jpeg"] == "PIL"
    assert "no such compiler" in st["build_error"]
    assert any(
        "native build failed" in r.getMessage() for r in caplog.records
    )
