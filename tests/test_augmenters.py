"""Extended augmenter flags: rotate / rotate_list,
min/max_random_scale, min/max_img_size, max_random_contrast,
max_random_illumination, fixed mirror — in both the PIL and native paths.

Reference semantics: src/io/image_augmenter.h:40-79 (geometric: fixed
rotate overrides max_rotate_angle, rotate_list overrides both; scale
s ~ U[min,max] with per-dimension clamp to [min_img_size, max_img_size])
and src/io/iter_normalize.h:173-201 (photometric: out = ((px - mean) * c
+ i) * scale, c ~ U[1-mc, 1+mc], i ~ U[-mi, mi]).
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio
from mxnet_tpu import native as native_mod
from mxnet_tpu import recordio as rio


def _make_jpeg_rec(tmp_path, n=8, size=32, quality=95, name="imgs.rec"):
    path = str(tmp_path / name)
    w = rio.MXRecordIO(path, "w")
    imgs = []
    for i in range(n):
        yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
        img = np.stack([(yy * 255 / size), (xx * 255 / size),
                        np.full_like(yy, (i * 13) % 255)],
                       axis=-1).astype(np.uint8)
        imgs.append(img)
        w.write(rio.pack_img(rio.IRHeader(0, float(i), i, 0), img,
                             quality=quality, img_fmt=".jpg"))
    w.close()
    return path, imgs


def _decoded(path, n):
    """The images exactly as the iterator's decoder sees them (JPEG is
    lossy, so expectations are built from the decoded pixels)."""
    r = rio.MXRecordIO(path, "r")
    out = []
    for _ in range(n):
        _, img = rio.unpack_img(r.read())
        out.append(img.astype(np.float32))
    r.close()
    return out


def _batches_chw(it):
    out = []
    for b in it:
        out.extend(np.asarray(b.data[0].asnumpy()))
    return out


# ---------------------------------------------------------------- PIL path

def test_rotate_fixed_180(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    path, _ = _make_jpeg_rec(tmp_path, n=4, size=32)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, rotate=180)
    assert it._native is None  # rotation routes around the native pipeline
    got = _batches_chw(it)
    for img, chw in zip(_decoded(path, 4), got):
        expect = img[::-1, ::-1].transpose(2, 0, 1)  # 180 deg is exact
        np.testing.assert_allclose(chw, expect, atol=1.0)


def test_rotate_list_picks_from_list(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    path, _ = _make_jpeg_rec(tmp_path, n=16, size=32)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=16, rotate_list="90,270", seed=3)
    got = _batches_chw(it)
    hits = set()
    for img, chw in zip(_decoded(path, 16), got):
        # PIL rotates counterclockwise; 90/270 on a square image are exact
        cands = {90: np.rot90(img, 1), 270: np.rot90(img, 3)}
        matched = None
        for ang, exp in cands.items():
            if np.allclose(chw, exp.transpose(2, 0, 1), atol=1.0):
                matched = ang
                break
        assert matched is not None, "image matches neither listed angle"
        hits.add(matched)
    assert hits == {90, 270}, f"both angles should occur, saw {hits}"


def test_random_scale_deterministic_when_pinned(tmp_path, monkeypatch):
    """min=max_random_scale pins the draw: 64px input at scale 0.5 becomes
    exactly the 32px resize (crop is then the identity)."""
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    from PIL import Image

    path, _ = _make_jpeg_rec(tmp_path, n=4, size=64)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, min_random_scale=0.5,
                             max_random_scale=0.5)
    got = _batches_chw(it)
    for img, chw in zip(_decoded(path, 4), got):
        expect = np.asarray(
            Image.fromarray(img.astype(np.uint8)).resize((32, 32)),
            dtype=np.float32).transpose(2, 0, 1)
        np.testing.assert_allclose(chw, expect, atol=1.0)


def test_img_size_clamp(tmp_path, monkeypatch):
    """Upscale by 2 with max_img_size=48: dims clamp to 48 (not 64), then
    the center crop takes 32."""
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    from PIL import Image

    path, _ = _make_jpeg_rec(tmp_path, n=4, size=32)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, min_random_scale=2.0,
                             max_random_scale=2.0, max_img_size=48)
    got = _batches_chw(it)
    for img, chw in zip(_decoded(path, 4), got):
        up = np.asarray(
            Image.fromarray(img.astype(np.uint8)).resize((48, 48)),
            dtype=np.float32)
        expect = up[8:40, 8:40].transpose(2, 0, 1)  # center 32 of 48
        np.testing.assert_allclose(chw, expect, atol=1.0)


def test_illumination_adds_bounded_constant(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=32)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=8, max_random_illumination=50,
                             seed=11)
    got = _batches_chw(it)
    offsets = []
    for img, chw in zip(_decoded(path, 8), got):
        diff = chw - img.transpose(2, 0, 1)
        off = float(np.mean(diff))
        assert abs(off) <= 50.0 + 1e-3
        np.testing.assert_allclose(diff, off, atol=1e-3)  # constant/image
        offsets.append(round(off, 3))
    assert len(set(offsets)) > 1, "illumination draw should vary per image"


def test_contrast_scales_about_mean(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NATIVE_IO", "0")
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=32)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=8, max_random_contrast=0.5, seed=5)
    got = _batches_chw(it)
    factors = []
    for img, chw in zip(_decoded(path, 8), got):
        base = img.transpose(2, 0, 1)
        c = float(np.sum(chw * base) / np.sum(base * base))  # lsq factor
        assert 0.5 - 1e-3 <= c <= 1.5 + 1e-3
        np.testing.assert_allclose(chw, base * c, atol=1e-2)
        factors.append(round(c, 4))
    assert len(set(factors)) > 1, "contrast draw should vary per image"


def test_uint8_output_rejects_photometric(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=4, size=32)
    with pytest.raises(mx.base.MXNetError):
        mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                            batch_size=4, output_dtype="uint8",
                            max_random_contrast=0.5)


def test_scale_range_validation(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=4, size=32)
    with pytest.raises(mx.base.MXNetError):
        mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                            batch_size=4, min_random_scale=1.5,
                            max_random_scale=0.5)


# ------------------------------------------------------------- native path

needs_native = pytest.mark.skipif(native_mod.get_lib() is None,
                                  reason="native library unavailable")


@needs_native
def test_native_stays_on_fast_path_for_new_flags(tmp_path):
    """Scale/img-size/photometric/fixed-mirror run natively; rotation still
    routes to the PIL path."""
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=64)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=8, min_random_scale=0.8,
                             max_random_scale=1.2, max_random_contrast=0.2,
                             mirror=True)
    assert it._native is not None
    it2 = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                              batch_size=8, rotate=90)
    assert it2._native is None


@needs_native
def test_native_pinned_scale_equals_resize_short(tmp_path):
    """scale 0.5 on 64px input takes the same ResizeBilinear as
    resize_short=32 — byte-identical outputs."""
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=64)
    offs = native_mod.scan_offsets(path)
    a = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  min_random_scale=0.5, max_random_scale=0.5)
    b = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  resize=32)
    da, _, _ = a.next()
    db, _, _ = b.next()
    np.testing.assert_array_equal(da, db)


@needs_native
def test_native_img_size_clamp_identity(tmp_path):
    """Upscale by 2 clamped back to the source size is the identity."""
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=64)
    offs = native_mod.scan_offsets(path)
    a = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  min_random_scale=2.0, max_random_scale=2.0,
                                  max_img_size=64.0)
    b = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32))
    da, _, _ = a.next()
    db, _, _ = b.next()
    np.testing.assert_array_equal(da, db)


@needs_native
def test_native_illumination_bounded_constant(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=32)
    offs = native_mod.scan_offsets(path)
    a = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  max_random_illumination=50.0, seed=7)
    b = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32))
    da, _, _ = a.next()
    db, _, _ = b.next()
    offsets = set()
    for i in range(8):
        diff = da[i] - db[i]
        off = float(np.mean(diff))
        assert abs(off) <= 50.0 + 1e-3
        np.testing.assert_allclose(diff, off, atol=1e-3)
        offsets.add(round(off, 3))
    assert len(offsets) > 1


@needs_native
def test_native_contrast_bounded_factor(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=32)
    offs = native_mod.scan_offsets(path)
    a = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  max_random_contrast=0.5, seed=7)
    b = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32))
    da, _, _ = a.next()
    db, _, _ = b.next()
    factors = set()
    for i in range(8):
        c = float(np.sum(da[i] * db[i]) / np.sum(db[i] * db[i]))
        assert 0.5 - 1e-3 <= c <= 1.5 + 1e-3
        np.testing.assert_allclose(da[i], db[i] * c, atol=1e-2)
        factors.add(round(c, 4))
    assert len(factors) > 1


@needs_native
def test_native_fixed_mirror(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=8, size=32)
    offs = native_mod.scan_offsets(path)
    a = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32),
                                  mirror=True)
    b = native_mod.NativePipeline(path, offs, batch=8, data_shape=(3, 32, 32))
    da, _, _ = a.next()
    db, _, _ = b.next()
    np.testing.assert_array_equal(da, db[:, :, :, ::-1])  # NCHW: flip W


@needs_native
def test_native_u8_rejects_photometric(tmp_path):
    path, _ = _make_jpeg_rec(tmp_path, n=4, size=32)
    offs = native_mod.scan_offsets(path)
    with pytest.raises(ValueError):
        native_mod.NativePipeline(path, offs, batch=4, data_shape=(3, 32, 32),
                                  out_u8=True, max_random_illumination=10.0)
