"""Native C++ IO pipeline tests (reference model: tests/python/unittest/
test_io.py ImageRecordIter cases + recordio round-trips).

Builds libmxtpu_io.so on demand (mxnet_tpu/_native.py); skips if no
toolchain.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio

pytestmark = pytest.mark.skipif(
    not __import__("mxnet_tpu._native", fromlist=["available"]).available(),
    reason="native io library unavailable")

from mxnet_tpu.recordio_iter import ImageRecordIter  # noqa: E402


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    """37 solid-color 40x52 images; color value verifiable post-decode."""
    path = str(tmp_path_factory.mktemp("recio") / "test.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    colors = []
    for i in range(37):
        val = int(rng.randint(0, 256))
        img = np.full((40, 52, 3), val, np.uint8)
        colors.append(val)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=100))
    rec.close()
    return path, colors


def test_sequential_epoch(rec_file):
    path, colors = rec_file
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=8, shuffle=False, preprocess_threads=3)
    assert it.num_samples == 37
    labels, vals, nb = [], [], 0
    for batch in it:
        nb += 1
        n = 8 - batch.pad
        labels.extend(batch.label[0].asnumpy()[:n].tolist())
        vals.extend(batch.data[0].asnumpy()[:n, 0, 0, 0].tolist())
    assert nb == 5
    assert labels == [float(i % 10) for i in range(37)]
    # solid colors survive JPEG at quality 100 within small tolerance
    assert max(abs(vals[i] - colors[i]) for i in range(37)) <= 3


def test_reset_epochs(rec_file):
    path, _ = rec_file
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=8)
    assert sum(1 for _ in it) == 5
    it.reset()
    assert sum(1 for _ in it) == 5


def test_shuffle_permutes(rec_file):
    path, _ = rec_file
    seq = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                          batch_size=8, shuffle=False)
    base = []
    for b in seq:
        base.extend(b.label[0].asnumpy()[:8 - b.pad].tolist())
    shuf = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                           batch_size=8, shuffle=True, seed=3)
    got = []
    for b in shuf:
        got.extend(b.label[0].asnumpy()[:8 - b.pad].tolist())
    assert sorted(got) == sorted(base) and got != base
    # different epochs shuffle differently
    shuf.reset()
    got2 = []
    for b in shuf:
        got2.extend(b.label[0].asnumpy()[:8 - b.pad].tolist())
    assert sorted(got2) == sorted(base) and got2 != got


def test_sharding_partitions(rec_file):
    path, _ = rec_file
    parts = []
    total = 0
    for pi in range(3):
        it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, num_parts=3, part_index=pi)
        total += it.num_samples
        got = []
        for b in it:
            got.extend(b.label[0].asnumpy()[:4 - b.pad].tolist())
        parts.append(got)
        assert len(got) == it.num_samples
    assert total == 37


def test_normalization_applied(rec_file):
    path, colors = rec_file
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=8, mean_r=128.0, mean_g=128.0,
                         mean_b=128.0, std_r=64.0, std_g=64.0, std_b=64.0)
    b = next(iter(it))
    v = b.data[0].asnumpy()[0, 0, 0, 0]
    expect = (colors[0] - 128.0) / 64.0
    assert abs(v - expect) < 0.1


def test_mean_img_channels_rgb(tmp_path):
    """R and B channels must not be swapped (OpenCV BGR -> RGB output)."""
    path = str(tmp_path / "rgb.rec")
    rec = recordio.MXRecordIO(path, "w")
    img = np.zeros((32, 32, 3), np.uint8)
    img[:, :, 2] = 200  # OpenCV BGR: red channel
    rec.write(recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img,
                                quality=100))
    rec.close()
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=1)
    d = next(iter(it)).data[0].asnumpy()[0]
    assert d[0].mean() > 150  # channel 0 = R
    assert d[2].mean() < 50   # channel 2 = B


def test_bad_file_raises(tmp_path):
    bad = tmp_path / "bad.rec"
    bad.write_bytes(b"not a recordio file at all........")
    with pytest.raises(Exception):
        ImageRecordIter(path_imgrec=str(bad), data_shape=(3, 32, 32),
                        batch_size=2)


def test_im2rec_roundtrip(tmp_path):
    import cv2
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            img = np.full((40, 40, 3), 60 * i + 30, np.uint8)
            cv2.imwrite(str(root / cls / ("%d.jpg" % i)), img)
    prefix = str(tmp_path / "ds")
    tools = os.path.join(os.path.dirname(mx.__file__), "..", "tools",
                         "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, tools, "--list", prefix, str(root)],
                   check=True, env=env)
    subprocess.run([sys.executable, tools, prefix, str(root)], check=True,
                   env=env)
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         data_shape=(3, 32, 32), batch_size=2)
    assert it.num_samples == 6
    labels = []
    for b in it:
        labels.extend(b.label[0].asnumpy()[:2 - b.pad].tolist())
    assert sorted(set(labels)) == [0.0, 1.0]
    # indexed random access via the .idx sidecar
    idx_rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                         "r")
    hdr, img = recordio.unpack_img(idx_rec.read_idx(idx_rec.keys[-1]))
    assert img.shape[2] == 3


def test_continuation_record_roundtrip(tmp_path):
    """Payloads containing the 4-byte magic split into cflag 1/2/3 parts on
    write and stitch back byte-exactly on read (dmlc recordio semantics) —
    for BOTH the python MXRecordIO and the native C++ reader."""
    import struct
    magic = struct.pack("<I", 0xced7230a)
    payloads = [
        b"plain record",
        magic + b"starts with magic",
        b"ends with magic" + b"x" * 1 + magic,       # aligned tail magic
        b"abcd" + magic + b"efgh" + magic + b"ijkl",  # two aligned magics
        magic * 3,                                    # only magics
        b"abc" + magic,  # UNaligned magic: must NOT split
    ]
    path = str(tmp_path / "cont.rec")
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()

    r = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        b = r.read()
        if b is None:
            break
        got.append(b)
    r.close()
    assert got == payloads

    # raw file structure: record 2 must have been split (contains >1 magic)
    raw = open(path, "rb").read()
    assert raw.count(magic) > len(payloads)  # seams present on disk


def test_color_geometric_augmenters(tmp_path):
    """Reference DefaultImageAugmenter jitters (image_aug_default.cc):
    brightness/contrast/saturation/pca/rotate/scale wired through the Ex
    C entry point. Statistical checks on solid-color images."""
    path = str(tmp_path / "aug.rec")
    rec = recordio.MXRecordIO(path, "w")
    import cv2
    for i in range(8):
        img = np.full((40, 40, 3), 120, np.uint8)
        rec.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                    img, quality=100))
    rec.close()

    def batch_mean(**kw):
        it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=8, seed=3, **kw)
        return next(iter(it)).data[0].asnumpy()

    base = batch_mean()
    np.testing.assert_allclose(base, 120.0, atol=2.0)

    # brightness jitter moves per-image means apart
    b = batch_mean(brightness=0.4)
    per_img = b.mean(axis=(1, 2, 3))
    assert per_img.std() > 2.0, per_img
    assert abs(b.mean() - 120.0) < 40.0

    # saturation on a gray image is a no-op (gray == value)
    s = batch_mean(saturation=0.5)
    np.testing.assert_allclose(s, 120.0, atol=2.5)

    # pca noise shifts channels jointly but images stay finite, near base
    p = batch_mean(pca_noise=0.1)
    assert np.isfinite(p).all()
    assert abs(p.mean() - 120.0) < 30.0

    # rotation of a solid image changes nothing; of a structured image it
    # moves pixels
    img_struct = np.zeros((40, 40, 3), np.uint8)
    img_struct[:, :20] = 200
    path2 = str(tmp_path / "rot.rec")
    rec2 = recordio.MXRecordIO(path2, "w")
    for i in range(4):
        rec2.write(recordio.pack_img(recordio.IRHeader(0, 0.0, i, 0),
                                     img_struct, quality=100))
    rec2.close()
    it0 = ImageRecordIter(path_imgrec=path2, data_shape=(3, 32, 32),
                          batch_size=4, seed=5)
    it1 = ImageRecordIter(path_imgrec=path2, data_shape=(3, 32, 32),
                          batch_size=4, seed=5, max_rotate_angle=30.0)
    d0 = next(iter(it0)).data[0].asnumpy()
    d1 = next(iter(it1)).data[0].asnumpy()
    assert np.abs(d0 - d1).max() > 10.0  # rotation really happened

    # random scale changes the pre-crop geometry
    it2 = ImageRecordIter(path_imgrec=path2, data_shape=(3, 32, 32),
                          batch_size=4, seed=5, resize=36,
                          min_random_scale=0.7, max_random_scale=1.3)
    d2 = next(iter(it2)).data[0].asnumpy()
    assert np.isfinite(d2).all()


def test_uint8_output_mode(rec_file):
    """dtype='uint8' emits raw RGB bytes identical to the float32 path
    (mean=0/std=1) — the device-normalize input pipeline contract."""
    path, _ = rec_file
    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
              shuffle=False, preprocess_threads=2)
    bf = next(iter(ImageRecordIter(**kw))).data[0].asnumpy()
    bu_iter = ImageRecordIter(dtype="uint8", mean_r=123.0, std_r=58.0, **kw)
    bu = next(iter(bu_iter)).data[0].asnumpy()
    assert bu.dtype == np.uint8
    # float path above had no mean/std; uint8 path NEVER normalizes
    # regardless of mean/std kwargs (they are exposed for graph folding)
    np.testing.assert_array_equal(bf.astype(np.uint8), bu)
    assert bu_iter.normalize_mean[0] == 123.0
    assert bu_iter.normalize_std[0] == 58.0
    assert bu_iter.provide_data[0].dtype == np.dtype(np.uint8)


def test_uint8_color_jitter_stays_uint8(rec_file):
    """color jitters in uint8 mode clamp-round the float jitter chain:
    same-seed float32 iterator (mean=0/std=1) is the value oracle."""
    path, _ = rec_file
    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
              preprocess_threads=1, shuffle=False, seed=9,
              brightness=0.3, contrast=0.2, saturation=0.2)
    du = next(iter(ImageRecordIter(dtype="uint8", **kw))).data[0].asnumpy()
    df = next(iter(ImageRecordIter(**kw))).data[0].asnumpy()
    assert du.dtype == np.uint8
    # identical rng stream -> identical jitter draws; uint8 is the float
    # chain rounded-and-clamped, so they agree to half a quantum
    clamped = np.clip(df, 0.0, 255.0)
    assert np.abs(du.astype(np.float32) - clamped).max() <= 0.5 + 1e-3
    # and the jitter genuinely fired (differs from the unjittered stream)
    plain = next(iter(ImageRecordIter(
        dtype="uint8", path_imgrec=path, data_shape=(3, 32, 32),
        batch_size=8, preprocess_threads=1, shuffle=False,
        seed=9))).data[0].asnumpy()
    assert np.abs(du.astype(np.int32) - plain.astype(np.int32)).max() > 2


def test_uint8_train_with_device_normalize(rec_file):
    """uint8 iter -> cast + _image_normalize prelude composed into a small
    net -> Module.fit: normalization runs in the XLA graph, matching the
    float32-iter path's learning behavior end to end."""
    path, _ = rec_file
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=8, preprocess_threads=2, dtype="uint8",
                         mean_r=123.0, mean_g=117.0, mean_b=104.0,
                         std_r=58.0, std_g=57.0, std_b=57.0)
    data = mx.sym.Variable("data")
    x = mx.sym.cast(data, dtype="float32")
    x = mx.sym._image_normalize(x, mean=it.normalize_mean,
                                std=it.normalize_std)
    x = mx.sym.Pooling(x, global_pool=True, pool_type="avg", kernel=(1, 1))
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=10)
    net = mx.sym.SoftmaxOutput(x, mx.sym.Variable("softmax_label"),
                               name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier())
    # the normalize prelude must actually have normalized: first FC input
    # stats are zero-centered-ish, so weights stay finite and small
    args, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in args.values())


def test_image_normalize_batched_axis():
    """_image_normalize must broadcast over the CHANNEL axis for both CHW
    (3d) and NCHW (4d) inputs — regression: 4d used to normalize over the
    batch axis."""
    x3 = mx.nd.array(np.arange(2 * 2 * 2, dtype=np.float32).reshape(2, 2, 2))
    x4 = mx.nd.array(np.arange(3 * 2 * 2 * 2,
                               dtype=np.float32).reshape(3, 2, 2, 2))
    mean, std = (1.0, 2.0), (2.0, 4.0)
    o3 = mx.nd._image_normalize(x3, mean=mean, std=std).asnumpy()
    o4 = mx.nd._image_normalize(x4, mean=mean, std=std).asnumpy()
    want3 = (x3.asnumpy() - np.array(mean).reshape(2, 1, 1)) \
        / np.array(std).reshape(2, 1, 1)
    want4 = (x4.asnumpy() - np.array(mean).reshape(1, 2, 1, 1)) \
        / np.array(std).reshape(1, 2, 1, 1)
    np.testing.assert_allclose(o3, want3, rtol=1e-6)
    np.testing.assert_allclose(o4, want4, rtol=1e-6)


def test_drain_mode_mismatch_errors(rec_file):
    """C-ABI guard: draining with the wrong-dtype entry point must return
    the error path (-2 + message), never memcpy into a mismatched buffer."""
    import ctypes
    from mxnet_tpu import _native
    path, _ = rec_file
    lib = _native.get_lib()
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=4, preprocess_threads=1)
    buf = np.zeros((4, 3, 32, 32), np.uint8)
    lab = np.zeros((4, 1), np.float32)
    rc = lib.MXTIONextU8(it._handle,
                         buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                         lab.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    assert rc == -2
    it2 = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                          batch_size=4, preprocess_threads=1, dtype="uint8")
    buf2 = np.zeros((4, 3, 32, 32), np.float32)
    rc2 = lib.MXTIONext(it2._handle,
                        buf2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                        lab.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    assert rc2 == -2


def test_augment_draws_fresh_per_epoch(rec_file):
    """epoch is folded into the worker rng seed: the same image gets
    different jitter in epoch 2 than in epoch 1 (augmentation diversity),
    while two same-seed iterators still agree epoch-by-epoch."""
    path, _ = rec_file
    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
              preprocess_threads=1, shuffle=False, seed=11, dtype="uint8",
              brightness=0.4)
    it_a = ImageRecordIter(**kw)
    e1 = next(iter(it_a)).data[0].asnumpy().astype(np.int32)
    it_a.reset()
    e2 = next(iter(it_a)).data[0].asnumpy().astype(np.int32)
    assert np.abs(e1 - e2).max() > 2  # fresh draws across epochs
    it_b = ImageRecordIter(**kw)
    f1 = next(iter(it_b)).data[0].asnumpy().astype(np.int32)
    np.testing.assert_array_equal(e1, f1)  # run-to-run reproducible


# ---------------------------------------------------------------- det --

@pytest.fixture(scope="module")
def det_rec_file(tmp_path_factory):
    """Synthetic VOC-style detection .rec via the example generator +
    im2rec --pack-label (the full user packing path)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(mx.__file__), "..", "example", "ssd", "dataset"))
    import make_synth_rec
    prefix = str(tmp_path_factory.mktemp("detrec") / "voc")
    make_synth_rec.generate(prefix, n_images=14, num_classes=5,
                            max_objects=3, image_size=72, seed=3)
    return prefix + ".rec"


def test_det_record_iter_layout(det_rec_file):
    """Label rows follow the reference layout [c, rows, cols, n,
    header_width, object_width, objects..., pad] with valid boxes
    (reference iter_image_det_recordio.cc:456-463)."""
    from mxnet_tpu.recordio_iter import ImageDetRecordIter
    it = ImageDetRecordIter(path_imgrec=det_rec_file, data_shape=(3, 48, 48),
                            batch_size=4, preprocess_threads=2)
    # auto pad width: 2 header + 3 objects * 5 floats + 4-prefix = 21
    assert it.label_width == 21
    seen = 0
    for batch in it:
        assert batch.data[0].shape == (4, 3, 48, 48)
        lab = batch.label[0].asnumpy()
        assert lab.shape == (4, 21)
        for row in lab:
            assert (row[0], row[1], row[2]) == (3, 48, 48)
            n = int(row[3])
            assert n >= 7 and (n - 2) % 5 == 0
            assert (row[4], row[5]) == (2, 5)
            objs = row[6:4 + n].reshape(-1, 5)
            assert np.all(objs[:, 0] >= 0) and np.all(objs[:, 0] < 5)
            assert np.all(objs[:, 1] <= objs[:, 3])
            assert np.all(objs[:, 2] <= objs[:, 4])
            assert np.all(row[4 + n:] == -1.0)
        seen += 1
    assert seen == 4  # 14 imgs, batch 4, round_batch pads the tail


def test_det_record_iter_augment_keeps_boxes_valid(det_rec_file):
    """Box-aware crop/expand/mirror never emit out-of-range or inverted
    boxes, and every image keeps >= 1 box (crop retries guarantee it)."""
    from mxnet_tpu.recordio_iter import ImageDetRecordIter
    it = ImageDetRecordIter(path_imgrec=det_rec_file, data_shape=(3, 48, 48),
                            batch_size=4, preprocess_threads=2, shuffle=True,
                            seed=5, rand_crop_prob=0.9, rand_pad_prob=0.9,
                            rand_mirror_prob=0.5)
    for _ in range(2):
        for batch in it:
            for row in batch.label[0].asnumpy():
                n = int(row[3])
                assert n >= 7, "augmentation dropped every box"
                objs = row[6:4 + n].reshape(-1, 5)
                assert np.all(objs[:, 1:] >= -1e-5)
                assert np.all(objs[:, 1:] <= 1 + 1e-5)
                assert np.all(objs[:, 3] >= objs[:, 1])
                assert np.all(objs[:, 4] >= objs[:, 2])
        it.reset()


def test_det_record_iter_mirror_flips_boxes(det_rec_file):
    """rand_mirror_prob=1 flips x coords: x' = 1 - x (within jpeg noise),
    verified against the unaugmented boxes of the same unshuffled epoch."""
    from mxnet_tpu.recordio_iter import ImageDetRecordIter
    kw = dict(path_imgrec=det_rec_file, data_shape=(3, 48, 48), batch_size=2,
              preprocess_threads=1, shuffle=False)
    plain = ImageDetRecordIter(**kw)
    flipped = ImageDetRecordIter(rand_mirror_prob=1.0, **kw)
    for bp, bf in zip(plain, flipped):
        lp, lf = bp.label[0].asnumpy(), bf.label[0].asnumpy()
        for rp, rf in zip(lp, lf):
            n = int(rp[3])
            assert int(rf[3]) == n
            op = rp[6:4 + n].reshape(-1, 5)
            of = rf[6:4 + n].reshape(-1, 5)
            np.testing.assert_allclose(of[:, 0], op[:, 0])        # class
            np.testing.assert_allclose(of[:, 1], 1 - op[:, 3], atol=1e-5)
            np.testing.assert_allclose(of[:, 3], 1 - op[:, 1], atol=1e-5)
            np.testing.assert_allclose(of[:, 2], op[:, 2], atol=1e-5)


def test_det_record_iter_pad_width_validation(det_rec_file):
    """A label_pad_width smaller than the widest record label fails
    loudly at construction (reference: LOG(FATAL) on underestimate)."""
    from mxnet_tpu.recordio_iter import ImageDetRecordIter
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="smaller than the widest"):
        ImageDetRecordIter(path_imgrec=det_rec_file, data_shape=(3, 48, 48),
                           batch_size=2, label_pad_width=5)
    # an ample explicit width is honored verbatim (train/val alignment)
    it = ImageDetRecordIter(path_imgrec=det_rec_file, data_shape=(3, 48, 48),
                            batch_size=2, label_pad_width=40)
    assert it.label_width == 44
    row = next(iter(it)).label[0].asnumpy()[0]
    assert np.all(row[4 + int(row[3]):] == -1.0)


def test_det_record_iter_sharding(det_rec_file):
    """num_parts shards partition the records (union of per-shard sample
    counts equals the total; shards are disjoint record subsets)."""
    from mxnet_tpu.recordio_iter import ImageDetRecordIter
    kw = dict(path_imgrec=det_rec_file, data_shape=(3, 48, 48), batch_size=2,
              preprocess_threads=1)
    full = ImageDetRecordIter(**kw)
    s0 = ImageDetRecordIter(num_parts=2, part_index=0, **kw)
    s1 = ImageDetRecordIter(num_parts=2, part_index=1, **kw)
    assert s0.num_samples + s1.num_samples == full.num_samples
    assert abs(s0.num_samples - s1.num_samples) <= 1


def test_stale_library_is_rebuilt(tmp_path, monkeypatch):
    """A library older than anything under src/ is stale: _load rebuilds
    before loading instead of reusing the old ABI, and a failed rebuild is
    'unavailable', not a silent load of the stale file."""
    from mxnet_tpu import _native
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.cc").write_text("// source")
    lib_mtime = os.path.getmtime(_native._LIB_PATH)
    monkeypatch.setattr(_native, "_SRC_DIR", str(src))
    os.utime(str(src / "a.cc"), (lib_mtime - 10, lib_mtime - 10))
    assert not _native._stale()
    os.utime(str(src / "a.cc"), (lib_mtime + 10, lib_mtime + 10))
    assert _native._stale()
    monkeypatch.setattr(_native, "_LIB_PATH", str(tmp_path / "missing.so"))
    assert _native._stale()

    calls = []
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_build", lambda: calls.append(1) or False)
    assert _native._load() is None and calls == [1]
