"""Real-dataset train-to-accuracy tier (reference:
tests/python/train/test_mlp.py trains actual MNIST and asserts final
accuracy). This environment has no network egress, so the real dataset is
scikit-learn's bundled handwritten digits (1797 genuine 8x8 grayscale digit
scans, shipped inside the package) — same task family, same protocol:
train/val split, train to convergence, assert the val accuracy bar.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym


def _digits():
    from sklearn.datasets import load_digits

    d = load_digits()
    X = (d.data / 16.0).astype(np.float32)          # (1797, 64) in [0, 1]
    y = d.target.astype(np.float32)
    rng = np.random.RandomState(0)
    order = rng.permutation(len(y))
    X, y = X[order], y[order]
    n_train = 1500
    return X[:n_train], y[:n_train], X[n_train:], y[n_train:]


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=128)
    net = sym.Activation(data=net, name="relu1", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=64)
    net = sym.Activation(data=net, name="relu2", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc3", num_hidden=10)
    return sym.SoftmaxOutput(data=net, name="softmax")


def _lenet():
    data = sym.Variable("data")
    net = sym.Convolution(data=data, name="c1", kernel=(3, 3), pad=(1, 1),
                          num_filter=16)
    net = sym.Activation(data=net, name="a1", act_type="relu")
    net = sym.Pooling(data=net, name="p1", kernel=(2, 2), stride=(2, 2),
                      pool_type="max")
    net = sym.Convolution(data=net, name="c2", kernel=(3, 3), pad=(1, 1),
                          num_filter=32)
    net = sym.Activation(data=net, name="a2", act_type="relu")
    net = sym.Pooling(data=net, name="p2", kernel=(2, 2), stride=(2, 2),
                      pool_type="max")
    net = sym.Flatten(data=net, name="flat")
    net = sym.FullyConnected(data=net, name="fc1", num_hidden=64)
    net = sym.Activation(data=net, name="a3", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=10)
    return sym.SoftmaxOutput(data=net, name="softmax")


@pytest.mark.slow
def test_mlp_digits_val_accuracy():
    X, y, Xv, yv = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=40,
                           learning_rate=0.1, momentum=0.9,
                           initializer=mx.init.Xavier())
    model.fit(X, y, batch_size=50)
    acc = model.score(mx.io.NDArrayIter(Xv, yv, batch_size=50))
    # bar raised 0.95 -> 0.97 in round 3 (reference anchor: MNIST MLP 97.8%,
    # example/mnist/README.md:24; this is the no-egress equivalent)
    assert acc >= 0.97, f"MLP val accuracy {acc:.4f} < 0.97"


@pytest.mark.slow
def test_lenet_digits_val_accuracy():
    X, y, Xv, yv = _digits()
    X4 = X.reshape(-1, 1, 8, 8)
    Xv4 = Xv.reshape(-1, 1, 8, 8)
    model = mx.FeedForward(_lenet(), ctx=mx.cpu(), num_epoch=40,
                           learning_rate=0.1, momentum=0.9,
                           initializer=mx.init.Xavier())
    model.fit(X4, y, batch_size=50)
    acc = model.score(mx.io.NDArrayIter(Xv4, yv, batch_size=50))
    assert acc >= 0.95, f"LeNet val accuracy {acc:.4f} < 0.95"


def _digits_recordio(path, X, y, upscale=3):
    """Pack digit scans as JPEG RecordIO shards: 8x8 grayscale scans are
    kron-upsampled (x3 -> 24x24) and replicated to RGB so the full
    ImageRecordIter path (JPEG decode, resize, crop, mirror) is exercised
    on real scanned data."""
    from mxnet_tpu import recordio as rio

    w = rio.MXRecordIO(path, "w")
    for i in range(len(y)):
        img8 = (X[i].reshape(8, 8) * 255).astype(np.uint8)
        img = np.kron(img8, np.ones((upscale, upscale), np.uint8))
        rgb = np.stack([img] * 3, axis=-1)
        w.write(rio.pack_img(rio.IRHeader(0, float(y[i]), i, 0), rgb,
                             quality=95, img_fmt=".jpg"))
    w.close()
    return path


def _lenet_rgb(size):
    data = sym.Variable("data")
    net = sym.Convolution(data=data, name="c1", kernel=(3, 3), pad=(1, 1),
                          num_filter=16)
    net = sym.Activation(data=net, name="a1", act_type="relu")
    net = sym.Pooling(data=net, name="p1", kernel=(2, 2), stride=(2, 2),
                      pool_type="max")
    net = sym.Convolution(data=net, name="c2", kernel=(3, 3), pad=(1, 1),
                          num_filter=32)
    net = sym.Activation(data=net, name="a2", act_type="relu")
    net = sym.Pooling(data=net, name="p2", kernel=(2, 2), stride=(2, 2),
                      pool_type="max")
    net = sym.Flatten(data=net, name="flat")
    net = sym.FullyConnected(data=net, name="fc1", num_hidden=64)
    net = sym.Activation(data=net, name="a3", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=10)
    return sym.SoftmaxOutput(data=net, name="softmax")


@pytest.mark.slow
def test_lenet_augmented_pipeline_accuracy_parity():
    """Augmentation tier: LeNet through the FULL
    ImageRecordIter pipeline (JPEG shards, rand-crop jitter + mirror) must
    train to accuracy parity (+-2%) with the unaugmented center-crop run.
    Digits survive mirroring poorly in principle, but the val protocol is
    identical for both runs (center crop), so the comparison isolates what
    augmentation does to training."""
    import os
    import tempfile

    X, y, Xv, yv = _digits()
    tmp = tempfile.mkdtemp(prefix="digits_rec_")
    train_rec = _digits_recordio(os.path.join(tmp, "train.rec"), X, y)
    val_rec = _digits_recordio(os.path.join(tmp, "val.rec"), Xv, yv)

    crop = 20  # from 24x24 sources: +-4px translation jitter when random
    def run(rand_crop, rand_mirror, seed=5):
        train_iter = mx.io.ImageRecordIter(
            path_imgrec=train_rec, data_shape=(3, crop, crop),
            batch_size=50, rand_crop=rand_crop, rand_mirror=rand_mirror,
            shuffle=True, seed=seed, scale=1.0 / 255)
        val_iter = mx.io.ImageRecordIter(
            path_imgrec=val_rec, data_shape=(3, crop, crop),
            batch_size=50, scale=1.0 / 255)
        model = mx.FeedForward(_lenet_rgb(crop), ctx=mx.cpu(), num_epoch=30,
                               learning_rate=0.1, momentum=0.9,
                               initializer=mx.init.Xavier())
        model.fit(train_iter, batch_size=50)
        return model.score(val_iter)

    plain = run(rand_crop=False, rand_mirror=False)
    cropped = run(rand_crop=True, rand_mirror=False)
    mirrored = run(rand_crop=True, rand_mirror=True)
    assert plain >= 0.90, f"unaugmented LeNet pipeline acc {plain:.4f} < 0.90"
    # label-preserving augmentation (translation jitter) must hold parity
    assert cropped >= plain - 0.02, (
        f"rand-crop run {cropped:.4f} fell more than 2% below "
        f"unaugmented {plain:.4f}")
    # mirroring is label-DESTRUCTIVE on digits (2/5, 3, 7 lose identity
    # when flipped — unlike the natural images the reference mirrors), so
    # the bar here is only that training still converges through the
    # mirror path, measured at 85%+ (empirically ~7% below plain)
    assert mirrored >= 0.80, (
        f"mirror-augmented run {mirrored:.4f} failed to converge")
