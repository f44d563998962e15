"""Input pipeline: threaded decode, raw records, staging buffers
(reference: ``iter_image_recordio_2.cc :: ImageRecordIOParser2``)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.image import ImageIter
from mxnet_tpu.io import ImageRecordIter


def _build(path, n, fmt="jpg", hw=64, crop=48):
    rng = np.random.RandomState(42)
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    raws = []
    for i in range(n):
        img = rng.randint(0, 255, (hw, hw, 3), dtype=np.uint8)
        header = recordio.IRHeader(0, float(i), i, 0)
        if fmt == "raw":
            raws.append(img[:crop, :crop].copy())
            rec.write_idx(i, recordio.pack(header, raws[-1].tobytes()))
        else:
            rec.write_idx(i, recordio.pack_img(header, img, quality=95))
    rec.close()
    return raws


def test_raw_records_roundtrip_exactly(tmp_path):
    p = str(tmp_path / "raw")
    raws = _build(p, 12, "raw")
    it = ImageIter(4, (3, 48, 48), path_imgrec=p + ".rec",
                   preprocess_threads=0, dtype="uint8")
    got = []
    labels = []
    try:
        while True:
            d, l, _pad = it.next_np()
            got.append(d)
            labels.append(l)
    except StopIteration:
        pass
    got = np.concatenate(got)
    labels = np.concatenate(labels)
    assert got.dtype == np.uint8
    for i in range(12):
        k = int(labels[i])
        np.testing.assert_array_equal(got[i], raws[k].transpose(2, 0, 1))


def test_threaded_decode_matches_sequential(tmp_path):
    """Regression for the shared-reader race: concurrent decode must
    produce the same batches as sequential (deterministic augmenters)."""
    p = str(tmp_path / "jpg")
    _build(p, 32, "jpg")

    def run(threads):
        it = ImageIter(8, (3, 48, 48), path_imgrec=p + ".rec",
                       preprocess_threads=threads)
        out = []
        try:
            while True:
                d, l, _pad = it.next_np()
                out.append((d, l))
        except StopIteration:
            pass
        return out

    seq = run(0)
    par = run(4)
    assert len(seq) == len(par) == 4
    for (d1, l1), (d2, l2) in zip(seq, par):
        np.testing.assert_array_equal(l1, l2)
        np.testing.assert_array_equal(d1, d2)


def test_next_np_out_buffer(tmp_path):
    p = str(tmp_path / "raw2")
    _build(p, 8, "raw")
    it = ImageIter(4, (3, 48, 48), path_imgrec=p + ".rec",
                   preprocess_threads=2, dtype="uint8")
    buf = np.empty((4, 3, 48, 48), np.uint8)
    d, l, _pad = it.next_np(out=buf)
    assert d is buf
    d2, _l2, _ = it.next_np()
    assert not np.array_equal(buf, d2)


def test_image_record_iter_normalizes(tmp_path):
    p = str(tmp_path / "jpg2")
    _build(p, 8, "jpg")
    it = ImageRecordIter(path_imgrec=p + ".rec", data_shape=(3, 48, 48),
                         batch_size=4, preprocess_threads=2,
                         mean_r=127.0, mean_g=127.0, mean_b=127.0,
                         std_r=58.0, std_g=58.0, std_b=58.0)
    batch = it.next()
    d = batch.data[0].asnumpy()
    assert d.shape == (4, 3, 48, 48)
    assert abs(float(d.mean())) < 1.0  # roughly centered


def test_im2rec_raw_encoding(tmp_path):
    from PIL import Image
    root = tmp_path / "imgs" / "cat"
    root.mkdir(parents=True)
    rng = np.random.RandomState(0)
    for i in range(4):
        Image.fromarray(rng.randint(0, 255, (32, 32, 3), dtype=np.uint8)) \
            .save(str(root / ("im%d.png" % i)))
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import im2rec
    prefix = str(tmp_path / "ds")
    im2rec.main([prefix, str(tmp_path / "imgs"), "--list"])
    im2rec.main([prefix + ".lst", str(tmp_path / "imgs"),
                 "--encoding", ".raw"])
    it = ImageIter(2, (3, 32, 32), path_imgrec=prefix + ".rec",
                   preprocess_threads=0, dtype="uint8")
    d, l, _pad = it.next_np()
    assert d.shape == (2, 3, 32, 32) and d.dtype == np.uint8


def test_uint8_batch_trains(tmp_path):
    """uint8 image batches feed Conv nets directly (cast to the weight
    dtype inside the op) -- the 4x-less-transfer pipeline contract."""
    from mxnet_tpu import autograd, gluon
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1, activation="relu"),
            gluon.nn.Flatten(), gluon.nn.Dense(3))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randint(
        0, 255, (2, 3, 8, 8)).astype(np.uint8))
    assert x.dtype == np.uint8
    out = net(x)
    assert out.shape == (2, 3)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                       kvstore=None)
    from mxnet_tpu.parallel import TrainStep
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                     mesh=None)
    y = mx.nd.array(np.zeros((2,), np.float32))
    l = float(step(x, y).asscalar())
    assert np.isfinite(l)


def test_process_pool_decode_matches_serial(tmp_path):
    """preprocess_procs: forkserver workers decode into the
    SharedMemory slab; batches must match the serial path exactly
    (deterministic augs).  The pool must NOT fork this
    (JAX-multithreaded) process: the os.fork RuntimeWarning is
    escalated to an error here (the fork-based pool
    was a deadlock time bomb)."""
    import warnings
    p = str(tmp_path / "procjpg")
    _build(p, 24, "jpg")

    def run(**kw):
        it = ImageIter(8, (3, 48, 48), path_imgrec=p + ".rec", **kw)
        try:
            out = []
            while True:
                d, l, _pad = it.next_np()
                out.append((d.copy(), l.copy()))
        except StopIteration:
            return out
        finally:
            it.close()

    serial = run(preprocess_threads=0)
    with warnings.catch_warnings():
        # CPython emits the multithreaded-fork hazard as
        # DeprecationWarning (3.12+) and RuntimeWarning in other
        # paths/versions; escalate any fork warning
        warnings.filterwarnings("error", message=".*fork.*",
                                category=Warning)
        pooled = run(preprocess_procs=2)
    assert len(serial) == len(pooled) == 3
    for (d0, l0), (d1, l1) in zip(serial, pooled):
        np.testing.assert_array_equal(l0, l1)
        np.testing.assert_allclose(d0, d1)


def test_process_pool_requires_recordio(tmp_path):
    lst = tmp_path / "x.lst"
    lst.write_text("0\t1.0\tnope.jpg\n")
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        ImageIter(2, (3, 8, 8), path_imglist=str(lst),
                  preprocess_procs=2)
