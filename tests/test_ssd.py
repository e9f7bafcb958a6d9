"""SSD end-to-end: symbol wiring, target matching, decode geometry, and a
training smoke gate (loss decreases) on synthetic detection data.

Ref: example/ssd/symbol/symbol_vgg16_ssd_300.py:124-155 (head wiring),
example/ssd/train.py. The convergence-to-mAP run lives in
example/ssd/train.py --min-map (too slow for unit CI).
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import ssd as ssd_model

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "example", "ssd"))
from train import MultiBoxMetric, synth_det_batch, voc_map  # noqa: E402


def test_train_symbol_shapes():
    net = ssd_model.get_symbol_train(num_classes=3, width=16)
    _, out, _ = net.infer_shape(data=(2, 3, 128, 128), label=(2, 4, 5))
    names = net.list_outputs()
    shapes = dict(zip(names, out))
    A = shapes["cls_label_output"][1]
    assert shapes["cls_prob_output"] == (2, 4, A)        # 3 classes + bg
    assert shapes["loc_loss_output"] == (2, 4 * A)
    assert shapes["det_out_output"] == (2, A, 6)


def test_eval_symbol_runs():
    net = ssd_model.get_symbol(num_classes=3, width=16)
    ex = net.simple_bind(mx.cpu(), data=(1, 3, 128, 128))
    ex.forward(is_train=False)
    det = ex.outputs[0].asnumpy()
    assert det.shape[2] == 6


def test_perfect_prediction_decodes_to_gt():
    """cls one-hot of targets + loc == loc_target must reproduce the gt box
    through MultiBoxDetection (decode+NMS geometry)."""
    anc = []
    for cy in np.linspace(0.1, 0.9, 8):
        for cx in np.linspace(0.1, 0.9, 8):
            for s in (0.2, 0.4):
                anc.append([cx - s / 2, cy - s / 2, cx + s / 2, cy + s / 2])
    anc = np.array(anc, np.float32)[None]
    A = anc.shape[1]
    gt = np.array([[[1, 0.3, 0.3, 0.62, 0.58], [-1, 0, 0, 0, 0]]],
                  np.float32)
    cls_pred = np.zeros((1, 3, A), np.float32)
    loc_t, _, cls_t = [x.asnumpy() for x in mx.nd.MultiBoxTarget(
        mx.nd.array(anc), mx.nd.array(gt), mx.nd.array(cls_pred),
        overlap_threshold=0.5, variances="0.1,0.1,0.2,0.2")]
    assert (cls_t > 0).sum() >= 1
    probs = np.zeros((1, 3, A), np.float32)
    probs[0, 0, :] = 1.0
    for a in range(A):
        if cls_t[0, a] > 0:
            probs[0, 0, a] = 0.0
            probs[0, int(cls_t[0, a]), a] = 1.0
    det = mx.nd.MultiBoxDetection(
        mx.nd.array(probs), mx.nd.array(loc_t.reshape(1, -1)),
        mx.nd.array(anc), nms_threshold=0.5,
        variances="0.1,0.1,0.2,0.2").asnumpy()
    kept = det[0][det[0, :, 0] >= 0]
    assert len(kept) == 1
    assert int(kept[0, 0]) == 1 and kept[0, 1] > 0.9
    np.testing.assert_allclose(kept[0, 2:], [0.3, 0.3, 0.62, 0.58],
                               atol=1e-5)


def test_multibox_pallas_kernel_matches_xla_sweep(monkeypatch):
    """MXTPU_PALLAS_MULTIBOX: the VMEM-resident NMS kernel (run in the
    Pallas interpreter here; compiled on the chip under ``1``) keeps
    exactly the anchors the XLA fori_loop sweep keeps — overlapping boxes
    of one class, of two classes, and dead (zero-score) anchors, batched
    the way MultiBoxDetection vmaps it."""
    rs = np.random.RandomState(3)
    A = 24
    c = rs.rand(A, 2) * 0.4 + 0.3
    wh = rs.rand(A, 2) * 0.3 + 0.1
    anc = np.concatenate([c - wh / 2, c + wh / 2], 1).astype(np.float32)[None]
    probs = rs.rand(2, 3, A).astype(np.float32)
    probs[:, 1:, -4:] = 0.0                  # anchors below the threshold
    probs /= probs.sum(axis=1, keepdims=True)
    loc = (rs.randn(2, A * 4) * 0.1).astype(np.float32)

    def detect():
        return mx.nd.MultiBoxDetection(
            mx.nd.array(probs), mx.nd.array(loc), mx.nd.array(anc),
            nms_threshold=0.3, threshold=0.05).asnumpy()

    ref = detect()
    monkeypatch.setenv("MXTPU_PALLAS_MULTIBOX", "interpret")
    got = detect()
    assert (ref[:, :, 0] < 0).any() and (ref[:, :, 0] >= 0).any()
    np.testing.assert_array_equal(got, ref)


def test_ssd_training_smoke_loss_decreases():
    # Xavier and the iterator's shuffle draw from the global stream: left
    # unseeded, whatever ran before in this worker decides the run, and 1
    # seed in 16 does not reach 0.8 in 16 epochs (seed 15; PR 30)
    mx.random.seed(0)
    rng = np.random.default_rng(0)
    imgs, labels = synth_det_batch(rng, 32, 96, 3)
    it = mx.io.NDArrayIter(imgs, labels, batch_size=16, shuffle=True,
                           label_name="label")
    net = ssd_model.get_symbol_train(num_classes=3, width=8)
    mod = mx.mod.Module(net, data_names=("data",), label_names=("label",))
    metric = MultiBoxMetric()
    losses = []
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    # adam: converges on the synthetic task in tens of steps where SGD
    # needs a long schedule (measured in example/ssd)
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3,
                                         "rescale_grad": 1.0})
    for _epoch in range(16):
        it.reset()
        metric.reset()
        for b in it:
            mod.forward_backward(b)
            mod.update()
            mod.update_metric(metric, b.label)
        losses.append(metric.get()[1][0])      # cross-entropy
    assert losses[-1] < losses[0] * 0.8, \
        "SSD cls loss did not decrease: %s" % losses
    assert all(np.isfinite(losses)), losses


def test_voc_map_helper():
    gt = [np.array([[0, 0.1, 0.1, 0.5, 0.5]], np.float32)]
    perfect = [np.array([[0, 0.99, 0.1, 0.1, 0.5, 0.5]], np.float32)]
    wrong = [np.array([[0, 0.99, 0.6, 0.6, 0.9, 0.9]], np.float32)]
    assert voc_map(perfect, gt, 1) > 0.99
    assert voc_map(wrong, gt, 1) < 0.01


def test_det_iter_feeds_ssd(tmp_path):
    """ImageDetIter batch shapes slot into the SSD train symbol."""
    pytest.importorskip("PIL.Image")
    import io as _io
    from PIL import Image
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "det.rec")
    idx = str(tmp_path / "det.idx")
    w = recordio.MXIndexedRecordIO(idx, rec_path, "w")
    rng = np.random.default_rng(0)
    for i in range(8):
        img = (rng.random((96, 96, 3)) * 255).astype(np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG")
        # det array label: [header_width=2, obj_width=5, cls,x1,y1,x2,y2]
        label = np.array([2, 5, 0, 0.2, 0.2, 0.6, 0.6], np.float32)
        w.write_idx(i, recordio.pack(recordio.IRHeader(0, label, i, 0),
                                     buf.getvalue()))
    w.close()
    it = mx.image.ImageDetIter(batch_size=4, data_shape=(3, 96, 96),
                               path_imgrec=rec_path)
    b = it.next()
    assert b.data[0].shape == (4, 3, 96, 96)
    lab = b.label[0].asnumpy()
    assert lab.ndim == 3 and lab.shape[2] == 5
    net = ssd_model.get_symbol_train(num_classes=3, width=8)
    _, out, _ = net.infer_shape(data=tuple(b.data[0].shape),
                                label=tuple(lab.shape))
    assert out is not None
