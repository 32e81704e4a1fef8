"""SSD model tests (reference example/ssd — symbol structure and a
miniature end-to-end train/detect cycle)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.models import ssd


def test_ssd300_symbol_shapes():
    net = ssd.get_symbol_train(num_classes=3)
    _, outs, _ = net.infer_shape(data=(1, 3, 300, 300), label=(1, 4, 5))
    a = outs[0][2]
    assert outs[0] == (1, 4, a)          # cls_prob (B, C+1, A)
    assert outs[1] == (1, a * 4)         # loc_loss
    assert outs[2] == (1, a)             # cls_label
    det = ssd.get_symbol(num_classes=3)
    _, o2, _ = det.infer_shape(data=(1, 3, 300, 300))
    assert o2 == [(1, a, 6)]


def _mini_ssd_train(num_classes=2):
    """Tiny single-scale SSD head on an 8x8 feature map."""
    data = sym.Variable('data')
    feat = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                           name='feat_conv')
    feat = sym.Activation(feat, act_type='relu')
    loc_preds, cls_preds, anchors = ssd.multibox_layer(
        [feat], num_classes, sizes=[[0.3, 0.4]], ratios=[[1, 2]])
    label = sym.Variable('label')
    loc_t, loc_m, cls_t = sym.MultiBoxTarget(
        anchors, label, cls_preds, overlap_threshold=0.5,
        negative_mining_ratio=3, negative_mining_thresh=0.5,
        name='multibox_target')
    cls_prob = sym.SoftmaxOutput(cls_preds, cls_t, ignore_label=-1,
                                 use_ignore=True, multi_output=True,
                                 normalization='valid', name='cls_prob')
    loc_loss = sym.MakeLoss(sym.smooth_l1(loc_m * (loc_preds - loc_t),
                                          scalar=1.0),
                            normalization='valid', name='loc_loss')
    return sym.Group([cls_prob, loc_loss])


def test_mini_ssd_trains():
    # Xavier draws from the process's stream, which the tests a worker
    # ran before have advanced: one draw in some dozens diverges
    mx.random.seed(0)
    net = _mini_ssd_train()
    mod = mx.mod.Module(net, data_names=('data',), label_names=('label',))
    B = 2
    mod.bind(data_shapes=[mx.io.DataDesc('data', (B, 3, 8, 8))],
             label_shapes=[mx.io.DataDesc('label', (B, 2, 5))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1})
    rs = np.random.RandomState(0)
    x = rs.rand(B, 3, 8, 8).astype(np.float32)
    lab = np.full((B, 2, 5), -1, np.float32)
    lab[:, 0] = [0, 0.2, 0.2, 0.6, 0.6]      # one gt box, class 0
    batch = mx.io.DataBatch(data=[nd.array(x)], label=[nd.array(lab)])
    losses = []
    for _ in range(10):
        mod.forward_backward(batch)
        mod.update()
        out = mod.get_outputs()
        losses.append(float(out[1].asnumpy().sum()))
    assert np.isfinite(losses).all()
    assert losses[-1] <= losses[0] + 1e-3    # loc loss not diverging


def test_mini_ssd_detect():
    """Detection path produces sane, thresholded, NMS'd output."""
    data = sym.Variable('data')
    feat = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                           name='feat_conv')
    loc_preds, cls_preds, anchors = ssd.multibox_layer(
        [feat], 2, sizes=[[0.3, 0.4]], ratios=[[1, 2]])
    cls_prob = sym.softmax(cls_preds, axis=1)
    det = sym.MultiBoxDetection(cls_prob, loc_preds, anchors,
                                nms_threshold=0.5, threshold=0.2)
    ex = det.simple_bind(mx.cpu(), data=(1, 3, 8, 8), grad_req='null')
    for k, v in ex.arg_dict.items():
        if k != 'data':
            v[:] = np.random.RandomState(0).rand(*v.shape).astype(
                np.float32) * 0.1
    ex.arg_dict['data'][:] = np.random.RandomState(1).rand(
        1, 3, 8, 8).astype(np.float32)
    out = ex.forward(is_train=False)[0].asnumpy()
    assert out.shape[2] == 6
    kept = out[0][out[0, :, 0] >= 0]
    if len(kept):
        assert (kept[:, 1] >= 0.2 - 1e-6).all()
        assert (kept[:, 2:] >= -1e-5).all() and (kept[:, 2:] <= 1 + 1e-5).all()
