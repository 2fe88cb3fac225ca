#!/usr/bin/env python
"""Model zoo generator — programmatically emits the prototxt zoo using
NetSpec (the reference keeps equivalent python generators in
models/modelBuilder/). Run from the repo root:

    python models/generate_models.py

Topologies follow the reference zoo: bvlc_alexnet, CIFAR-10 quick,
GoogLeNet (inception v1), ResNet-50 (bottleneck [3,4,6,3], NVCaffe
fused-scale BatchNorm). Inputs are Input layers (feed-based); the data
pipeline binds real datasets at run time.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from caffe_mpi_tpu.net_spec import L, NetSpec


def train_test_tail(n, logits, include_train_loss=True):
    n.loss = L.SoftmaxWithLoss(logits, n.label,
                               include=dict(phase="TRAIN"))
    n.accuracy = L.Accuracy(logits, n.label, include=dict(phase="TEST"))
    n.accuracy_top5 = L.Accuracy(logits, n.label, top_k=5,
                                 include=dict(phase="TEST"))


def conv_relu(bottom, nout, ks, stride=1, pad=0, group=1):
    c = L.Convolution(bottom, num_output=nout, kernel_size=ks, stride=stride,
                      pad=pad, group=group,
                      weight_filler=dict(type="gaussian", std=0.01),
                      bias_filler=dict(type="constant"),
                      param=[dict(lr_mult=1, decay_mult=1),
                             dict(lr_mult=2, decay_mult=0)])
    return c, L.ReLU(c, in_place=True)


def alexnet(batch=256):
    """bvlc_alexnet topology (reference models/bvlc_alexnet)."""
    n = NetSpec("AlexNet")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 227, 227]), dict(dim=[batch])]))
    n.conv1, n.relu1 = conv_relu(n.data, 96, 11, stride=4)
    n.norm1 = L.LRN(n.relu1, local_size=5, alpha=1e-4, beta=0.75)
    n.pool1 = L.Pooling(n.norm1, pool="MAX", kernel_size=3, stride=2)
    n.conv2, n.relu2 = conv_relu(n.pool1, 256, 5, pad=2, group=2)
    n.norm2 = L.LRN(n.relu2, local_size=5, alpha=1e-4, beta=0.75)
    n.pool2 = L.Pooling(n.norm2, pool="MAX", kernel_size=3, stride=2)
    n.conv3, n.relu3 = conv_relu(n.pool2, 384, 3, pad=1)
    n.conv4, n.relu4 = conv_relu(n.relu3, 384, 3, pad=1, group=2)
    n.conv5, n.relu5 = conv_relu(n.relu4, 256, 3, pad=1, group=2)
    n.pool5 = L.Pooling(n.relu5, pool="MAX", kernel_size=3, stride=2)
    n.fc6 = L.InnerProduct(n.pool5, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=0.1))
    n.relu6 = L.ReLU(n.fc6, in_place=True)
    n.drop6 = L.Dropout(n.fc6, dropout_ratio=0.5, in_place=True)
    n.fc7 = L.InnerProduct(n.fc6, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=0.1))
    n.relu7 = L.ReLU(n.fc7, in_place=True)
    n.drop7 = L.Dropout(n.fc7, dropout_ratio=0.5, in_place=True)
    n.fc8 = L.InnerProduct(n.fc7, num_output=1000,
                           weight_filler=dict(type="gaussian", std=0.01),
                           bias_filler=dict(type="constant"))
    train_test_tail(n, n.fc8)
    return n


def cifar10_quick(batch=100):
    """CIFAR-10 quick (reference examples/cifar10)."""
    n = NetSpec("CIFAR10_quick")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 32, 32]), dict(dim=[batch])]))
    n.conv1 = L.Convolution(n.data, num_output=32, kernel_size=5, pad=2,
                            weight_filler=dict(type="gaussian", std=0.0001),
                            param=[dict(lr_mult=1), dict(lr_mult=2)])
    n.pool1 = L.Pooling(n.conv1, pool="MAX", kernel_size=3, stride=2)
    n.relu1 = L.ReLU(n.pool1, in_place=True)
    n.conv2 = L.Convolution(n.pool1, num_output=32, kernel_size=5, pad=2,
                            weight_filler=dict(type="gaussian", std=0.01),
                            param=[dict(lr_mult=1), dict(lr_mult=2)])
    n.relu2 = L.ReLU(n.conv2, in_place=True)
    n.pool2 = L.Pooling(n.conv2, pool="AVE", kernel_size=3, stride=2)
    n.conv3 = L.Convolution(n.pool2, num_output=64, kernel_size=5, pad=2,
                            weight_filler=dict(type="gaussian", std=0.01),
                            param=[dict(lr_mult=1), dict(lr_mult=2)])
    n.relu3 = L.ReLU(n.conv3, in_place=True)
    n.pool3 = L.Pooling(n.conv3, pool="AVE", kernel_size=3, stride=2)
    n.ip1 = L.InnerProduct(n.pool3, num_output=64,
                           weight_filler=dict(type="gaussian", std=0.1),
                           param=[dict(lr_mult=1), dict(lr_mult=2)])
    n.ip2 = L.InnerProduct(n.ip1, num_output=10,
                           weight_filler=dict(type="gaussian", std=0.1),
                           param=[dict(lr_mult=1), dict(lr_mult=2)])
    train_test_tail(n, n.ip2)
    return n


def inception(n, name, bottom, o1, o3r, o3, o5r, o5, op):
    """GoogLeNet inception module (reference layer names:
    inception_Xy/1x1 etc., so reference .caffemodel weights load by name)."""
    def cr(branch, b, nout, ks, pad=0):
        c = L.Convolution(b, num_output=nout, kernel_size=ks, pad=pad,
                          weight_filler=dict(type="xavier"),
                          bias_filler=dict(type="constant", value=0.2),
                          param=[dict(lr_mult=1, decay_mult=1),
                                 dict(lr_mult=2, decay_mult=0)])
        r = L.ReLU(c, in_place=True)
        setattr(n, f"{name}/{branch}", c)
        setattr(n, f"{name}/relu_{branch}", r)
        return r

    c1 = cr("1x1", bottom, o1, 1)
    c3r = cr("3x3_reduce", bottom, o3r, 1)
    c3 = cr("3x3", c3r, o3, 3, pad=1)
    c5r = cr("5x5_reduce", bottom, o5r, 1)
    c5 = cr("5x5", c5r, o5, 5, pad=2)
    pool = L.Pooling(bottom, pool="MAX", kernel_size=3, stride=1, pad=1)
    setattr(n, f"{name}/pool", pool)
    cp = cr("pool_proj", pool, op, 1)
    out = L.Concat(c1, c3, c5, cp)
    setattr(n, f"{name}/output", out)
    return out


def _googlenet_aux(n, prefix, bottom, label):
    """Aux classifier head (reference loss1/* and loss2/*)."""
    pool = L.Pooling(bottom, pool="AVE", kernel_size=5, stride=3)
    setattr(n, f"{prefix}/ave_pool", pool)
    c = L.Convolution(pool, num_output=128, kernel_size=1,
                      weight_filler=dict(type="xavier"),
                      bias_filler=dict(type="constant", value=0.2),
                      param=[dict(lr_mult=1, decay_mult=1),
                             dict(lr_mult=2, decay_mult=0)])
    setattr(n, f"{prefix}/conv", c)
    setattr(n, f"{prefix}/relu_conv", L.ReLU(c, in_place=True))
    fc = L.InnerProduct(c, num_output=1024,
                        weight_filler=dict(type="xavier"),
                        bias_filler=dict(type="constant", value=0.2),
                        param=[dict(lr_mult=1, decay_mult=1),
                               dict(lr_mult=2, decay_mult=0)])
    setattr(n, f"{prefix}/fc", fc)
    setattr(n, f"{prefix}/relu_fc", L.ReLU(fc, in_place=True))
    setattr(n, f"{prefix}/drop_fc", L.Dropout(fc, dropout_ratio=0.7,
                                              in_place=True))
    cls = L.InnerProduct(fc, num_output=1000,
                         weight_filler=dict(type="xavier"),
                         bias_filler=dict(type="constant"),
                         param=[dict(lr_mult=1, decay_mult=1),
                                dict(lr_mult=2, decay_mult=0)])
    setattr(n, f"{prefix}/classifier", cls)
    setattr(n, f"{prefix}/loss", L.SoftmaxWithLoss(
        cls, label, loss_weight=0.3, include=dict(phase="TRAIN")))
    setattr(n, f"{prefix}/top-1", L.Accuracy(cls, label,
                                             include=dict(phase="TEST")))
    setattr(n, f"{prefix}/top-5", L.Accuracy(cls, label, top_k=5,
                                             include=dict(phase="TEST")))


def googlenet(batch=128):
    """bvlc_googlenet (reference models/bvlc_googlenet/train_val.prototxt):
    9 inception modules, loss1/loss2 aux heads at weight 0.3, reference
    layer names throughout."""
    n = NetSpec("GoogLeNet")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 224, 224]), dict(dim=[batch])]))

    def cr(name, b, nout, ks, stride=1, pad=0):
        c = L.Convolution(b, num_output=nout, kernel_size=ks, stride=stride,
                          pad=pad, weight_filler=dict(type="xavier"),
                          bias_filler=dict(type="constant", value=0.2),
                          param=[dict(lr_mult=1, decay_mult=1),
                                 dict(lr_mult=2, decay_mult=0)])
        r = L.ReLU(c, in_place=True)
        setattr(n, name, c)
        setattr(n, f"{name.rsplit('/', 1)[0]}/relu_{name.rsplit('/', 1)[1]}", r)
        return r

    x = cr("conv1/7x7_s2", n.data, 64, 7, stride=2, pad=3)
    setattr(n, "pool1/3x3_s2", L.Pooling(x, pool="MAX", kernel_size=3, stride=2))
    setattr(n, "pool1/norm1", L.LRN(getattr(n, "pool1/3x3_s2"),
                                    local_size=5, alpha=1e-4, beta=0.75))
    x = cr("conv2/3x3_reduce", getattr(n, "pool1/norm1"), 64, 1)
    x = cr("conv2/3x3", x, 192, 3, pad=1)
    setattr(n, "conv2/norm2", L.LRN(x, local_size=5, alpha=1e-4, beta=0.75))
    setattr(n, "pool2/3x3_s2", L.Pooling(getattr(n, "conv2/norm2"),
                                         pool="MAX", kernel_size=3, stride=2))
    x = inception(n, "inception_3a", getattr(n, "pool2/3x3_s2"),
                  64, 96, 128, 16, 32, 32)
    x = inception(n, "inception_3b", x, 128, 128, 192, 32, 96, 64)
    setattr(n, "pool3/3x3_s2", L.Pooling(x, pool="MAX", kernel_size=3, stride=2))
    x = inception(n, "inception_4a", getattr(n, "pool3/3x3_s2"),
                  192, 96, 208, 16, 48, 64)
    _googlenet_aux(n, "loss1", x, n.label)
    x = inception(n, "inception_4b", x, 160, 112, 224, 24, 64, 64)
    x = inception(n, "inception_4c", x, 128, 128, 256, 24, 64, 64)
    x = inception(n, "inception_4d", x, 112, 144, 288, 32, 64, 64)
    _googlenet_aux(n, "loss2", x, n.label)
    x = inception(n, "inception_4e", x, 256, 160, 320, 32, 128, 128)
    setattr(n, "pool4/3x3_s2", L.Pooling(x, pool="MAX", kernel_size=3, stride=2))
    x = inception(n, "inception_5a", getattr(n, "pool4/3x3_s2"),
                  256, 160, 320, 32, 128, 128)
    x = inception(n, "inception_5b", x, 384, 192, 384, 48, 128, 128)
    setattr(n, "pool5/7x7_s1", L.Pooling(x, pool="AVE", kernel_size=7, stride=1))
    setattr(n, "pool5/drop_7x7_s1", L.Dropout(getattr(n, "pool5/7x7_s1"),
                                              dropout_ratio=0.4, in_place=True))
    cls = L.InnerProduct(getattr(n, "pool5/7x7_s1"), num_output=1000,
                         weight_filler=dict(type="xavier"),
                         bias_filler=dict(type="constant"),
                         param=[dict(lr_mult=1, decay_mult=1),
                                dict(lr_mult=2, decay_mult=0)])
    setattr(n, "loss3/classifier", cls)
    setattr(n, "loss3/loss3", L.SoftmaxWithLoss(
        cls, n.label, include=dict(phase="TRAIN")))
    setattr(n, "loss3/top-1", L.Accuracy(cls, n.label,
                                         include=dict(phase="TEST")))
    setattr(n, "loss3/top-5", L.Accuracy(cls, n.label, top_k=5,
                                         include=dict(phase="TEST")))
    return n


def _resnet(n, batch, stages, bottleneck):
    """Shared ResNet body emitter with the reference's layer names
    (res{stage}.{block}.conv{i} / .skipConv / .sum, X/bn, fc — see
    models/resnet50/train_val.prototxt): fused scale_bias BN, eps 1e-4,
    msra fillers, stride on the block's first conv."""
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 224, 224]), dict(dim=[batch])]))

    def cb(name, b, nout, ks, stride=1, pad=0, relu=True):
        return conv_bn_relu(n, name, b, nout, ks, stride=stride, pad_h=pad,
                            filler="msra", relu=relu)

    x = cb("conv1", n.data, 64, 7, stride=2, pad=3)
    n.pool1 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    x = n.pool1
    for si, (nout, blocks) in enumerate(stages):
        for bi in range(1, blocks + 1):
            prefix = f"res{si + 2}.{bi}"
            stride = 2 if (si > 0 and bi == 1) else 1
            x = bottleneck(n, prefix, x, nout, stride, cb,
                           project=(bi == 1))
    n.pool5 = L.Pooling(x, pool="AVE", global_pooling=True)
    n.fc = L.InnerProduct(n.pool5, num_output=1000,
                          weight_filler=dict(type="msra"),
                          bias_filler=dict(type="constant"),
                          param=[dict(lr_mult=1, decay_mult=1),
                                 dict(lr_mult=2, decay_mult=0)])
    train_test_tail(n, n.fc)
    return n


def _bottleneck50(n, prefix, b, nout, stride, cb, project):
    if project:
        sc = cb(f"{prefix}.skipConv", b, nout * 4, 1, stride=stride,
                relu=False)
    else:
        sc = b
    x = cb(f"{prefix}.conv1", b, nout, 1, stride=stride)
    x = cb(f"{prefix}.conv2", x, nout, 3, pad=1)
    x = cb(f"{prefix}.conv3", x, nout * 4, 1, relu=False)
    s = L.Eltwise(x, sc, operation="SUM")
    setattr(n, f"{prefix}.sum", s)
    r = L.ReLU(s, in_place=True)
    setattr(n, f"{prefix}.relu", r)
    return r


def _basicblock18(n, prefix, b, nout, stride, cb, project):
    project = project and (stride != 1 or nout != 64)
    if project:
        sc = cb(f"{prefix}.skipConv", b, nout, 1, stride=stride, relu=False)
    else:
        sc = b
    x = cb(f"{prefix}.conv1", b, nout, 3, stride=stride, pad=1)
    x = cb(f"{prefix}.conv2", x, nout, 3, pad=1, relu=False)
    s = L.Eltwise(x, sc, operation="SUM")
    setattr(n, f"{prefix}.sum", s)
    r = L.ReLU(s, in_place=True)
    setattr(n, f"{prefix}.relu", r)
    return r


def resnet50(batch=32):
    """ResNet-50 (reference models/resnet50/train_val.prototxt): bottleneck
    [3,4,6,3] with reference layer names so reference weights load."""
    return _resnet(NetSpec("ResNet50"), batch,
                   [(64, 3), (128, 4), (256, 6), (512, 3)], _bottleneck50)


def resnet18(batch=64):
    """ResNet-18 (reference models/resnet18/train_val.prototxt): basic
    blocks [2,2,2,2], projection only on downsampling stages."""
    return _resnet(NetSpec("ResNet18"), batch,
                   [(64, 2), (128, 2), (256, 2), (512, 2)], _basicblock18)


def conv_bn_relu(n, name, bottom, nout, kh, kw=None, stride=1, pad_h=0,
                 pad_w=None, group=1, eps=1e-4, filler="xavier", relu=True):
    """conv (bias-free) -> BatchNorm (separate top, fused scale/bias,
    eps 1e-4 like the reference BN zoo models) -> in-place ReLU.
    Shared by alexnet_bn / inception_v3 / cifar10_nv generators."""
    kw = kh if kw is None else kw
    pad_w = pad_h if pad_w is None else pad_w
    kwargs = dict(num_output=nout, bias_term=False,
                  weight_filler=dict(type=filler),
                  param=[dict(lr_mult=1, decay_mult=1)])
    if kh == kw:
        kwargs.update(kernel_size=kh)
    else:
        kwargs.update(kernel_h=kh, kernel_w=kw)
    if stride != 1:
        kwargs.update(stride=stride)
    if pad_h == pad_w:
        if pad_h:
            kwargs.update(pad=pad_h)
    else:
        kwargs.update(pad_h=pad_h, pad_w=pad_w)
    if group != 1:
        kwargs.update(group=group)
    c = L.Convolution(bottom, **kwargs)
    bn = L.BatchNorm(c, scale_bias=True, eps=eps,
                     moving_average_fraction=0.9)
    setattr(n, name, c)
    setattr(n, f"{name}/bn", bn)
    if not relu:
        return bn
    r = L.ReLU(bn, in_place=True)
    setattr(n, f"{name}/relu", r)
    return r


def alexnet_bn(batch=256):
    """AlexNet with BatchNorm after each conv (reference models/alexnet_bn;
    BN eps 1e-4 per its train_val.prototxt)."""
    n = NetSpec("AlexNet_BN")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 227, 227]), dict(dim=[batch])]))

    def cbr(name, b, nout, ks, stride=1, pad=0, group=1):
        return conv_bn_relu(n, name, b, nout, ks, stride=stride, pad_h=pad,
                            group=group, filler="msra")

    x = cbr("conv1", n.data, 96, 11, stride=4)
    n.pool1 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    x = cbr("conv2", n.pool1, 256, 5, pad=2, group=2)
    n.pool2 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    x = cbr("conv3", n.pool2, 384, 3, pad=1)
    x = cbr("conv4", x, 384, 3, pad=1, group=2)
    x = cbr("conv5", x, 256, 3, pad=1, group=2)
    n.pool5 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    n.fc6 = L.InnerProduct(n.pool5, num_output=4096,
                           weight_filler=dict(type="msra"),
                           bias_filler=dict(type="constant"))
    n.relu6 = L.ReLU(n.fc6, in_place=True)
    n.drop6 = L.Dropout(n.fc6, dropout_ratio=0.5, in_place=True)
    n.fc7 = L.InnerProduct(n.fc6, num_output=4096,
                           weight_filler=dict(type="msra"),
                           bias_filler=dict(type="constant"))
    n.relu7 = L.ReLU(n.fc7, in_place=True)
    n.drop7 = L.Dropout(n.fc7, dropout_ratio=0.5, in_place=True)
    n.fc8 = L.InnerProduct(n.fc7, num_output=1000,
                           weight_filler=dict(type="gaussian", std=0.01),
                           bias_filler=dict(type="constant"))
    train_test_tail(n, n.fc8)
    return n


def alexnet_owt(batch=256):
    """AlexNet "One Weird Trick" variant (reference models/alexnet_owt):
    single-tower — no LRN, no grouped convolutions; otherwise the
    bvlc_alexnet channel plan."""
    n = NetSpec("AlexNet-OWT")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 227, 227]), dict(dim=[batch])]))
    n.conv1, n.relu1 = conv_relu(n.data, 96, 11, stride=4)
    n.pool1 = L.Pooling(n.relu1, pool="MAX", kernel_size=3, stride=2)
    n.conv2, n.relu2 = conv_relu(n.pool1, 256, 5, pad=2)
    n.pool2 = L.Pooling(n.relu2, pool="MAX", kernel_size=3, stride=2)
    n.conv3, n.relu3 = conv_relu(n.pool2, 384, 3, pad=1)
    n.conv4, n.relu4 = conv_relu(n.relu3, 384, 3, pad=1)
    n.conv5, n.relu5 = conv_relu(n.relu4, 256, 3, pad=1)
    n.pool5 = L.Pooling(n.relu5, pool="MAX", kernel_size=3, stride=2)
    n.fc6 = L.InnerProduct(n.pool5, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=0.1))
    n.relu6 = L.ReLU(n.fc6, in_place=True)
    n.drop6 = L.Dropout(n.fc6, dropout_ratio=0.5, in_place=True)
    n.fc7 = L.InnerProduct(n.fc6, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=0.1))
    n.relu7 = L.ReLU(n.fc7, in_place=True)
    n.drop7 = L.Dropout(n.fc7, dropout_ratio=0.5, in_place=True)
    n.fc8 = L.InnerProduct(n.fc7, num_output=1000,
                           weight_filler=dict(type="gaussian", std=0.01),
                           bias_filler=dict(type="constant"))
    train_test_tail(n, n.fc8)
    return n


def inception_v2(batch=32):
    """Inception-v2 / BN-GoogLeNet (reference models/inception_v2/
    train_val.prototxt): GoogLeNet shape with BatchNorm (separate /bn top,
    fused scale+bias, eps 1e-4, maf 0.9) after every conv, the 5x5 branch
    conv named '5x5b', stride-2 reduction blocks 3c/4e (no 1x1 branch,
    MAX pool, no pool_proj), 5b's block pool is MAX, aux heads after
    3c and 4e at loss_weight 0.3. Reference layer names throughout so
    reference .caffemodel weights load by name."""
    n = NetSpec("Inception_v2")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 224, 224]), dict(dim=[batch])]))

    def cbr(name, b, nout, ks, stride=1, pad=0):
        bn = conv_bn_relu(n, name, b, nout, ks, stride=stride, pad_h=pad,
                          relu=False)
        r = L.ReLU(bn, in_place=True)
        setattr(n, f"{name}/bn/relu", r)
        return r

    def block(name, bottom, o1, o3r, o3, o5r, o5, op, pool="AVE"):
        c1 = cbr(f"{name}/1x1", bottom, o1, 1)
        c3r = cbr(f"{name}/3x3_reduce", bottom, o3r, 1)
        c3 = cbr(f"{name}/3x3", c3r, o3, 3, pad=1)
        c5r = cbr(f"{name}/5x5_reduce", bottom, o5r, 1)
        c5 = cbr(f"{name}/5x5b", c5r, o5, 5, pad=2)
        p = L.Pooling(bottom, pool=pool, kernel_size=3, stride=1, pad=1)
        setattr(n, f"{name}/pool", p)
        cp = cbr(f"{name}/pool_proj", p, op, 1)
        out = L.Concat(c1, c3, c5, cp)
        setattr(n, f"{name}/output", out)
        return out

    def reduce_block(name, bottom, o3r, o3, o5r, o5):
        """Stride-2 grid reduction: 3x3 and 5x5b branches at stride 2 +
        a MAX-pool passthrough; no 1x1/pool_proj branches."""
        c3r = cbr(f"{name}/3x3_reduce", bottom, o3r, 1)
        c3 = cbr(f"{name}/3x3", c3r, o3, 3, stride=2, pad=1)
        c5r = cbr(f"{name}/5x5_reduce", bottom, o5r, 1)
        c5 = cbr(f"{name}/5x5b", c5r, o5, 5, stride=2, pad=2)
        p = L.Pooling(bottom, pool="MAX", kernel_size=3, stride=2)
        setattr(n, f"{name}/pool", p)
        out = L.Concat(c3, c5, p)
        setattr(n, f"{name}/output", out)
        return out

    def aux_head(prefix, pool_name, bottom):
        p = L.Pooling(bottom, pool="AVE", kernel_size=5, stride=3)
        setattr(n, pool_name, p)
        c = cbr(f"{prefix}/conv", p, 128, 1)
        fc = L.InnerProduct(c, num_output=1024,
                            weight_filler=dict(type="xavier"),
                            bias_filler=dict(type="constant"))
        setattr(n, f"{prefix}/fc", fc)
        setattr(n, f"{prefix}/fc/relu", L.ReLU(fc, in_place=True))
        cls = L.InnerProduct(fc, num_output=1000,
                             weight_filler=dict(type="xavier"),
                             bias_filler=dict(type="constant"))
        setattr(n, f"{prefix}/classifier", cls)
        setattr(n, f"{prefix}/loss", L.SoftmaxWithLoss(
            cls, n.label, loss_weight=0.3, include=dict(phase="TRAIN")))
        prob = L.Softmax(cls, include=dict(phase="TEST"))
        setattr(n, f"{prefix}/prob", prob)
        setattr(n, f"{prefix}/top-1", L.Accuracy(
            prob, n.label, include=dict(phase="TEST")))
        setattr(n, f"{prefix}/top-5", L.Accuracy(
            prob, n.label, top_k=5, include=dict(phase="TEST")))

    x = cbr("conv1/7x7_s2", n.data, 64, 7, stride=2, pad=3)
    p1 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    setattr(n, "pool1/3x3_s2", p1)
    x = cbr("conv2/3x3_reduce", p1, 64, 1)
    x = cbr("conv2/3x3", x, 192, 3, pad=1)
    p2 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    setattr(n, "pool2/3x3_s2", p2)

    x = block("inception_3a", p2, 64, 64, 64, 64, 96, 32)
    x = block("inception_3b", x, 64, 64, 96, 64, 96, 64)
    x = reduce_block("inception_3c", x, 128, 160, 64, 96)
    aux_head("loss1", "pool3/5x5_s3", x)
    x = block("inception_4a", x, 224, 64, 96, 96, 128, 128)
    x = block("inception_4b", x, 192, 96, 128, 96, 128, 128)
    x = block("inception_4c", x, 160, 128, 160, 128, 160, 96)
    x = block("inception_4d", x, 96, 128, 192, 160, 192, 96)
    x = reduce_block("inception_4e", x, 128, 192, 192, 256)
    aux_head("loss2", "pool4/5x5_s3", x)
    x = block("inception_5a", x, 352, 192, 320, 160, 224, 128)
    x = block("inception_5b", x, 352, 192, 320, 192, 224, 128, pool="MAX")

    p5 = L.Pooling(x, pool="AVE", kernel_size=7, stride=1)
    setattr(n, "pool5/7x7_s1", p5)
    cls = L.InnerProduct(p5, num_output=1000,
                         weight_filler=dict(type="xavier"),
                         bias_filler=dict(type="constant"))
    setattr(n, "loss3/classifier", cls)
    n.loss = L.SoftmaxWithLoss(cls, n.label)
    setattr(n, "accuracy/top-1", L.Accuracy(cls, n.label,
                                            include=dict(phase="TEST")))
    setattr(n, "accuracy/top-5", L.Accuracy(cls, n.label, top_k=5,
                                            include=dict(phase="TEST")))
    return n


def inception_v3(batch=32):
    """Inception v3, faithful to reference models/inception_v3/train_val
    .prototxt: its NVCaffe stem (conv4=80 3x3, conv5=192 3x3/s2, conv6=288,
    ONE stem maxpool), blocks 3A-3C / 4A-4E (ch7 128,160,160,192,192) /
    5A-5B, reductions 3R/4R, aux heads loss1/loss2 (weight 0.3) after the
    reductions, AVE k7 tail pool, reference layer names (e.g. 3A/p2_3x3)."""
    n = NetSpec("InceptionV3")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 299, 299]), dict(dim=[batch])]))

    def cbr(name, b, nout, kh, kw=None, stride=1, pad_h=0, pad_w=None):
        return conv_bn_relu(n, name, b, nout, kh, kw, stride=stride,
                            pad_h=pad_h, pad_w=pad_w)

    def block_a(p, x):
        b1 = cbr(f"{p}/p1_1x1", x, 64, 1)
        b2 = cbr(f"{p}/p2_3x3", cbr(f"{p}/p2_1x1", x, 64, 1), 96, 3, pad_h=1)
        b3 = cbr(f"{p}/p3_1x1", x, 48, 1)
        b3 = cbr(f"{p}/p3_3x3a", b3, 64, 3, pad_h=1)
        b3 = cbr(f"{p}/p3_3x3b", b3, 64, 3, pad_h=1)
        pool = L.Pooling(x, pool="AVE", kernel_size=3, stride=1, pad=1)
        setattr(n, f"{p}/p4_pool", pool)
        b4 = cbr(f"{p}/p4_1x1", pool, 64, 1)
        out = L.Concat(b1, b2, b3, b4)
        setattr(n, f"{p}/concat", out)
        return out

    def block_b(p, x, ch7):
        b1 = cbr(f"{p}/p1_1x1", x, 192, 1)
        b2 = cbr(f"{p}/p2_1x1", x, ch7, 1)
        b2 = cbr(f"{p}/p2_1x7", b2, ch7, 1, 7, pad_h=0, pad_w=3)
        b2 = cbr(f"{p}/p2_7x1", b2, 192, 7, 1, pad_h=3, pad_w=0)
        b3 = cbr(f"{p}/p3_1x1", x, ch7, 1)
        b3 = cbr(f"{p}/p3_1x7a", b3, ch7, 1, 7, pad_h=0, pad_w=3)
        b3 = cbr(f"{p}/p3_7x1a", b3, ch7, 7, 1, pad_h=3, pad_w=0)
        b3 = cbr(f"{p}/p3_1x7b", b3, ch7, 1, 7, pad_h=0, pad_w=3)
        b3 = cbr(f"{p}/p3_7x1b", b3, 192, 7, 1, pad_h=3, pad_w=0)
        pool = L.Pooling(x, pool="AVE", kernel_size=3, stride=1, pad=1)
        setattr(n, f"{p}/p4_pool", pool)
        b4 = cbr(f"{p}/p4_1x1", pool, 192, 1)
        out = L.Concat(b1, b2, b3, b4)
        setattr(n, f"{p}/concat", out)
        return out

    def block_c(p, x):
        b1 = cbr(f"{p}/p1_1x1", x, 320, 1)
        b2r = cbr(f"{p}/p2_1x1", x, 384, 1)
        b2a = cbr(f"{p}/p2_1x3", b2r, 384, 1, 3, pad_h=0, pad_w=1)
        b2b = cbr(f"{p}/p2_3x1", b2r, 384, 3, 1, pad_h=1, pad_w=0)
        b2 = L.Concat(b2a, b2b)
        setattr(n, f"{p}/p2_concat", b2)
        b3r = cbr(f"{p}/p3_3x3", cbr(f"{p}/p3_1x1", x, 448, 1), 384, 3,
                  pad_h=1)
        b3a = cbr(f"{p}/p3_1x3", b3r, 384, 1, 3, pad_h=0, pad_w=1)
        b3b = cbr(f"{p}/p3_3x1", b3r, 384, 3, 1, pad_h=1, pad_w=0)
        b3 = L.Concat(b3a, b3b)
        setattr(n, f"{p}/p3_concat", b3)
        pool = L.Pooling(x, pool="AVE", kernel_size=3, stride=1, pad=1)
        setattr(n, f"{p}/p4_pool", pool)
        b4 = cbr(f"{p}/p4_1x1", pool, 192, 1)
        out = L.Concat(b1, b2, b3, b4)
        setattr(n, f"{p}/concat", out)
        return out

    def aux_head(p, x):
        pool = L.Pooling(x, pool="AVE", kernel_size=5, stride=3)
        setattr(n, f"{p}/pool", pool)
        conv = cbr(f"{p}/conv", pool, 128, 1)
        fc1 = L.InnerProduct(conv, num_output=1024,
                             weight_filler=dict(type="xavier"),
                             bias_filler=dict(type="constant"))
        setattr(n, f"{p}/fc1", fc1)
        setattr(n, f"{p}/fc1_relu", L.ReLU(fc1, in_place=True))
        fc2 = L.InnerProduct(fc1, num_output=1000,
                             weight_filler=dict(type="xavier"),
                             bias_filler=dict(type="constant"))
        setattr(n, f"{p}/fc2", fc2)
        setattr(n, f"{p}/loss", L.SoftmaxWithLoss(fc2, n.label,
                                                  loss_weight=0.3))
        setattr(n, f"{p}/top-1", L.Accuracy(fc2, n.label,
                                            include=dict(phase="TEST")))
        setattr(n, f"{p}/top-5", L.Accuracy(fc2, n.label, top_k=5,
                                            include=dict(phase="TEST")))

    x = cbr("conv1", n.data, 32, 3, stride=2)           # 149
    x = cbr("conv2", x, 32, 3)                          # 147
    x = cbr("conv3", x, 64, 3, pad_h=1)                 # 147
    n.pool1 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)  # 73
    x = cbr("conv4", n.pool1, 80, 3)                    # 71
    x = cbr("conv5", x, 192, 3, stride=2)               # 35
    x = cbr("conv6", x, 288, 3, pad_h=1)                # 35
    for p in ("3A", "3B", "3C"):
        x = block_a(p, x)
    # 3R reduction -> 17x17
    r1 = cbr("3R/p1_1x1", x, 64, 1)
    r1 = cbr("3R/p1_3x3a", r1, 96, 3, pad_h=1)
    r1 = cbr("3R/p1_3x3b", r1, 96, 3, stride=2)
    r2 = cbr("3R/p2_3x3", x, 384, 3, stride=2)
    rp = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    setattr(n, "3R/p3_pool", rp)
    x = L.Concat(r1, r2, rp)
    setattr(n, "3R/concat", x)
    aux_head("loss1", x)
    for p, ch7 in zip(("4A", "4B", "4C", "4D", "4E"),
                      (128, 160, 160, 192, 192)):
        x = block_b(p, x, ch7)
    # 4R reduction -> 8x8
    r1 = cbr("4R/p1_1x1", x, 192, 1)
    r1 = cbr("4R/p1_3x3", r1, 320, 3, stride=2)
    r2 = cbr("4R/p2_1x1", x, 192, 1)
    r2 = cbr("4R/p2_1x7", r2, 192, 1, 7, pad_h=0, pad_w=3)
    r2 = cbr("4R/p2_7x1", r2, 192, 7, 1, pad_h=3, pad_w=0)
    r2 = cbr("4R/p2_3x3", r2, 192, 3, stride=2)
    rp = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    setattr(n, "4R/p3_pool", rp)
    x = L.Concat(r1, r2, rp)
    setattr(n, "4R/concat", x)
    aux_head("loss2", x)
    for p in ("5A", "5B"):
        x = block_c(p, x)
    pool = L.Pooling(x, pool="AVE", kernel_size=7, stride=1)
    setattr(n, "loss/pool", pool)
    fc = L.InnerProduct(pool, num_output=1000,
                        weight_filler=dict(type="xavier"),
                        bias_filler=dict(type="constant"))
    setattr(n, "loss/fc", fc)
    n.loss = L.SoftmaxWithLoss(fc, n.label)
    setattr(n, "accuracy/top-1", L.Accuracy(fc, n.label,
                                            include=dict(phase="TEST")))
    setattr(n, "accuracy/top-5", L.Accuracy(fc, n.label, top_k=5,
                                            include=dict(phase="TEST")))
    return n


def caffenet(batch=256, name="CaffeNet", classes=1000, head="fc8",
             head_lr=1):
    """bvlc_reference_caffenet: AlexNet variant with pool-before-norm
    (reference models/bvlc_reference_caffenet). head/classes/head_lr
    parameterize the classifier for finetuning recipes (the flickr-style
    net is this body with a fresh fc8_flickr head at 10x lr)."""
    n = NetSpec(name)
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 227, 227]), dict(dim=[batch])]))
    n.conv1, n.relu1 = conv_relu(n.data, 96, 11, stride=4)
    n.pool1 = L.Pooling(n.relu1, pool="MAX", kernel_size=3, stride=2)
    n.norm1 = L.LRN(n.pool1, local_size=5, alpha=1e-4, beta=0.75)
    n.conv2, n.relu2 = conv_relu(n.norm1, 256, 5, pad=2, group=2)
    n.pool2 = L.Pooling(n.relu2, pool="MAX", kernel_size=3, stride=2)
    n.norm2 = L.LRN(n.pool2, local_size=5, alpha=1e-4, beta=0.75)
    n.conv3, n.relu3 = conv_relu(n.norm2, 384, 3, pad=1)
    n.conv4, n.relu4 = conv_relu(n.relu3, 384, 3, pad=1, group=2)
    n.conv5, n.relu5 = conv_relu(n.relu4, 256, 3, pad=1, group=2)
    n.pool5 = L.Pooling(n.relu5, pool="MAX", kernel_size=3, stride=2)
    n.fc6 = L.InnerProduct(n.pool5, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=1))
    n.relu6 = L.ReLU(n.fc6, in_place=True)
    n.drop6 = L.Dropout(n.fc6, dropout_ratio=0.5, in_place=True)
    n.fc7 = L.InnerProduct(n.fc6, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant", value=1))
    n.relu7 = L.ReLU(n.fc7, in_place=True)
    n.drop7 = L.Dropout(n.fc7, dropout_ratio=0.5, in_place=True)
    head_kw = {}
    if head_lr != 1:
        # finetuning: the fresh head learns 10x faster than the
        # pretrained body (reference models/finetune_flickr_style/
        # train_val.prototxt lr_mult 10/20 on fc8_flickr)
        head_kw["param"] = [dict(lr_mult=head_lr, decay_mult=1),
                            dict(lr_mult=2 * head_lr, decay_mult=0)]
    ip = L.InnerProduct(n.fc7, num_output=classes,
                        weight_filler=dict(type="gaussian", std=0.01),
                        bias_filler=dict(type="constant"), **head_kw)
    setattr(n, head, ip)
    train_test_tail(n, ip)
    return n


def finetune_flickr_style(batch=50):
    """CaffeNet body + fresh 20-way fc8_flickr head: `caffe train -solver
    models/finetune_flickr_style/solver.prototxt -weights
    models/caffenet/<caffenet>.caffemodel` loads every body layer by name
    and leaves the renamed head at its filler init — the reference's
    canonical finetuning workflow (reference
    models/finetune_flickr_style/train_val.prototxt, examples/
    finetune_flickr_style/readme.md; 20 Flickr style classes)."""
    return caffenet(batch=batch, name="FlickrStyleCaffeNet", classes=20,
                    head="fc8_flickr", head_lr=10)


def vgg16(batch=64):
    """VGG-16 (reference models/vgg16)."""
    n = NetSpec("VGG16")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 224, 224]), dict(dim=[batch])]))
    x = n.data
    cfg = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]
    for bi, (reps, ch) in enumerate(cfg, start=1):
        for ri in range(1, reps + 1):
            c = L.Convolution(x, num_output=ch, kernel_size=3, pad=1,
                              weight_filler=dict(type="msra"),
                              bias_filler=dict(type="constant"),
                              param=[dict(lr_mult=1, decay_mult=1),
                                     dict(lr_mult=2, decay_mult=0)])
            r = L.ReLU(c, in_place=True)
            setattr(n, f"conv{bi}_{ri}", c)
            setattr(n, f"relu{bi}_{ri}", r)
            x = r
        p = L.Pooling(x, pool="MAX", kernel_size=2, stride=2)
        setattr(n, f"pool{bi}", p)
        x = p
    n.fc6 = L.InnerProduct(x, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant"))
    n.relu6 = L.ReLU(n.fc6, in_place=True)
    n.drop6 = L.Dropout(n.fc6, dropout_ratio=0.5, in_place=True)
    n.fc7 = L.InnerProduct(n.fc6, num_output=4096,
                           weight_filler=dict(type="gaussian", std=0.005),
                           bias_filler=dict(type="constant"))
    n.relu7 = L.ReLU(n.fc7, in_place=True)
    n.drop7 = L.Dropout(n.fc7, dropout_ratio=0.5, in_place=True)
    # the reference vgg16 names this LAYER "fc8-5" but its top blob "fc8"
    n.fc8 = L.InnerProduct(n.fc7, num_output=1000, layer_name="fc8-5",
                           weight_filler=dict(type="gaussian", std=0.01),
                           bias_filler=dict(type="constant"))
    train_test_tail(n, n.fc8)
    return n



def cifar10_nv(batch=128):
    """cifar10_nv (reference models/cifar10_nv/cifar10_nv_train_test
    .prototxt): all-convolutional — 3x [128 3x3] with BN on conv3, pool,
    3x [256 3x3] with BN on conv6, pool, 320 3x3 / 320 1x1 / 10 1x1 head,
    AVE k5 pool; 28x28 crops of CIFAR images."""
    n = NetSpec("CIFAR10_nv")
    n.data, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, 3, 28, 28]), dict(dim=[batch])]))

    def cr(name, b, nout, ks, pad=0):
        c = L.Convolution(b, num_output=nout, kernel_size=ks, pad=pad,
                          weight_filler=dict(type="xavier"),
                          bias_filler=dict(type="constant"),
                          param=[dict(lr_mult=1), dict(lr_mult=2)])
        r = L.ReLU(c, in_place=True)
        setattr(n, name, c)
        setattr(n, f"{name}_relu", r)
        return r

    def cbnr(name, b, nout, ks, pad=0):
        # bn'd convs (conv3/conv6): bias-free conv + BN eps 1e-4 + ReLU
        return conv_bn_relu(n, name, b, nout, ks, pad_h=pad)

    x = cr("conv1", n.data, 128, 3, pad=1)
    x = cr("conv2", x, 128, 3, pad=1)
    x = cbnr("conv3", x, 128, 3, pad=1)
    n.pool3 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    x = cr("conv4", n.pool3, 256, 3, pad=1)
    x = cr("conv5", x, 256, 3, pad=1)
    x = cbnr("conv6", x, 256, 3, pad=1)
    n.pool6 = L.Pooling(x, pool="MAX", kernel_size=3, stride=2)
    x = cr("conv7", n.pool6, 320, 3)
    x = cr("conv8", x, 320, 1)
    x = cr("conv9", x, 10, 1)
    n.pool9 = L.Pooling(x, pool="AVE", kernel_size=5)
    train_test_tail(n, n.pool9)
    return n


def rcnn(batch=10):
    """R-CNN classifier head (reference models/rcnn, ilsvrc13 200-way):
    CaffeNet body with an fc-rcnn scoring layer; deploy-style for use
    with the Detector wrapper."""
    spec = NetSpec("R-CNN-ilsvrc13")
    spec.data = L.Input(input_param=dict(
        shape=dict(dim=[batch, 3, 227, 227])))
    # reuse the caffenet body topology by regenerating it on spec
    prev = spec.data
    body = [("conv1", 96, 11, 4, 0, 1, True), ("conv2", 256, 5, 1, 2, 2, True),
            ("conv3", 384, 3, 1, 1, 1, False), ("conv4", 384, 3, 1, 1, 2, False),
            ("conv5", 256, 3, 1, 1, 2, True)]
    norms = {"conv1", "conv2"}
    for name, nout, ks, st, pad, grp, pool in body:
        c = L.Convolution(prev, num_output=nout, kernel_size=ks, stride=st,
                          pad=pad, group=grp,
                          weight_filler=dict(type="gaussian", std=0.01),
                          bias_filler=dict(type="constant"))
        r = L.ReLU(c, in_place=True)
        setattr(spec, name, c)
        setattr(spec, f"{name}_relu", r)
        prev = r
        if pool:
            p = L.Pooling(prev, pool="MAX", kernel_size=3, stride=2)
            setattr(spec, f"pool_{name}", p)
            prev = p
        if name in norms:
            nm = L.LRN(prev, local_size=5, alpha=1e-4, beta=0.75)
            setattr(spec, f"norm_{name}", nm)
            prev = nm
    spec.fc6 = L.InnerProduct(prev, num_output=4096,
                              weight_filler=dict(type="gaussian", std=0.005))
    spec.relu6 = L.ReLU(spec.fc6, in_place=True)
    spec.fc7 = L.InnerProduct(spec.fc6, num_output=4096,
                              weight_filler=dict(type="gaussian", std=0.005))
    spec.relu7 = L.ReLU(spec.fc7, in_place=True)
    setattr(spec, "fc-rcnn", L.InnerProduct(
        spec.fc7, num_output=200,
        weight_filler=dict(type="gaussian", std=0.01)))
    return spec


def transformer_lm(batch=8, seq=64, vocab=256, dim=128, heads=4,
                   n_blocks=2, ffn_hidden=256, moe_experts=4):
    """Decoder-only language model — the beyond-reference flagship for the
    long-context stack, expressed entirely in prototxt layer types:
    Embed + learnable positional bias, pre-LN blocks of causal Attention
    and FFN (one block's FFN is an MoE with a weighted aux-loss top),
    trailing LayerNorm, per-position classifier, spatial SoftmaxWithLoss.
    The reference (a CNN framework) has no analogue; every extension type
    used here (Attention/MoE/LayerNorm) is registered and gradchecked
    like the reference ops."""
    n = NetSpec("transformer_lm")
    n.tokens, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, seq]), dict(dim=[batch, seq])]))
    n.embed = L.Embed(n.tokens, input_dim=vocab, num_output=dim,
                      bias_term=False,
                      weight_filler=dict(type="gaussian", std=0.02))
    n.pos = L.Parameter(ntop=1, parameter_param=dict(
        shape=dict(dim=[seq, dim])))
    # broadcast-add positions onto (N, S, C) starting at axis 1
    n.x0 = L.Bias(n.embed, n.pos, axis=1)
    x = n.x0
    for b in range(n_blocks):
        ln1 = L.LayerNorm(x)
        setattr(n, f"blk{b}/ln1", ln1)
        attn = L.Attention(ln1, num_heads=heads, causal=True,
                           weight_filler=dict(type="gaussian", std=0.02))
        setattr(n, f"blk{b}/attn", attn)
        res1 = L.Eltwise(x, attn)
        setattr(n, f"blk{b}/res1", res1)
        ln2 = L.LayerNorm(res1)
        setattr(n, f"blk{b}/ln2", ln2)
        if b == n_blocks - 1 and moe_experts:
            moe_y, moe_aux = L.MoE(ln2, ntop=2,
                                   loss_weight=[0.0, 0.01],
                                   moe_param=dict(num_experts=moe_experts,
                                                  hidden_dim=ffn_hidden,
                                                  capacity_factor=2.0))
            setattr(n, f"blk{b}/moe", moe_y)
            setattr(n, f"blk{b}/moe_aux", moe_aux)
            ffn = moe_y
        else:
            fc1 = L.InnerProduct(ln2, num_output=ffn_hidden, axis=2,
                                 weight_filler=dict(type="gaussian",
                                                    std=0.02))
            setattr(n, f"blk{b}/fc1", fc1)
            setattr(n, f"blk{b}/relu", L.ReLU(fc1, in_place=True))
            ffn = L.InnerProduct(fc1, num_output=dim, axis=2,
                                 weight_filler=dict(type="gaussian",
                                                    std=0.02))
            setattr(n, f"blk{b}/fc2", ffn)
        res2 = L.Eltwise(res1, ffn)
        setattr(n, f"blk{b}/res2", res2)
        x = res2
    n.ln_f = L.LayerNorm(x)
    n.logits = L.InnerProduct(n.ln_f, num_output=vocab, axis=2,
                              weight_filler=dict(type="gaussian", std=0.02))
    n.loss = L.SoftmaxWithLoss(n.logits, n.label,
                               softmax_param=dict(axis=2))
    n.accuracy = L.Accuracy(n.logits, n.label, axis=2,
                            include=dict(phase="TEST"))
    return n


SMALLTHINKER = dict(
    # https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json: widths as published; depth, experts held and vocabulary
    # are one chip's share (benchmarks/configs/smallthinker_21b_a3b.json)
    seq=8192, vocab=37984, dim=2560, heads=28, kv_heads=4, head_dim=128,
    window=4096, rope_theta=1.5e6, window_layout=(0, 1, 1, 1),
    rope_layout=(0, 1, 1, 1), experts=64, experts_held=16, top_k=6,
    expert_width=768, eps=1e-6)
# the size of tests/test_smallthinker.py and of the benchmark's CPU
# rehearsal: every mechanism, no width
SMALLTHINKER_TINY = dict(
    seq=32, vocab=64, dim=64, heads=4, kv_heads=2, head_dim=16, window=8,
    rope_theta=1.5e6, window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    experts=8, experts_held=2, top_k=2, expert_width=32, eps=1e-6)


def smallthinker(batch=1, *, seq, vocab, dim, heads, kv_heads, head_dim,
                 window, rope_theta, window_layout, rope_layout, experts,
                 experts_held, top_k, expert_width, eps, first_expert=0,
                 use_flash=True, name="smallthinker_21b_a3b"):
    """SmallThinker-21BA3B-Instruct (arXiv:2507.20984), one chip's share:
    pre-norm blocks of grouped-head attention (global layers without
    positions, windowed layers with rotary ones) and a dropless top-k
    expert layer of gated ReLU experts whose router reads the block's
    INPUT, before the attention norm. No biases, no q/k norm, no shared
    expert, untied embedding and head. The layer equations are written
    out in benchmarks/reference/lm_ref.py."""
    filler = dict(type="gaussian", std=0.02)
    n = NetSpec(name)
    n.tokens, n.label = L.Input(ntop=2, input_param=dict(
        shape=[dict(dim=[batch, seq]), dict(dim=[batch, seq])]))
    # the table alone is unit normal (an embedding's usual start): the
    # blocks act on normalised inputs, so what they add to the residual
    # stream has a fixed size, and most of what attention adds is common
    # to every token (a softmax average keeps the tokens' common part and
    # averages their own away). Against rows of norm 1 (std 0.02) that
    # common part wins by the third layer, every token's router, which
    # reads the stream un-normed, picks the same six experts, and whether
    # they are among the 16 held is a draw of the seed (PERF.md, PR 27)
    n.embed = L.Embed(n.tokens, input_dim=vocab, num_output=dim,
                      bias_term=False,
                      weight_filler=dict(type="gaussian", std=1.0))
    x = n.embed
    for b, (windowed, rotary) in enumerate(zip(window_layout, rope_layout)):
        ln1 = L.RMSNorm(x, eps=eps)
        setattr(n, f"blk{b}/ln1", ln1)
        attn = L.Attention(
            ln1, num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
            causal=True, use_flash=use_flash, bias_term=False,
            window=window if windowed else 0,
            rope_theta=rope_theta if rotary else 0.0, weight_filler=filler)
        setattr(n, f"blk{b}/attn", attn)
        res1 = L.Eltwise(x, attn)
        setattr(n, f"blk{b}/res1", res1)
        ln2 = L.RMSNorm(res1, eps=eps)
        setattr(n, f"blk{b}/ln2", ln2)
        # second bottom: what the router scores (the block's input); second
        # top: rows each held expert received, read by checks, no loss.
        # Routing is a constant of the step: the router (`gate`, the first
        # blob) is frozen and nothing trains through it (no gradient into
        # the second bottom). A share's router sees only the experts held
        # here lower the loss, and so does whatever feeds it: left to
        # train, either way every row ends up here (49,152 a layer from
        # 12,288 within ten iterations; PERF.md, PR 27), where in the
        # deployment the other chips' experts pull as hard
        moe, rows = L.MoE(ln2, x, ntop=2, loss_weight=[0.0, 0.0],
                          param=[dict(lr_mult=0, decay_mult=0)],
                          propagate_down=[True, False],
                          moe_param=dict(
                              num_experts=experts, hidden_dim=expert_width,
                              top_k=top_k, dropless=True,
                              experts_held=experts_held,
                              first_expert=first_expert,
                              weight_filler=filler))
        setattr(n, f"blk{b}/moe", moe)
        setattr(n, f"blk{b}/moe_rows", rows)
        res2 = L.Eltwise(res1, moe)
        setattr(n, f"blk{b}/res2", res2)
        x = res2
    n.ln_f = L.RMSNorm(x, eps=eps)
    n.logits = L.InnerProduct(n.ln_f, num_output=vocab, axis=2,
                              bias_term=False, weight_filler=filler)
    n.loss = L.SoftmaxWithLoss(n.logits, n.label,
                               softmax_param=dict(axis=2))
    n.accuracy = L.Accuracy(n.logits, n.label, axis=2,
                            include=dict(phase="TEST"))
    return n


def smallthinker_solver(net: str, prefix: str) -> str:
    return f"""# SmallThinker-21BA3B-Instruct, one chip's share: Adam (this
# system's coupled L2, none set), fixed 3e-4, global-norm clip 1
net: "models/smallthinker_21b_a3b/{net}"
base_lr: 0.0003
lr_policy: "fixed"
display: 10
max_iter: 10000
momentum: 0.9
momentum2: 0.95
type: "Adam"
clip_gradients: 1.0
# static: bf16 has float32's exponent range, and the dynamic scale's
# skip-step guard keeps the old and the new weights and Adam slots alive
# side by side, 7.9 GB more than a 16 GB chip has beside them
loss_scale: 1.0
snapshot: 10000
snapshot_prefix: "models/smallthinker_21b_a3b/{prefix}"
"""


JOYAI = dict(
    # https://huggingface.co/jdopensource/JoyAI-LLM-Flash config.json:
    # widths as published; depth, experts held and vocabulary are one
    # chip's share (benchmarks/configs/joyai_llm_flash.json)
    seq=8192, vocab=16160, dim=2048, heads=32, q_lora=1536, kv_lora=512,
    nope=128, rot=64, vd=128, rope_theta=3.2e7, dense_width=7168,
    expert_layers=4, experts=256, experts_held=16, top_k=8,
    expert_width=768, shared_experts=1, scaling=2.5, eps=1e-6)
# the size of tests/test_joyai.py and of the benchmark's CPU rehearsal:
# every mechanism, no width (32 experts in 16 shares of 2)
JOYAI_TINY = dict(
    seq=32, vocab=64, dim=64, heads=4, q_lora=48, kv_lora=32, nope=16,
    rot=8, vd=16, rope_theta=3.2e7, dense_width=96, expert_layers=2,
    experts=32, experts_held=2, top_k=4, expert_width=32,
    shared_experts=1, scaling=2.5, eps=1e-6)
JOYAI_MTP_WEIGHT = 0.3
# layers computed again in the backward pass (LayerParameter.remat)
JOYAI_REMAT = ("attn",)
# the selection bias: a level the choice cannot see and the weights would,
# a spread that decides the eighth choice of about half the tokens
JOYAI_SELECT_BIAS = dict(type="gaussian", mean=-0.8, std=0.01)


def joyai_llm_flash(batch=1, *, seq, vocab, dim, heads, q_lora, kv_lora,
                    nope, rot, vd, rope_theta, dense_width, expert_layers,
                    experts, experts_held, top_k, expert_width,
                    shared_experts, scaling, eps, first_expert=0,
                    use_flash=True, remat=(), name="joyai_llm_flash"):
    """JoyAI-LLM-Flash (DeepSeek-V3 family, arXiv:2412.19437), one chip's
    share: pre-norm blocks of latent attention (arXiv:2405.04434) and a
    feed-forward that is a dense gated-SiLU MLP in the leading block and,
    after it, a dropless expert layer with sigmoid scores chosen under a
    selection bias, SiLU-gated experts and one shared expert; then the
    head and the multi-token-prediction module (one more expert block on
    [norm(Emb(next token)) | norm(trunk output)], the embedding table and
    the head shared with the trunk by name, its loss weighted 0.3). No
    biases, untied embedding and head. The layer equations are written out
    in benchmarks/reference/joyai_ref.py. `remat`: layer-name suffixes
    whose layers are computed again in the backward pass."""
    filler = dict(type="gaussian", std=0.02)
    n = NetSpec(name)
    # label = the next token (also what the MTP module embeds), label_mtp
    # the one after
    n.tokens, n.label, n.label_mtp = L.Input(ntop=3, input_param=dict(
        shape=[dict(dim=[batch, seq])] * 3))
    again = lambda layer: dict(remat=True) \
        if any(layer.endswith(suffix) for suffix in remat) else {}

    def put(layer, top):
        setattr(n, layer, top)
        return top

    def norm(layer, x):
        return put(layer, L.RMSNorm(x, eps=eps, **again(layer)))

    def block(b, x, dense):
        ln1 = norm(f"{b}/ln1", x)
        attn = put(f"{b}/attn", L.Attention(
            ln1, num_heads=heads, causal=True, use_flash=use_flash,
            bias_term=False, rope_theta=rope_theta, q_lora_rank=q_lora,
            kv_lora_rank=kv_lora, qk_nope_head_dim=nope,
            qk_rope_head_dim=rot, v_head_dim=vd, rope_interleave=True,
            norm_eps=eps, weight_filler=filler, **again(f"{b}/attn")))
        res1 = put(f"{b}/res1", L.Eltwise(x, attn))
        ln2 = norm(f"{b}/ln2", res1)
        if dense:
            # silu(m G) * (m U) as m G * sigmoid(m G) * m U
            product = dict(num_output=dense_width, axis=2, bias_term=False,
                           weight_filler=filler)
            gate = put(f"{b}/gate", L.InnerProduct(ln2, **product))
            up = put(f"{b}/up", L.InnerProduct(ln2, **product))
            sig = put(f"{b}/sig", L.Sigmoid(gate))
            act = put(f"{b}/act", L.Eltwise(gate, sig, up, operation="PROD"))
            ffn = put(f"{b}/down", L.InnerProduct(
                act, num_output=dim, axis=2, bias_term=False,
                weight_filler=filler))
        else:
            # the router scores the experts' own input (the same blob,
            # twice). Routing is a constant of the step, as in
            # smallthinker(): the router matrix and the selection bias
            # (the first two blobs) are frozen and nothing trains through
            # the scores. Second top: rows each held expert received
            ffn, rows = L.MoE(
                ln2, ln2, ntop=2, loss_weight=[0.0, 0.0],
                param=[dict(lr_mult=0, decay_mult=0)] * 2,
                propagate_down=[True, False],
                moe_param=dict(
                    num_experts=experts, hidden_dim=expert_width,
                    top_k=top_k, dropless=True, experts_held=experts_held,
                    first_expert=first_expert, scoring="sigmoid",
                    routed_scaling_factor=scaling, activation="silu",
                    shared_experts=shared_experts, weight_filler=filler,
                    bias_filler=JOYAI_SELECT_BIAS))
            put(f"{b}/moe", ffn)
            put(f"{b}/moe_rows", rows)
        return put(f"{b}/res2", L.Eltwise(res1, ffn))

    table = dict(input_dim=vocab, num_output=dim, bias_term=False,
                 weight_filler=dict(type="gaussian", std=1.0),
                 param=[dict(name="embed_w")])
    head = dict(num_output=vocab, axis=2, bias_term=False,
                weight_filler=filler, param=[dict(name="head_w")])
    n.embed = L.Embed(n.tokens, **table)
    x = n.embed
    for l in range(1 + expert_layers):
        x = block(f"blk{l}", x, dense=l == 0)
    n.ln_f = L.RMSNorm(x, eps=eps)
    n.logits = L.InnerProduct(n.ln_f, **head)
    n.loss = L.SoftmaxWithLoss(n.logits, n.label,
                               softmax_param=dict(axis=2))
    # multi-token prediction, depth 1: the trunk's output BEFORE its last
    # norm beside the next token's embedding, one expert block, the
    # trunk's head
    emb = put("mtp/embed", L.Embed(n.label, **table))
    cat = put("mtp/cat", L.Concat(norm("mtp/enorm", emb),
                                  norm("mtp/hnorm", x), axis=2))
    z = put("mtp/eh_proj", L.InnerProduct(
        cat, num_output=dim, axis=2, bias_term=False, weight_filler=filler))
    z = block("mtp", z, dense=False)
    logits = put("mtp/logits", L.InnerProduct(norm("mtp/ln_f", z), **head))
    put("mtp/loss", L.SoftmaxWithLoss(
        logits, n.label_mtp, loss_weight=JOYAI_MTP_WEIGHT,
        softmax_param=dict(axis=2)))
    n.accuracy = L.Accuracy(n.logits, n.label, axis=2,
                            include=dict(phase="TEST"))
    return n


def joyai_solver(net: str, prefix: str) -> str:
    return f"""# JoyAI-LLM-Flash, one chip's share: Adam (this system's
# coupled L2, none set), fixed 3e-4, global-norm clip 1; static loss scale
# for the reason models/smallthinker_21b_a3b/solver.prototxt gives
net: "models/joyai_llm_flash/{net}"
base_lr: 0.0003
lr_policy: "fixed"
display: 10
max_iter: 10000
momentum: 0.9
momentum2: 0.95
type: "Adam"
clip_gradients: 1.0
loss_scale: 1.0
snapshot: 10000
snapshot_prefix: "models/joyai_llm_flash/{prefix}"
"""


SDAR = dict(
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat config.json: widths as
    # published; depth, experts held and vocabulary are one chip's share,
    # block length and noise schedule the configuration's `assumed`
    # (benchmarks/configs/sdar_30b_a3b.json)
    seq=8192, vocab=18992, dim=2048, heads=32, kv_heads=4, head_dim=128,
    rope_theta=1e6, layers=5, experts=128, experts_held=16, top_k=8,
    expert_width=768, eps=1e-6, block_length=4, mask_id=18991, t_min=1e-3)
# the size of tests/test_sdar.py and of the benchmark's CPU rehearsal: every
# mechanism, no width (32 experts in 16 shares of 2)
SDAR_TINY = dict(
    seq=32, vocab=64, dim=64, heads=4, kv_heads=2, head_dim=16,
    rope_theta=1e6, layers=3, experts=32, experts_held=2, top_k=4,
    expert_width=32, eps=1e-6, block_length=4, mask_id=63, t_min=1e-3)
SDAR_IGNORE = -1
# layers computed again in the backward pass (LayerParameter.remat): as
# written the step needs 15.55e9 bytes, with the attention layers' remat
# (the flash kernel's output kept) 13.96e9 (deviceless compile)
SDAR_REMAT = ("attn",)
# the frozen router starts as `experts / experts_held` copies of one matrix
# of experts_held columns (filler `tile`): every row's top_k logits are then
# one column's copies, one expert on each of the chips that share the layer,
# and this chip receives exactly its share, 2 x seq rows a layer, on every
# seed and at every step. With independent columns the share is a draw of
# the seed: to a fresh router every masked row, a quarter of the rows, is
# the same row through every layer (near-uniform attention adds nothing a
# row could be told by), so they pick the same top_k experts, and how many
# of those are among the held decides the load: 11,400 to 26,000 rows a
# layer over 16 seeds on the chip, 3 of 16 past the expert layer's row
# bound in some layer, and a rate that spread 0.7 % (PERF.md section 6,
# PR 33). A deployment balances its chips by a loss; this is that balance
# taken to its end
def sdar_router(experts: int, experts_held: int) -> dict:
    return dict(type="gaussian", std=0.02, tile=experts // experts_held)


def sdar(batch=1, *, seq, vocab, dim, heads, kv_heads, head_dim, rope_theta,
         layers, experts, experts_held, top_k, expert_width, eps,
         block_length, mask_id, t_min, first_expert=0, use_flash=True,
         remat=(), name="sdar_30b_a3b"):
    """SDAR-30B-A3B-Chat (arXiv:2510.06303), one chip's share, as it is
    trained: by block diffusion (arXiv:2503.09573). The noise layer turns
    `seq` clean tokens into the 2 x seq ids [noisy | clean], which go
    through every block together under the block-diffusion mask; the head
    and the 1/t-weighted loss read the noisy half alone, with no shift. A
    block is pre-norm: grouped-head attention with an RMSNorm on each query
    and key head and rotary positions i mod seq, then a dropless top-k
    expert layer (softmax over the chosen logits, SiLU-gated experts) whose
    router reads the experts' own normed input. No biases, no shared
    expert, untied embedding and head. The equations are written out in
    benchmarks/reference/sdar_ref.py. `remat`: layer-name suffixes whose
    layers are computed again in the backward pass."""
    filler = dict(type="gaussian", std=0.02)
    n = NetSpec(name)
    n.tokens = L.Input(input_param=dict(shape=[dict(dim=[batch, seq])]))
    n.ids, n.label, n.weight, n.masked = L.BlockDiffusionNoise(
        n.tokens, ntop=4,
        block_diffusion_param=dict(block_length=block_length,
                                   mask_id=mask_id, t_min=t_min,
                                   ignore_label=SDAR_IGNORE))
    again = lambda layer: dict(remat=True) \
        if any(layer.endswith(suffix) for suffix in remat) else {}
    # unit normal, for smallthinker()'s reason
    n.embed = L.Embed(n.ids, input_dim=vocab, num_output=dim,
                      bias_term=False,
                      weight_filler=dict(type="gaussian", std=1.0))
    x = n.embed
    for b in range(layers):
        ln1 = L.RMSNorm(x, eps=eps)
        setattr(n, f"blk{b}/ln1", ln1)
        attn = L.Attention(
            ln1, num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
            use_flash=use_flash, bias_term=False, rope_theta=rope_theta,
            block_diffusion=block_length, qk_norm=True, norm_eps=eps,
            weight_filler=filler, **again(f"blk{b}/attn"))
        setattr(n, f"blk{b}/attn", attn)
        res1 = L.Eltwise(x, attn)
        setattr(n, f"blk{b}/res1", res1)
        ln2 = L.RMSNorm(res1, eps=eps)
        setattr(n, f"blk{b}/ln2", ln2)
        # the router scores the experts' own input (the same blob, twice);
        # routing is a constant of the step, as in smallthinker(): the
        # router matrix is frozen and nothing trains through the scores.
        # Second top: rows each held expert received
        moe, rows = L.MoE(ln2, ln2, ntop=2, loss_weight=[0.0, 0.0],
                          param=[dict(lr_mult=0, decay_mult=0)],
                          propagate_down=[True, False],
                          moe_param=dict(
                              num_experts=experts, hidden_dim=expert_width,
                              top_k=top_k, dropless=True,
                              experts_held=experts_held,
                              first_expert=first_expert, activation="silu",
                              gate_filler=sdar_router(experts, experts_held),
                              weight_filler=filler))
        setattr(n, f"blk{b}/moe", moe)
        setattr(n, f"blk{b}/moe_rows", rows)
        res2 = L.Eltwise(res1, moe)
        setattr(n, f"blk{b}/res2", res2)
        x = res2
    # the loss reads the noisy half; the clean half ends here
    n.noisy, n.clean = L.Slice(x, ntop=2, axis=1, slice_point=[seq])
    n.drop_clean = L.Silence(n.clean, ntop=0)
    n.ln_f = L.RMSNorm(n.noisy, eps=eps)
    n.logits = L.InnerProduct(n.ln_f, num_output=vocab, axis=2,
                              bias_term=False, weight_filler=filler)
    # FULL: 1 / (N seq), whatever the draw masked
    n.loss = L.SoftmaxWithLoss(
        n.logits, n.label, n.weight, softmax_param=dict(axis=2),
        loss_param=dict(ignore_label=SDAR_IGNORE, normalization="FULL"))
    n.accuracy = L.Accuracy(n.logits, n.label, axis=2,
                            ignore_label=SDAR_IGNORE,
                            include=dict(phase="TEST"))
    return n


def sdar_solver(net: str, prefix: str) -> str:
    return f"""# SDAR-30B-A3B-Chat, one chip's share, trained by block
# diffusion: Adam (this system's coupled L2, none set), fixed 3e-4,
# global-norm clip 1; static loss scale for the reason
# models/smallthinker_21b_a3b/solver.prototxt gives
net: "models/sdar_30b_a3b/{net}"
base_lr: 0.0003
lr_policy: "fixed"
display: 10
max_iter: 10000
momentum: 0.9
momentum2: 0.95
type: "Adam"
clip_gradients: 1.0
loss_scale: 1.0
snapshot: 10000
snapshot_prefix: "models/sdar_30b_a3b/{prefix}"
"""


def transformer_lm_pp_prototxt(batch=8, seq=64, vocab=256, dim=128, heads=4,
                               n_stages=4, micro_batches=4, ffn_hidden=256):
    """Pipeline-parallel transformer_lm variant: the trunk is ONE Pipeline
    layer whose repeated block is the pre-LN attention+FFN pair, so
    `caffe train -solver models/transformer_lm/solver_pp.prototxt -mesh
    data=N,model=4` trains with stage weights sharded one-per-device
    (layers/composite.py). Stages must be structurally identical, so this
    variant is homogeneous (no MoE block) and emitted as text rather than
    through NetSpec (which has no nested-block syntax)."""
    blk = f"""    layer {{ name: "ln1" type: "LayerNorm" bottom: "h" top: "n1" }}
    layer {{ name: "attn" type: "Attention" bottom: "n1" top: "a"
             attention_param {{ num_heads: {heads} causal: true
               weight_filler {{ type: "gaussian" std: 0.02 }} }} }}
    layer {{ name: "res1" type: "Eltwise" bottom: "h" bottom: "a" top: "r1" }}
    layer {{ name: "ln2" type: "LayerNorm" bottom: "r1" top: "n2" }}
    layer {{ name: "fc1" type: "InnerProduct" bottom: "n2" top: "f1"
             inner_product_param {{ num_output: {ffn_hidden} axis: 2
               weight_filler {{ type: "gaussian" std: 0.02 }} }} }}
    layer {{ name: "relu" type: "ReLU" bottom: "f1" top: "f1" }}
    layer {{ name: "fc2" type: "InnerProduct" bottom: "f1" top: "f2"
             inner_product_param {{ num_output: {dim} axis: 2
               weight_filler {{ type: "gaussian" std: 0.02 }} }} }}
    layer {{ name: "res2" type: "Eltwise" bottom: "r1" bottom: "f2"
             top: "out" }}"""
    return f"""name: "transformer_lm_pp"
layer {{ name: "tokens" type: "Input" top: "tokens" top: "label"
        input_param {{ shape {{ dim: {batch} dim: {seq} }}
                       shape {{ dim: {batch} dim: {seq} }} }} }}
layer {{ name: "embed" type: "Embed" bottom: "tokens" top: "embed"
        embed_param {{ input_dim: {vocab} num_output: {dim} bias_term: false
          weight_filler {{ type: "gaussian" std: 0.02 }} }} }}
layer {{ name: "pos" type: "Parameter" top: "pos"
        parameter_param {{ shape {{ dim: {seq} dim: {dim} }} }} }}
layer {{ name: "h" type: "Bias" bottom: "embed" bottom: "pos" top: "h"
        bias_param {{ axis: 1 }} }}
layer {{ name: "trunk" type: "Pipeline" bottom: "h" top: "hN"
        pipeline_param {{ num_stages: {n_stages}
          micro_batches: {micro_batches}
{blk} }} }}
layer {{ name: "ln_f" type: "LayerNorm" bottom: "hN" top: "ln_f" }}
layer {{ name: "logits" type: "InnerProduct" bottom: "ln_f" top: "logits"
        inner_product_param {{ num_output: {vocab} axis: 2
          weight_filler {{ type: "gaussian" std: 0.02 }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
        bottom: "label" top: "loss" softmax_param {{ axis: 2 }} }}
layer {{ name: "accuracy" type: "Accuracy" bottom: "logits" bottom: "label"
        top: "accuracy" accuracy_param {{ axis: 2 }}
        include {{ phase: TEST }} }}
"""


SOLVERS = {
    "transformer_lm": """# transformer_lm solver (beyond-reference demo model; Adam recipe)
net: "models/transformer_lm/train_val.prototxt"
test_iter: 16
test_interval: 1000
test_initialization: false
base_lr: 0.001
lr_policy: "fixed"
display: 100
max_iter: 10000
momentum: 0.9
momentum2: 0.999
type: "Adam"
snapshot: 10000
snapshot_prefix: "models/transformer_lm/transformer_lm"
""",
    "alexnet": """# AlexNet solver (reference models/bvlc_alexnet/solver.prototxt recipe)
net: "models/alexnet/train_val.prototxt"
test_iter: 1000
test_interval: 1000
base_lr: 0.01
lr_policy: "step"
gamma: 0.1
stepsize: 100000
display: 20
max_iter: 450000
momentum: 0.9
weight_decay: 0.0005
snapshot: 10000
snapshot_prefix: "models/alexnet/caffe_alexnet_train"
""",
    "cifar10_quick": """# CIFAR-10 quick solver (reference examples/cifar10 recipe)
net: "models/cifar10_quick/train_val.prototxt"
test_iter: 100
test_interval: 500
base_lr: 0.001
momentum: 0.9
weight_decay: 0.004
lr_policy: "fixed"
display: 100
max_iter: 4000
snapshot: 4000
snapshot_prefix: "models/cifar10_quick/cifar10_quick"
""",
    "googlenet": """# GoogLeNet solver (reference models/bvlc_googlenet recipe)
net: "models/googlenet/train_val.prototxt"
test_iter: 1000
test_interval: 4000
base_lr: 0.01
lr_policy: "poly"
power: 0.5
display: 40
max_iter: 2400000
momentum: 0.9
weight_decay: 0.0002
snapshot: 40000
snapshot_prefix: "models/googlenet/bvlc_googlenet"
""",
    "cifar10_nv": """# cifar10_nv solver (reference models/cifar10_nv/cifar10_nv_solver.prototxt)
net: "models/cifar10_nv/train_val.prototxt"
test_iter: 20
test_interval: 400
display: 100
max_iter: 100000
lr_policy: "poly"
base_lr: 0.01
power: 2
momentum: 0.9
weight_decay: 0.004
snapshot: 1000000
snapshot_prefix: "models/cifar10_nv/cifar10_nv"
snapshot_after_train: false
""",
    "alexnet_bn": """# AlexNet-BN solver (reference models/alexnet_bn/solver.prototxt)
net: "models/alexnet_bn/train_val.prototxt"
test_iter: 195
test_interval: 5000
test_initialization: false
display: 100
max_iter: 150000
lr_policy: "poly"
base_lr: 0.02
power: 2.0
momentum: 0.9
weight_decay: 0.0005
snapshot: 500000
snapshot_prefix: "models/alexnet_bn/alexnet_bn"
""",
    "inception_v3": """# Inception-v3 solver (reference models/inception_v3/solver.prototxt;
# DGX-1 batch-256 variant: max_iter 300000, base_lr 0.2)
net: "models/inception_v3/train_val.prototxt"
test_iter: 1563
test_interval: 20000
test_initialization: false
display: 100
max_iter: 2400000
base_lr: 0.05
lr_policy: "poly"
power: 2
momentum: 0.9
weight_decay: 0.0001
snapshot: 20000
snapshot_prefix: "models/inception_v3/inception_v3"
""",
    "alexnet_owt": """# AlexNet-OWT solver (reference models/alexnet_owt/solver.prototxt:
# poly power 2, base_lr 0.02 for B=1024, 100 epochs)
net: "models/alexnet_owt/train_val.prototxt"
test_iter: 195
test_interval: 5000
test_initialization: false
display: 100
max_iter: 125000
base_lr: 0.02
lr_policy: "poly"
power: 2.0
momentum: 0.9
weight_decay: 0.0005
snapshot: 500000
snapshot_prefix: "models/alexnet_owt/alexnet_owt"
""",
    "inception_v2": """# Inception-v2 solver (reference models/inception_v2/solver.prototxt:
# poly power 2; B=256 variant uses base_lr 0.2, max_iter 300000)
net: "models/inception_v2/train_val.prototxt"
test_iter: 1563
test_interval: 20000
test_initialization: false
display: 100
max_iter: 2400000
base_lr: 0.05
lr_policy: "poly"
power: 2.0
momentum: 0.9
weight_decay: 0.0002
snapshot: 20000
snapshot_prefix: "models/inception_v2/inception_v2"
""",
    "caffenet": """# CaffeNet solver (reference bvlc_reference_caffenet recipe)
net: "models/caffenet/train_val.prototxt"
test_iter: 1000
test_interval: 1000
base_lr: 0.01
lr_policy: "step"
gamma: 0.1
stepsize: 100000
display: 20
max_iter: 450000
momentum: 0.9
weight_decay: 0.0005
snapshot: 10000
snapshot_prefix: "models/caffenet/caffenet_train"
""",
    "finetune_flickr_style": """# Flickr-style finetuning solver (reference
# models/finetune_flickr_style/solver.prototxt: lr 10x lower than
# from-scratch, step decay closer-in, fresh head at lr_mult 10)
net: "models/finetune_flickr_style/train_val.prototxt"
test_iter: 100
test_interval: 1000
base_lr: 0.001
lr_policy: "step"
gamma: 0.1
stepsize: 20000
display: 20
max_iter: 100000
momentum: 0.9
weight_decay: 0.0005
snapshot: 10000
snapshot_prefix: "models/finetune_flickr_style/finetune_flickr_style"
""",
    "vgg16": """# VGG-16 solver (reference models/vgg16 recipe class)
net: "models/vgg16/train_val.prototxt"
test_iter: 1000
test_interval: 4000
base_lr: 0.01
lr_policy: "step"
gamma: 0.1
stepsize: 100000
display: 40
max_iter: 370000
momentum: 0.9
weight_decay: 0.0005
snapshot: 20000
snapshot_prefix: "models/vgg16/vgg16"
""",
    "resnet18": """# ResNet-18 solver (reference models/resnet18 recipe class)
net: "models/resnet18/train_val.prototxt"
test_iter: 1000
test_interval: 5000
base_lr: 0.1
lr_policy: "poly"
power: 1.0
display: 100
max_iter: 600000
momentum: 0.9
weight_decay: 0.0001
snapshot: 25000
snapshot_prefix: "models/resnet18/resnet18"
""",
    "resnet50": """# ResNet-50 solver (reference models/resnet50/solver.prototxt recipe:
# poly power=2, momentum 0.9, wd 1e-4; DGX-1-class batch-256 variant uses
# base_lr 0.2 with warmup)
net: "models/resnet50/train_val.prototxt"
test_iter: 1000
test_interval: 5000
base_lr: 0.1
lr_policy: "poly"
power: 2.0
rampup_interval: 5000
rampup_lr: 0.01
display: 100
max_iter: 600000
momentum: 0.9
weight_decay: 0.0001
snapshot: 25000
snapshot_prefix: "models/resnet50/resnet50"
""",
}


def make_deploy(train_val_path: str, batch: int = 10) -> str:
    """Derive a deploy net from a train_val file (reference zoo ships
    deploy.prototxt per model): drop phase-gated loss/accuracy layers and
    the label input, softmax the final classifier into 'prob'."""
    from caffe_mpi_tpu.proto import NetParameter, NetState, filter_net, normalize_net
    from caffe_mpi_tpu.proto.text_format import PbNode, PbEnum

    net = normalize_net(NetParameter.from_file(train_val_path))
    # keep only layers live in NEITHER-specific deploy sense: drop anything
    # phase-gated (losses, accuracies) and any loss-typed layer
    drop_types = {"SoftmaxWithLoss", "Accuracy", "EuclideanLoss", "HingeLoss",
                  "SigmoidCrossEntropyLoss", "ContrastiveLoss", "InfogainLoss",
                  "MultinomialLogisticLoss", "L1Loss"}
    kept = [lp for lp in net.layer
            if lp.type not in drop_types and not lp.include and not lp.exclude]
    consumed = {b for lp in kept for b in lp.bottom}
    produced = [t for lp in kept for t in lp.top]
    # classifier blob = last produced blob not consumed elsewhere
    final = [t for t in produced if t not in consumed][-1]
    # dead-branch elimination by reverse liveness (robust to in-place
    # relu/dropout self-loops): keep only layers reaching the classifier —
    # the reference deploy files likewise omit the aux branches
    live = {final}
    kept_rev = []
    for lp in reversed(kept):
        if lp.type == "Input" or any(t in live for t in lp.top):
            kept_rev.append(lp)
            live.update(lp.bottom)
    kept = list(reversed(kept_rev))

    root = PbNode()
    root.add("name", net.name)
    for lp in kept:
        node = lp.to_node()
        if lp.type == "Input":
            # single data input at deploy batch size (keep the net's own
            # first top name — image nets call it "data", the LM "tokens")
            first_top = lp.top[0]
            node.fields.pop("top", None)
            node.add("top", first_top)
            ip = PbNode()
            shape = PbNode()
            dims = lp.input_param.shape[0].dim
            for d in [batch] + [int(x) for x in dims[1:]]:
                shape.add("dim", d)
            ip.add("shape", shape)
            node.fields["input_param"] = [ip]
        root.add("layer", node)
    prob = PbNode()
    prob.add("name", "prob")
    prob.add("type", "Softmax")
    prob.add("bottom", final)
    prob.add("top", "prob")
    root.add("layer", prob)
    return root.to_text()


def main():
    out_root = os.path.dirname(os.path.abspath(__file__))
    nets = {
        "alexnet": alexnet(),
        "alexnet_bn": alexnet_bn(),
        "alexnet_owt": alexnet_owt(),
        "inception_v2": inception_v2(),
        "caffenet": caffenet(),
        "finetune_flickr_style": finetune_flickr_style(),
        "cifar10_quick": cifar10_quick(),
        "googlenet": googlenet(),
        "inception_v3": inception_v3(),
        "resnet18": resnet18(),
        "resnet50": resnet50(),
        "vgg16": vgg16(),
        "cifar10_nv": cifar10_nv(),
        "transformer_lm": transformer_lm(),
    }
    # deploy-only model (no solver): rcnn
    d = os.path.join(out_root, "rcnn")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "deploy.prototxt"), "w") as f:
        f.write(rcnn().to_prototxt() + "\n")
    print("wrote models/rcnn/ (deploy only)")

    # fp16 variants (reference models/resnet50/train_val_fp16.prototxt +
    # solver_fp16.prototxt): FLOAT16 -> bfloat16 on TPU, f32 master
    # weights, loss scaling
    # the reference ships fp16 variants for these families
    for name in ("resnet50", "resnet18", "alexnet", "alexnet_owt",
                 "googlenet", "inception_v2", "inception_v3", "vgg16"):
        d = os.path.join(out_root, name)
        base = open(os.path.join(d, "train_val.prototxt")).read()
        with open(os.path.join(d, "train_val_fp16.prototxt"), "w") as f:
            f.write("default_forward_type: FLOAT16\n"
                    "default_backward_type: FLOAT16\n"
                    "global_grad_scale: 1000\n" + base)
        solver = open(os.path.join(d, "solver.prototxt")).read()
        with open(os.path.join(d, "solver_fp16.prototxt"), "w") as f:
            f.write(solver.replace("train_val.prototxt",
                                   "train_val_fp16.prototxt"))
        print(f"wrote models/{name}/ fp16 variant")
    for name, spec in nets.items():
        d = os.path.join(out_root, name)
        os.makedirs(d, exist_ok=True)
        tv = os.path.join(d, "train_val.prototxt")
        with open(tv, "w") as f:
            f.write(spec.to_prototxt() + "\n")
        with open(os.path.join(d, "solver.prototxt"), "w") as f:
            f.write(SOLVERS[name])
        with open(os.path.join(d, "deploy.prototxt"), "w") as f:
            f.write(make_deploy(tv) + "\n")
        print(f"wrote models/{name}/")

    # the language-model configurations of the benchmark: the recipe at the
    # published widths and the tiny one its CPU rehearsal and tests run
    # (tests/test_smallthinker.py, tests/test_joyai.py, tests/test_sdar.py).
    # Train only: no
    # deploy net, the serving path has no key/value cache yet
    for family, build, solver_text, real, tiny, prefix in (
            ("smallthinker_21b_a3b", smallthinker, smallthinker_solver,
             SMALLTHINKER, SMALLTHINKER_TINY, "smallthinker"),
            ("joyai_llm_flash", joyai_llm_flash, joyai_solver,
             dict(JOYAI, remat=JOYAI_REMAT),
             dict(JOYAI_TINY, remat=JOYAI_REMAT), "joyai"),
            ("sdar_30b_a3b", sdar, sdar_solver,
             dict(SDAR, remat=SDAR_REMAT),
             dict(SDAR_TINY, remat=SDAR_REMAT), "sdar")):
        d = os.path.join(out_root, family)
        os.makedirs(d, exist_ok=True)
        for net, solver, sizes in (
                ("train_val.prototxt", "solver.prototxt", real),
                ("tiny_train_val.prototxt", "tiny_solver.prototxt", tiny)):
            with open(os.path.join(d, net), "w") as f:
                f.write(build(**sizes).to_prototxt() + "\n")
            with open(os.path.join(d, solver), "w") as f:
                f.write(solver_text(net, net.split("train_val")[0] + prefix))
        print(f"wrote models/{family}/")

    # transformer_lm model-parallel variants: PP trunk (Pipeline layer)
    # and SP attention (sequence_parallel: true), each launchable from one
    # `caffe train -mesh data=N,model=M` line
    d = os.path.join(out_root, "transformer_lm")
    with open(os.path.join(d, "train_val_pp.prototxt"), "w") as f:
        f.write(transformer_lm_pp_prototxt())
    base = open(os.path.join(d, "train_val.prototxt")).read()
    with open(os.path.join(d, "train_val_sp.prototxt"), "w") as f:
        f.write(base.replace("causal: true",
                             "causal: true\n    sequence_parallel: true"))
    solver = open(os.path.join(d, "solver.prototxt")).read()
    for variant in ("pp", "sp"):
        with open(os.path.join(d, f"solver_{variant}.prototxt"), "w") as f:
            # the second replace also renames the snapshot_prefix line
            # (it ends in transformer_lm")
            f.write(solver.replace("train_val.prototxt",
                                   f"train_val_{variant}.prototxt")
                    .replace("transformer_lm\"",
                             f"transformer_lm_{variant}\""))
    print("wrote models/transformer_lm/ pp + sp variants")


if __name__ == "__main__":
    main()
