"""Inception-v3 (reference example/image-classification/symbols/inception-v3.py
behavior — BASELINE benchmark model #2).

`layout="NHWC"` builds the TPU-native channel-last graph (conv weights
HWIO — the layout that keeps the fast bf16 grad kernels reachable,
README Roofline item 2), threaded through every tower exactly like
models/resnet.py.  The 299^2 3x3/s2 stem conv is eligible for the
space-to-depth rewrite (`MXNET_TPU_S2D_STEM`, ops/nn.py
space_to_depth_stem): the switch's effect on this
model is not measured on the chip (no cell runs Inception-v3;
ROADMAP.md D4)."""
from .. import symbol as sym

__all__ = ["get_inception_v3"]


def _caxis(layout):
    """Channel axis for BatchNorm/Concat under the given data layout."""
    return -1 if layout.endswith("C") else 1


def ConvFactory(data, num_filter, kernel, stride=(1, 1), pad=(0, 0), name="", suffix="",
                layout="NCHW"):
    conv = sym.Convolution(data, num_filter=num_filter, kernel=kernel, stride=stride, pad=pad,
                           no_bias=True, layout=layout, name="%s%s_conv2d" % (name, suffix))
    bn = sym.BatchNorm(conv, fix_gamma=True, axis=_caxis(layout),
                       name="%s%s_batchnorm" % (name, suffix))
    act = sym.Activation(bn, act_type="relu", name="%s%s_relu" % (name, suffix))
    return act


def Inception7A(data, num_1x1, num_3x3_red, num_3x3_1, num_3x3_2, num_5x5_red, num_5x5,
                pool, proj, name, layout="NCHW"):
    tower_1x1 = ConvFactory(data, num_1x1, (1, 1), name="%s_conv" % name, layout=layout)
    tower_5x5 = ConvFactory(data, num_5x5_red, (1, 1), name="%s_tower" % name, suffix="_conv", layout=layout)
    tower_5x5 = ConvFactory(tower_5x5, num_5x5, (5, 5), pad=(2, 2), name="%s_tower" % name,
                            suffix="_conv_1", layout=layout)
    tower_3x3 = ConvFactory(data, num_3x3_red, (1, 1), name="%s_tower_1" % name, suffix="_conv", layout=layout)
    tower_3x3 = ConvFactory(tower_3x3, num_3x3_1, (3, 3), pad=(1, 1), name="%s_tower_1" % name,
                            suffix="_conv_1", layout=layout)
    tower_3x3 = ConvFactory(tower_3x3, num_3x3_2, (3, 3), pad=(1, 1), name="%s_tower_1" % name,
                            suffix="_conv_2", layout=layout)
    pooling = sym.Pooling(data, kernel=(3, 3), stride=(1, 1), pad=(1, 1), pool_type=pool,
                          name="%s_pool_%s_pool" % (pool, name), layout=layout)
    cproj = ConvFactory(pooling, proj, (1, 1), name="%s_tower_2" % name, suffix="_conv", layout=layout)
    return sym.Concat(tower_1x1, tower_5x5, tower_3x3, cproj, name="ch_concat_%s_chconcat" % name, dim=_caxis(layout))


def Inception7B(data, num_3x3, num_d3x3_red, num_d3x3_1, num_d3x3_2, pool, name,
                layout="NCHW"):
    tower_3x3 = ConvFactory(data, num_3x3, (3, 3), pad=(0, 0), stride=(2, 2),
                            name="%s_conv" % name, layout=layout)
    tower_d3x3 = ConvFactory(data, num_d3x3_red, (1, 1), name="%s_tower" % name, suffix="_conv", layout=layout)
    tower_d3x3 = ConvFactory(tower_d3x3, num_d3x3_1, (3, 3), pad=(1, 1), stride=(1, 1),
                             name="%s_tower" % name, suffix="_conv_1", layout=layout)
    tower_d3x3 = ConvFactory(tower_d3x3, num_d3x3_2, (3, 3), pad=(0, 0), stride=(2, 2),
                             name="%s_tower" % name, suffix="_conv_2", layout=layout)
    pooling = sym.Pooling(data, kernel=(3, 3), stride=(2, 2), pad=(0, 0), pool_type="max",
                          name="max_pool_%s_pool" % name, layout=layout)
    return sym.Concat(tower_3x3, tower_d3x3, pooling, name="ch_concat_%s_chconcat" % name, dim=_caxis(layout))


def Inception7C(data, num_1x1, num_d7_red, num_d7_1, num_d7_2, num_q7_red, num_q7_1,
                num_q7_2, num_q7_3, num_q7_4, pool, proj, name, layout="NCHW"):
    tower_1x1 = ConvFactory(data, num_1x1, (1, 1), name="%s_conv" % name, layout=layout)
    tower_d7 = ConvFactory(data, num_d7_red, (1, 1), name="%s_tower" % name, suffix="_conv", layout=layout)
    tower_d7 = ConvFactory(tower_d7, num_d7_1, (1, 7), pad=(0, 3), name="%s_tower" % name,
                           suffix="_conv_1", layout=layout)
    tower_d7 = ConvFactory(tower_d7, num_d7_2, (7, 1), pad=(3, 0), name="%s_tower" % name,
                           suffix="_conv_2", layout=layout)
    tower_q7 = ConvFactory(data, num_q7_red, (1, 1), name="%s_tower_1" % name, suffix="_conv", layout=layout)
    tower_q7 = ConvFactory(tower_q7, num_q7_1, (7, 1), pad=(3, 0), name="%s_tower_1" % name,
                           suffix="_conv_1", layout=layout)
    tower_q7 = ConvFactory(tower_q7, num_q7_2, (1, 7), pad=(0, 3), name="%s_tower_1" % name,
                           suffix="_conv_2", layout=layout)
    tower_q7 = ConvFactory(tower_q7, num_q7_3, (7, 1), pad=(3, 0), name="%s_tower_1" % name,
                           suffix="_conv_3", layout=layout)
    tower_q7 = ConvFactory(tower_q7, num_q7_4, (1, 7), pad=(0, 3), name="%s_tower_1" % name,
                           suffix="_conv_4", layout=layout)
    pooling = sym.Pooling(data, kernel=(3, 3), stride=(1, 1), pad=(1, 1), pool_type=pool,
                          name="%s_pool_%s_pool" % (pool, name), layout=layout)
    cproj = ConvFactory(pooling, proj, (1, 1), name="%s_tower_2" % name, suffix="_conv", layout=layout)
    return sym.Concat(tower_1x1, tower_d7, tower_q7, cproj, name="ch_concat_%s_chconcat" % name, dim=_caxis(layout))


def Inception7D(data, num_3x3_red, num_3x3, num_d7_3x3_red, num_d7_1, num_d7_2, num_d7_3x3,
                pool, name, layout="NCHW"):
    tower_3x3 = ConvFactory(data, num_3x3_red, (1, 1), name="%s_tower" % name, suffix="_conv", layout=layout)
    tower_3x3 = ConvFactory(tower_3x3, num_3x3, (3, 3), stride=(2, 2), name="%s_tower" % name,
                            suffix="_conv_1", layout=layout)
    tower_d7_3x3 = ConvFactory(data, num_d7_3x3_red, (1, 1), name="%s_tower_1" % name,
                               suffix="_conv", layout=layout)
    tower_d7_3x3 = ConvFactory(tower_d7_3x3, num_d7_1, (1, 7), pad=(0, 3),
                               name="%s_tower_1" % name, suffix="_conv_1", layout=layout)
    tower_d7_3x3 = ConvFactory(tower_d7_3x3, num_d7_2, (7, 1), pad=(3, 0),
                               name="%s_tower_1" % name, suffix="_conv_2", layout=layout)
    tower_d7_3x3 = ConvFactory(tower_d7_3x3, num_d7_3x3, (3, 3), stride=(2, 2),
                               name="%s_tower_1" % name, suffix="_conv_3", layout=layout)
    pooling = sym.Pooling(data, kernel=(3, 3), stride=(2, 2), pool_type=pool,
                          name="%s_pool_%s_pool" % (pool, name), layout=layout)
    return sym.Concat(tower_3x3, tower_d7_3x3, pooling, name="ch_concat_%s_chconcat" % name, dim=_caxis(layout))


def Inception7E(data, num_1x1, num_d3_red, num_d3_1, num_d3_2, num_3x3_d3_red, num_3x3,
                num_3x3_d3_1, num_3x3_d3_2, pool, proj, name, layout="NCHW"):
    tower_1x1 = ConvFactory(data, num_1x1, (1, 1), name="%s_conv" % name, layout=layout)
    tower_d3 = ConvFactory(data, num_d3_red, (1, 1), name="%s_tower" % name, suffix="_conv", layout=layout)
    tower_d3_a = ConvFactory(tower_d3, num_d3_1, (1, 3), pad=(0, 1), name="%s_tower" % name,
                             suffix="_mixed_conv", layout=layout)
    tower_d3_b = ConvFactory(tower_d3, num_d3_2, (3, 1), pad=(1, 0), name="%s_tower" % name,
                             suffix="_mixed_conv_1", layout=layout)
    tower_3x3_d3 = ConvFactory(data, num_3x3_d3_red, (1, 1), name="%s_tower_1" % name,
                               suffix="_conv", layout=layout)
    tower_3x3_d3 = ConvFactory(tower_3x3_d3, num_3x3, (3, 3), pad=(1, 1),
                               name="%s_tower_1" % name, suffix="_conv_1", layout=layout)
    tower_3x3_d3_a = ConvFactory(tower_3x3_d3, num_3x3_d3_1, (1, 3), pad=(0, 1),
                                 name="%s_tower_1" % name, suffix="_mixed_conv", layout=layout)
    tower_3x3_d3_b = ConvFactory(tower_3x3_d3, num_3x3_d3_2, (3, 1), pad=(1, 0),
                                 name="%s_tower_1" % name, suffix="_mixed_conv_1", layout=layout)
    pooling = sym.Pooling(data, kernel=(3, 3), stride=(1, 1), pad=(1, 1), pool_type=pool,
                          name="%s_pool_%s_pool" % (pool, name), layout=layout)
    cproj = ConvFactory(pooling, proj, (1, 1), name="%s_tower_2" % name, suffix="_conv", layout=layout)
    return sym.Concat(tower_1x1, tower_d3_a, tower_d3_b, tower_3x3_d3_a, tower_3x3_d3_b, cproj,
                      name="ch_concat_%s_chconcat" % name, dim=_caxis(layout))


def get_inception_v3(num_classes=1000, layout="NCHW"):
    data = sym.Variable("data")
    # stage 1
    conv = ConvFactory(data, 32, (3, 3), stride=(2, 2), name="conv", layout=layout)
    conv_1 = ConvFactory(conv, 32, (3, 3), name="conv_1", layout=layout)
    conv_2 = ConvFactory(conv_1, 64, (3, 3), pad=(1, 1), name="conv_2", layout=layout)
    pool = sym.Pooling(conv_2, kernel=(3, 3), stride=(2, 2), pool_type="max", name="pool", layout=layout)
    # stage 2
    conv_3 = ConvFactory(pool, 80, (1, 1), name="conv_3", layout=layout)
    conv_4 = ConvFactory(conv_3, 192, (3, 3), name="conv_4", layout=layout)
    pool1 = sym.Pooling(conv_4, kernel=(3, 3), stride=(2, 2), pool_type="max", name="pool1", layout=layout)
    # stage 3
    in3a = Inception7A(pool1, 64, 64, 96, 96, 48, 64, "avg", 32, "mixed", layout=layout)
    in3b = Inception7A(in3a, 64, 64, 96, 96, 48, 64, "avg", 64, "mixed_1", layout=layout)
    in3c = Inception7A(in3b, 64, 64, 96, 96, 48, 64, "avg", 64, "mixed_2", layout=layout)
    in3d = Inception7B(in3c, 384, 64, 96, 96, "max", "mixed_3", layout=layout)
    # stage 4
    in4a = Inception7C(in3d, 192, 128, 128, 192, 128, 128, 128, 128, 192, "avg", 192, "mixed_4", layout=layout)
    in4b = Inception7C(in4a, 192, 160, 160, 192, 160, 160, 160, 160, 192, "avg", 192, "mixed_5", layout=layout)
    in4c = Inception7C(in4b, 192, 160, 160, 192, 160, 160, 160, 160, 192, "avg", 192, "mixed_6", layout=layout)
    in4d = Inception7C(in4c, 192, 192, 192, 192, 192, 192, 192, 192, 192, "avg", 192, "mixed_7", layout=layout)
    in4e = Inception7D(in4d, 192, 320, 192, 192, 192, 192, "max", "mixed_8", layout=layout)
    # stage 5
    in5a = Inception7E(in4e, 320, 384, 384, 384, 448, 384, 384, 384, "avg", 192, "mixed_9", layout=layout)
    in5b = Inception7E(in5a, 320, 384, 384, 384, 448, 384, 384, 384, "max", 192, "mixed_10", layout=layout)
    # pool
    pool = sym.Pooling(in5b, kernel=(8, 8), stride=(1, 1), pool_type="avg", global_pool=True,
                       name="global_pool", layout=layout)
    flatten = sym.Flatten(pool, name="flatten")
    fc1 = sym.FullyConnected(flatten, num_hidden=num_classes, name="fc1")
    return sym.SoftmaxOutput(fc1, name="softmax")
