"""The benchmark's own FLOP count against the program's, once."""
import json
import os

from benchmarks.families import resnet
from benchmarks.harness import spec


def test_resnet50_flops_agree_with_the_executors_count():
    import mxnet_tpu as mx

    with open(os.path.join(spec.HERE, "configs", "resnet50.json")) as f:
        config = json.load(f)
    mine = resnet.train_flops_per_item(config)
    assert abs(mine / 1e9 - 24.5) < 0.2   # ~4.09 GMACs forward, x 2 x 3
    mod = mx.mod.Module(resnet.symbol(config), context=mx.cpu())
    mod.bind(data_shapes=[("data", (1,) + resnet.item_shape(config))],
             label_shapes=[("softmax_label", (1,))])
    mod.init_params(resnet.initializer(mx))
    theirs = mod._exec_group.execs[0].flops_per_step(is_train=True)
    assert theirs > 0 and abs(mine - theirs) / theirs < 0.01
