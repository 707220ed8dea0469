"""The `resnet` family: how a configuration file becomes the model under
test, its FLOP count and its comparison with the plain reference."""
import numpy as np

from ..reference import resnet as reference

# On the chip the model under test computes in bfloat16 (convolutions,
# matmuls and the activations between them; f32 BatchNorm statistics and
# f32 master weights) and the reference in f32 at "highest" precision.
#
# At He initialisation no tolerance on a convolution's gradient would be
# a check.  The f32 reference's OWN first-convolution gradient moves by
# 0.87 of its norm when its input images are rounded once to bfloat16
# (relative 2^-9), and a plain `jax.numpy` ResNet with bf16 convolutions
# and everything else f32 is 1.03 away from it: 50 random layers make
# the gradient chaotic (ReLU masks flip, so an error of e in the
# activations is sqrt(e) in the gradient, layer after layer), whoever
# computes it.  The Module's bf16 step reads 1.2 on the chip and 1.23 on
# the CPU, at batch 8 and at batch 32, on white noise and on smooth
# images: that is this arithmetic, not a defect (PR 22, PERF.md section
# 6).  So the comparison is made where the function is smooth: the last
# convolution of every residual branch is scaled by BRANCH_SCALE in the
# weights both sides get, which leaves every layer, shape and code path
# as it is and makes the network close to its shortcuts.  There the bf16
# step agrees with the reference as the bounds below say, and a zero, a
# sign-flipped, a mis-scaled or a partly missing gradient (all 1.0 or
# more away) does not.  Measured (PR 22): first convolution 0.282 on the
# chip and 0.297-0.311 on the CPU over 8 seeds (0.296 on a 4-device
# mesh), middle 0.276 and 0.293-0.304, classifier 0.0075 and
# 0.0095-0.0098, loss 8e-5 and 4e-5 to 7e-4; the f32 reference itself
# moves by 0.15 / 0.14 / 0.002 under the rounded input.  Each bound is
# about twice the reading (five times for the classifier, seven for the
# loss).
BRANCH_SCALE = 0.1
LOSS_RTOL = 5e-3
GRAD_RTOL = {"first": 0.6, "middle": 0.6, "classifier": 0.05}
CHECK_BATCH = 8


def _checked_weights(config):
    """The weights whose gradients are compared: the first convolution,
    the first of the middle stage, and the classifier."""
    return {"first": "conv0_weight",
            "middle": "stage%d_unit1_conv1_weight"
                      % (len(config["units"]) // 2 + 1),
            "classifier": "fc1_weight"}


def symbol(config):
    from mxnet_tpu.models.resnet import get_resnet

    hw = config["image_size"]
    return get_resnet(config["units"], config["filters"],
                      num_classes=config["num_classes"],
                      bottle_neck=config["bottleneck"],
                      image_shape=(config["channels"], hw, hw),
                      layout="NHWC")


def item_shape(config):
    hw = config["image_size"]
    return (hw, hw, config["channels"])


def train_flops_per_item(config):
    """Model FLOPs of one training step per image: 2 FLOPs per
    multiply-add of every convolution and of the classifier, forward,
    times 3 for forward plus backward (the usual accounting; BatchNorm,
    ReLU, pooling and the optimizer are not counted)."""
    macs = 0
    for _name, k, _stride, c_in, c_out, out_hw in reference.conv_plan(config):
        macs += out_hw * out_hw * k * k * c_in * c_out
    macs += config["filters"][-1] * config["num_classes"]
    return 3 * 2 * macs


def initializer(mx):
    return mx.init.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)


def check_against_reference(mx, config, contexts, seed):
    """One seeded batch through the model under test (the same dtype and
    contexts as the measured job) and through the plain reference, on
    the same seeded weights with their residual branches scaled (see
    BRANCH_SCALE): loss, and the gradients of the first convolution, a
    middle one and the classifier.  Returns (ok, facts)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n = CHECK_BATCH
    images = rng.standard_normal((n,) + item_shape(config), np.float32)
    labels = rng.integers(0, config["num_classes"], n).astype(np.float32)
    mx.random.seed(seed)
    mod = mx.mod.Module(symbol(config), context=contexts,
                        compute_dtype=config.get("compute_dtype"))
    mod.bind(data_shapes=[("data", images.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(initializer(mx))
    arg, aux = mod.get_params()
    last = "_conv3_weight" if config["bottleneck"] else "_conv2_weight"
    arg = {k: v * BRANCH_SCALE if k.endswith(last) else v
           for k, v in arg.items()}
    mod.set_params(arg, aux)
    params = {k: jnp.asarray(v.asnumpy()) for k, v in arg.items()}
    wrt = _checked_weights(config)
    batch = mx.io.DataBatch(data=[mx.nd.array(images)],
                            label=[mx.nd.array(labels)])
    mod.forward_backward(batch)
    prob = mod.get_outputs()[0].asnumpy()
    loss = float(-np.mean(np.log(
        prob[np.arange(n), labels.astype(int)].astype(np.float64))))
    exe = mod._exec_group.execs[0]
    got = {w: exe.grad_dict[w].asnumpy().astype(np.float64)
           for w in wrt.values()}

    ref_loss, ref_grads = jax.jit(
        lambda p, x, y: reference.loss_and_grads(p, config, x, y,
                                                 tuple(wrt.values()))
    )(params, jnp.asarray(images), jnp.asarray(labels))
    ref_loss = float(ref_loss)
    facts = {"loss": loss, "ref_loss": ref_loss,
             "loss_rel_err": abs(loss - ref_loss) / abs(ref_loss)}
    ok = np.isfinite(loss) and facts["loss_rel_err"] <= LOSS_RTOL
    for which, w in wrt.items():
        ref = np.asarray(ref_grads[w], np.float64)
        err = float(np.linalg.norm(got[w] - ref) / np.linalg.norm(ref))
        facts["grad_rel_err." + w] = err
        ok = ok and np.isfinite(err) and err <= GRAD_RTOL[which]
    return bool(ok), facts
