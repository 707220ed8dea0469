"""Plain reference for the `resnet` family: pre-activation ResNet
(He et al., arXiv:1603.05027 layout; widths and depths of
arXiv:1512.03385 Table 1) in straightforward float32 `jax.numpy`, NHWC,
training-mode BatchNorm (batch statistics, biased variance), softmax
cross-entropy.  Independent of `mxnet_tpu`: only the parameter names and
the layer order follow the model under test, so that one seeded set of
weights runs through both.

Departures from the paper, as the model under test has them: a
BatchNorm with gamma fixed to 1 on the raw input (`bn_data`); the
projection shortcut branches off after the unit's first BN+ReLU.
"""
import jax
import jax.numpy as jnp
from jax import lax

EPS = 2e-5
HIGHEST = lax.Precision.HIGHEST


def conv_plan(config):
    """Every convolution of the network as (name, kernel, stride, c_in,
    c_out, out_hw), in forward order; the stem's pooling is accounted
    for in `out_hw`.  Shared by the forward pass below and by the FLOP
    count (benchmarks/families/resnet.py)."""
    units, filters = config["units"], config["filters"]
    hw = config["image_size"]
    plan = []
    hw = (hw + 2 * 3 - 7) // 2 + 1
    plan.append(("conv0", 7, 2, config["channels"], filters[0], hw))
    hw = (hw + 2 * 1 - 3) // 2 + 1  # 3x3/2 max pool
    c_in = filters[0]
    for i, n_units in enumerate(units):
        c_out = filters[i + 1]
        for j in range(n_units):
            stride = 2 if (j == 0 and i > 0) else 1
            name = "stage%d_unit%d" % (i + 1, j + 1)
            out = (hw + 2 - 3) // stride + 1
            if config["bottleneck"]:
                mid = c_out // 4
                plan.append((name + "_conv1", 1, 1, c_in, mid, hw))
                plan.append((name + "_conv2", 3, stride, mid, mid, out))
                plan.append((name + "_conv3", 1, 1, mid, c_out, out))
            else:
                plan.append((name + "_conv1", 3, stride, c_in, c_out, out))
                plan.append((name + "_conv2", 3, 1, c_out, c_out, out))
            if j == 0:
                plan.append((name + "_sc", 1, stride, c_in, c_out, out))
            hw, c_in = out, c_out
    return plan


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + beta


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def logits(params, config, images):
    p = params

    def bn(x, name):
        return _bn(x, p[name + "_gamma"], p[name + "_beta"])

    x = _bn(images, 1.0, p["bn_data_beta"])  # gamma fixed to 1
    x = _conv(x, p["conv0_weight"], 2, 3)
    x = jax.nn.relu(bn(x, "bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for i, n_units in enumerate(config["units"]):
        for j in range(n_units):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            stride = 2 if (j == 0 and i > 0) else 1
            act1 = jax.nn.relu(bn(x, name + "_bn1"))
            if config["bottleneck"]:
                y = _conv(act1, p[name + "_conv1_weight"], 1, 0)
                y = jax.nn.relu(bn(y, name + "_bn2"))
                y = _conv(y, p[name + "_conv2_weight"], stride, 1)
                y = jax.nn.relu(bn(y, name + "_bn3"))
                y = _conv(y, p[name + "_conv3_weight"], 1, 0)
            else:
                y = _conv(act1, p[name + "_conv1_weight"], stride, 1)
                y = jax.nn.relu(bn(y, name + "_bn2"))
                y = _conv(y, p[name + "_conv2_weight"], 1, 1)
            if j == 0:
                x = y + _conv(act1, p[name + "_sc_weight"], stride, 0)
            else:
                x = y + x
    x = jax.nn.relu(bn(x, "bn1"))
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, p["fc1_weight"].T, precision=HIGHEST) + p["fc1_bias"]


def summed_cross_entropy(params, config, images, labels):
    """Cross-entropy summed over the batch: the loss whose gradient the
    model under test's softmax head back-propagates (no 1/batch)."""
    lg = logits(params, config, images)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=1))


def loss_and_grads(params, config, images, labels, wrt):
    """(mean cross-entropy, {name: d summed-CE / d params[name]})."""
    with jax.default_matmul_precision("highest"):
        def f(sub):
            return summed_cross_entropy(dict(params, **sub), config,
                                        images, labels)

        total, grads = jax.value_and_grad(f)({n: params[n] for n in wrt})
    return total / images.shape[0], grads
