"""Weight initializers (parity: reference python/mxnet/initializer.py:17-655).

`InitDesc`-driven dispatch: names ending in `_weight`/`_bias`/`_gamma`/...
get the standard treatment; variables can override via `__init__` attr
(reference initializer.py InitDesc + Initializer.__call__).
"""
from __future__ import annotations

import json
import math

import numpy as _np

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray

__all__ = [
    "InitDesc", "Initializer", "Uniform", "Normal", "Zero", "One", "Constant",
    "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear", "LSTMBias", "Load", "Mixed",
    "register",
]

_INIT_REGISTRY = {}


def register(klass):
    _INIT_REGISTRY[klass.__name__.lower()] = klass
    return klass


class InitDesc(str):
    """Name + attrs descriptor for initialization (parity: initializer.py InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    """Base initializer (parity: initializer.py Initializer)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, InitDesc):
            desc = InitDesc(desc)
        init = desc.attrs.get("__init__", "")
        if init:
            klass, kwargs = json.loads(init)
            _INIT_REGISTRY[klass.lower()](**kwargs)._init_weight(desc, arr)
            return
        name = desc.lower()
        if name.endswith("weight"):
            self._init_weight(desc, arr)
        elif name.endswith("bias"):
            self._init_bias(desc, arr)
        elif name.endswith("gamma"):
            self._init_gamma(desc, arr)
        elif name.endswith("beta"):
            self._init_beta(desc, arr)
        elif name.endswith("moving_mean") or name.endswith("running_mean"):
            self._init_zero(desc, arr)
        elif name.endswith("moving_var") or name.endswith("running_var"):
            self._init_one(desc, arr)
        elif name.endswith("moving_inv_var") or name.endswith("moving_avg"):
            self._init_zero(desc, arr)
        else:
            self._init_default(desc, arr)

    def _init_bias(self, _, arr):
        arr[:] = 0.0

    def _init_gamma(self, _, arr):
        arr[:] = 1.0

    def _init_beta(self, _, arr):
        arr[:] = 0.0

    def _init_zero(self, _, arr):
        arr[:] = 0.0

    def _init_one(self, _, arr):
        arr[:] = 1.0

    def _init_weight(self, name, arr):
        raise NotImplementedError()

    def _init_default(self, name, arr):
        raise MXNetError(
            "Unknown initialization pattern for %s. "
            "Default initialization is now limited to *_weight/*_bias/*_gamma/*_beta." % name
        )


# NOTE: initializers sample on the HOST (numpy) and upload once.  Sampling
# through device ops costs one compile per distinct parameter shape plus a
# dispatch per parameter (magnitude on the TPU host: not measured); the
# reference also initializes on CPU (python/mxnet/initializer.py).


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        from .ops.random_ops import HOST_RNG

        arr[:] = HOST_RNG.uniform(-self.scale, self.scale, arr.shape).astype(_np.float32)


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        from .ops.random_ops import HOST_RNG

        arr[:] = HOST_RNG.normal(0.0, self.sigma, arr.shape).astype(_np.float32)


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 0.0


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 1.0


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        arr[:] = self.value


@register
class Orthogonal(Initializer):
    """Orthogonal init (parity: initializer.py Orthogonal; Saxe et al.)."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(_np.prod(arr.shape[1:]))
        from .ops.random_ops import HOST_RNG

        if self.rand_type == "uniform":
            tmp = HOST_RNG.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = HOST_RNG.normal(0.0, 1.0, (nout, nin))
        u, _, v = _np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else v
        arr[:] = (self.scale * res).reshape(arr.shape).astype(_np.float32)


@register
class Xavier(Initializer):
    """Xavier/Glorot (parity: initializer.py Xavier)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type, magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise MXNetError("Xavier requires ndim >= 2: %s" % str(name))
        layout = getattr(name, "attrs", {}).get("__layout__", "")
        if layout.endswith("IO"):
            # channel-last conv kernel (spatial..., I, O) — the NHWC path's
            # HWIO weights; fans computed over the right dims
            hw_scale = _np.prod(shape[:-2]) if len(shape) > 2 else 1.0
            fan_in, fan_out = shape[-2] * hw_scale, shape[-1] * hw_scale
        else:
            if len(shape) > 2:
                hw_scale = _np.prod(shape[2:])
            fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        factor = {"avg": (fan_in + fan_out) / 2.0, "in": fan_in, "out": fan_out}[self.factor_type]
        scale = math.sqrt(self.magnitude / factor)
        from .ops.random_ops import HOST_RNG

        if self.rnd_type == "uniform":
            arr[:] = HOST_RNG.uniform(-scale, scale, arr.shape).astype(_np.float32)
        else:
            arr[:] = HOST_RNG.normal(0.0, scale, arr.shape).astype(_np.float32)


@register
class MSRAPrelu(Xavier):
    """Kaiming init (parity: initializer.py MSRAPrelu)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    """Bilinear upsampling kernel (parity: initializer.py Bilinear)."""

    def _init_weight(self, _, arr):
        weight = _np.zeros(int(_np.prod(arr.shape)), dtype="float32")
        shape = arr.shape
        f = _np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(_np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = weight.reshape(shape)


@register
class LSTMBias(Initializer):
    """Init LSTM biases with forget gate bias (parity: initializer.py LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = _np.zeros(arr.shape, dtype="float32")
        num_hidden = int(b.shape[0] / 4)
        b[num_hidden : 2 * num_hidden] = self.forget_bias
        arr[:] = b

    _init_bias = _init_weight


@register
class FusedRNN(Initializer):
    """Init the packed FusedRNN parameter vector (parity: initializer.py
    FusedRNN:655): weights get `init` (default Uniform), biases zero, and
    LSTM forget-gate i2h biases get `forget_bias`.  Layout per reference
    rnn_cell.py _slice_weights (see ops/rnn_op.py)."""

    def __init__(self, init=None, num_hidden=None, num_layers=None, mode="lstm",
                 bidirectional=False, forget_bias=1.0):
        super().__init__(init=init.dumps() if hasattr(init, "dumps") else init,
                         num_hidden=num_hidden, num_layers=num_layers, mode=mode,
                         bidirectional=bidirectional, forget_bias=forget_bias)
        self._init = init or Uniform(0.07)
        if isinstance(self._init, str):
            import json as _json

            name, kwargs = _json.loads(self._init)
            self._init = _INIT_REGISTRY[name.lower()](**kwargs)
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, name, arr):
        from .ops.rnn_op import _GATES

        g = _GATES[self._mode]
        h = self._num_hidden
        l = self._num_layers
        d = 2 if self._bidirectional else 1
        flat = _np.zeros((int(_np.prod(arr.shape)),), dtype="float32")
        # infer input size from total length (reference unpack_weights:624)
        c = flat.size // d // h // g - (l - 1) * (h + d * h + 2) - h - 2
        pos = 0
        for layer in range(l):
            inp = c if layer == 0 else d * h
            for _dir in range(d):
                for rows, cols in ((g * h, inp), (g * h, h)):
                    block = _np.zeros((rows, cols), dtype="float32")
                    self._init._init_weight(name, block)
                    flat[pos:pos + rows * cols] = block.ravel()
                    pos += rows * cols
        for layer in range(l):
            for _dir in range(d):
                for _ in range(2):  # i2h bias then h2h bias
                    block = _np.zeros((g * h,), dtype="float32")
                    self._init._init_weight(name, block)
                    if self._mode == "lstm":
                        # both bias halves get forget_bias, matching the
                        # reference FusedRNN init (initializer.py:698-700)
                        block[h:2 * h] = self._forget_bias
                    flat[pos:pos + g * h] = block
                    pos += g * h
        arr[:] = flat.reshape(arr.shape)


class Load:
    """Init from a dict of arrays (parity: initializer.py Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        if isinstance(param, str):
            from .ndarray import load as nd_load

            param = nd_load(param)
        self.param = {
            k[4:] if k.startswith("arg:") or k.startswith("aux:") else k: v
            for k, v in param.items()
        }
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            if tuple(self.param[name].shape) != tuple(arr.shape):
                raise MXNetError("shape mismatch for %s" % name)
            arr[:] = self.param[name]
        else:
            if self.default_init is None:
                raise MXNetError("Cannot init %s: not in loaded param and no default" % name)
            self.default_init(name, arr)


class Mixed:
    """Regex-dispatched initializer mix (parity: initializer.py Mixed)."""

    def __init__(self, patterns, initializers):
        import re

        if len(patterns) != len(initializers):
            raise MXNetError("patterns and initializers must have same length")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(str(name)):
                init(name, arr)
                return
        raise MXNetError('Parameter "%s" did not match any pattern' % name)
