"""Optimizers (parity: reference python/mxnet/optimizer.py:13-852).

Python is the source of truth in the reference too (the C++ side has only a
vestigial SGD, reference src/optimizer/sgd-inl.h).  TPU-native design: each
update rule is a pure `_fused(w, g, states, lr, wd, t)` kernel over jax
arrays.  `update()` applies it per key (reference Updater semantics), and
`Updater.update_batch` traces ALL parameters' kernels into ONE jitted XLA
call per step — the analog of the reference's bulk-exec for the optimizer,
which removes one eager dispatch per parameter per step.
"""
from __future__ import annotations

import math
import pickle

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray, zeros
from .lr_scheduler import LRScheduler

__all__ = [
    "Optimizer", "SGD", "DCASGD", "NAG", "SGLD", "ccSGD", "Adam", "AdaGrad",
    "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam", "Test", "Updater",
    "get_updater", "create", "register", "schedule_prefix",
]


def schedule_prefix(optimizer, keys, steps):
    """Host-computed (steps, len(keys), 3) float32 prefix of the per-step
    scheduler values (lr, wd, t) for a block of `steps` fused updates.

    Advances the optimizer's update counts EXACTLY as `steps` sequential
    eager updates over `keys` would (lr/wd read before `_update_count`,
    keys visited in order, so `num_update`-driven LR schedules evolve
    identically) — the fused paths then ship the whole block's scalars as
    ONE packed host array instead of a scalar `device_put` per step/key,
    each of which is a separate host-to-device transfer (their share of
    a step on the TPU host: not measured)."""
    import numpy as _np

    out = _np.empty((int(steps), len(keys), 3), dtype=_np.float32)
    for s in range(int(steps)):
        for row, key in enumerate(keys):
            out[s, row, 0] = optimizer._get_lr(key)
            out[s, row, 1] = optimizer._get_wd(key)
            optimizer._update_count(key)
            out[s, row, 2] = optimizer._index_update_count[key]
    return out


def _state_leaves(state):
    """Flatten a create_state result to its non-None NDArray leaves."""
    if state is None:
        return []
    if isinstance(state, NDArray):
        return [state]
    return [s for s in state if s is not None]


class Optimizer:
    """Base optimizer (parity: optimizer.py Optimizer)."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict)
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        self.set_lr_mult({})
        self.set_wd_mult({})

    def create_state(self, index, weight):
        return None

    # ------------------------------------------------------------------
    # fused-kernel protocol
    # ------------------------------------------------------------------
    _fused = None  # subclasses set a pure (w, g, states, lr, wd, t) kernel

    @property
    def fused_supported(self):
        return self._fused is not None

    def _prep(self, g, dtype=None, wd_weight=None):
        """Rescale [+ wd fold] + clip (parity: the reference kernels'
        rescale_grad/clip_gradient handling).  The SGD-family kernels clip
        rescale*grad alone; the Adam/RMSProp kernels fold wd*weight BEFORE
        the clip — pass wd_weight=(wd, w) to get the latter ordering."""
        if dtype is not None:
            g = g.astype(dtype)
        g = g * self.rescale_grad
        if wd_weight is not None:
            wd, w = wd_weight
            g = g + wd * w
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def update(self, index, weight, grad, state):
        """Per-key eager update via the fused kernel (non-fused optimizers
        override this entirely)."""
        if self._fused is None:
            raise NotImplementedError()
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        leaves = _state_leaves(state)
        new_w, new_leaves = self._fused(
            weight.data, grad.data, tuple(l.data for l in leaves), lr, wd, t
        )
        weight._set_data(new_w)
        for l, v in zip(leaves, new_leaves):
            l._set_data(v)

    # ------------------------------------------------------------------
    def set_lr_mult(self, args_lr_mult):
        """Per-arg lr multipliers incl. __lr_mult__ attrs (parity: optimizer.py)."""
        self.lr_mult = {}
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd


register = Optimizer.register


@register
class SGD(Optimizer):
    """SGD with momentum & optional multi-precision (parity: optimizer.py:311).

    state layout: [momentum?] + [weight_master_copy?] (fp16 weights)."""

    def __init__(self, momentum=0.0, multi_precision=False, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.multi_precision = multi_precision

    def create_state(self, index, weight):
        if self.multi_precision and weight.dtype == jnp.float16:
            master = weight.astype("float32")
            if self.momentum != 0.0:
                return (zeros(weight.shape, weight.context, dtype="float32"), master)
            return (None, master)
        if self.momentum != 0.0:
            return zeros(weight.shape, weight.context, dtype=weight.dtype)
        return None

    def _fused(self, w, g, states, lr, wd, t):
        use_mp = self.multi_precision and w.dtype == jnp.float16
        w32 = states[-1] if use_mp else w
        g = self._prep(g, dtype=w32.dtype) + wd * w32
        new_states = []
        if self.momentum != 0.0:
            mom = states[0] * self.momentum - lr * g
            new_w = w32 + mom
            new_states.append(mom)
        else:
            new_w = w32 - lr * g
        if use_mp:
            new_states.append(new_w)
            return new_w.astype(w.dtype), tuple(new_states)
        return new_w, tuple(new_states)


@register
class ccSGD(SGD):
    """Alias of SGD (parity: optimizer.py ccSGD — kept for compatibility)."""


@register
class NAG(SGD):
    """Nesterov accelerated SGD (parity: optimizer.py:444).

    Shares SGD's state layout incl. the fp16 master-copy scheme."""

    def _fused(self, w, g, states, lr, wd, t):
        use_mp = self.multi_precision and w.dtype == jnp.float16
        w32 = states[-1] if use_mp else w
        g = self._prep(g, dtype=w32.dtype)
        gfull = g + wd * w32
        new_states = []
        if self.momentum != 0.0:
            mom = states[0] * self.momentum + gfull
            new_w = w32 - lr * (gfull + self.momentum * mom)
            new_states.append(mom)
        else:
            new_w = w32 - lr * gfull
        if use_mp:
            new_states.append(new_w)
            return new_w.astype(w.dtype), tuple(new_states)
        return new_w, tuple(new_states)


@register
class Adam(Optimizer):
    """Adam (parity: optimizer.py:515)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def _fused(self, w, g, states, lr, wd, t):
        # t may be a traced scalar in the batch path — use jnp math
        coef1 = 1.0 - self.beta1 ** jnp.float32(t)
        coef2 = 1.0 - self.beta2 ** jnp.float32(t)
        lr_t = lr * jnp.sqrt(coef2) / coef1
        # wd folds BEFORE the clip — the kernel ordering the reference's
        # python Adam inherits by dispatching to adam_update (optimizer.py:564)
        g = self._prep(g, wd_weight=(wd, w))
        mean, var = states
        m = self.beta1 * mean + (1.0 - self.beta1) * g
        v = self.beta2 * var + (1.0 - self.beta2) * g * g
        return w - lr_t * m / (jnp.sqrt(v) + self.epsilon), (m, v)


@register
class AdaGrad(Optimizer):
    """AdaGrad (parity: optimizer.py:568)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def _fused(self, w, g, states, lr, wd, t):
        g = self._prep(g)
        h = states[0] + g * g
        return w - lr * (g / jnp.sqrt(h + self.float_stable_eps) + wd * w), (h,)


@register
class RMSProp(Optimizer):
    """RMSProp, centered/non-centered (parity: optimizer.py:605)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.context), zeros(weight.shape, weight.context),
                    zeros(weight.shape, weight.context))
        return (zeros(weight.shape, weight.context),)

    def _fused(self, w, g, states, lr, wd, t):
        # wd before the clip, matching rmsprop_update/rmspropalex_update
        g = self._prep(g, wd_weight=(wd, w))
        if self.centered:
            n, gm, delta = states
            n_new = (1 - self.gamma1) * g * g + self.gamma1 * n
            g_new = (1 - self.gamma1) * g + self.gamma1 * gm
            d_new = self.gamma2 * delta - lr * g / jnp.sqrt(n_new - g_new * g_new + self.epsilon)
            new_w = w + d_new
            new_states = (n_new, g_new, d_new)
        else:
            (n,) = states
            n_new = (1 - self.gamma1) * g * g + self.gamma1 * n
            new_w = w - lr * g / jnp.sqrt(n_new + self.epsilon)
            new_states = (n_new,)
        if self.clip_weights:
            new_w = jnp.clip(new_w, -self.clip_weights, self.clip_weights)
        return new_w, new_states


@register
class AdaDelta(Optimizer):
    """AdaDelta (parity: optimizer.py:681)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context), zeros(weight.shape, weight.context))

    def _fused(self, w, g, states, lr, wd, t):
        g = self._prep(g)
        acc_g, acc_delta = states
        ag = self.rho * acc_g + (1.0 - self.rho) * g * g
        delta = jnp.sqrt(acc_delta + self.epsilon) / jnp.sqrt(ag + self.epsilon) * g
        ad = self.rho * acc_delta + (1.0 - self.rho) * delta * delta
        return w - delta - wd * w, (ag, ad)


@register
class Ftrl(Optimizer):
    """FTRL-proximal (parity: optimizer.py:730)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(**kwargs)
        self.lamda1 = lamda1
        self.beta = beta
        self.lr = learning_rate

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context), zeros(weight.shape, weight.context))

    def _fused(self, w, g, states, lr, wd, t):
        g = self._prep(g)
        dn, n = states
        d = dn + g - (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr * w
        nn = n + g * g
        new_w = (jnp.sign(d) * self.lamda1 - d) / ((self.beta + jnp.sqrt(nn)) / lr + wd) * (
            jnp.abs(d) > self.lamda1
        )
        return new_w, (d, nn)


@register
class Adamax(Optimizer):
    """AdaMax (infinity-norm Adam variant)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context), zeros(weight.shape, weight.context))

    def _fused(self, w, g, states, lr, wd, t):
        lr = lr / (1.0 - self.beta1 ** jnp.float32(t))
        g = self._prep(g, wd_weight=(wd, w))
        m_t, u_t = states
        m = self.beta1 * m_t + (1.0 - self.beta1) * g
        u = jnp.maximum(self.beta2 * u_t, jnp.abs(g))
        return w - lr * m / (u + 1e-8), (m, u)


@register
class Nadam(Optimizer):
    """Nesterov Adam."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context), zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        # m_schedule is sequential across calls — keep eager (not batch-fusable
        # without per-index schedules; matches reference semantics)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        g = self._prep(grad.data, wd_weight=(wd, weight.data))
        mom_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mom_t1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * mom_t
        m_sched_next = self.m_schedule * mom_t1
        m_t, v_t = state
        m = self.beta1 * m_t.data + (1.0 - self.beta1) * g
        v = self.beta2 * v_t.data + (1.0 - self.beta2) * g * g
        m_t._set_data(m)
        v_t._set_data(v)
        g_prime = g / (1.0 - self.m_schedule)
        m_prime = m / (1.0 - m_sched_next)
        v_prime = v / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - mom_t) * g_prime + mom_t1 * m_prime
        weight._set_data(weight.data - lr * m_bar / (jnp.sqrt(v_prime) + self.epsilon))


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (parity: optimizer.py:388)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.context), weight.copy())

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = self._prep(grad.data)
        mon, previous_weight = state
        w = weight.data
        comp = g + wd * w + self.lamda * g * g * (w - previous_weight.data)
        if mon is not None:
            m = mon.data * self.momentum - lr * comp
            mon._set_data(m)
        else:
            m = -lr * comp
        previous_weight._set_data(w)
        weight._set_data(w + m)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (parity: optimizer.py:480)."""

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = self._prep(grad.data)
        from .ops.random_ops import GLOBAL_RNG

        noise = jax.random.normal(GLOBAL_RNG.next_key(), weight.shape) * math.sqrt(lr)
        weight._set_data(weight.data - lr / 2 * (g + wd * weight.data) + noise)


@register
class Test(Optimizer):
    """Test optimizer: w += g (parity: optimizer.py Test)."""

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight._set_data(weight.data + grad.data * self.rescale_grad)
        state._set_data(weight.data)


create = Optimizer.create_optimizer


class Updater:
    """Apply an optimizer with per-key state (parity: optimizer.py get_updater).

    `update_batch` is the TPU fast path: all keys' fused kernels trace into
    one jitted call per step (compile cached on the batch structure)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self._batch_fn = None
        self._batch_sig = None

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
        self.optimizer.update(index, weight, grad, self.states[index])

    def update_batch(self, triples):
        """Apply updates for [(index, grad, weight), ...] in one fused call."""
        opt = self.optimizer
        if not opt.fused_supported:
            for index, grad, weight in triples:
                self(index, grad, weight)
            return
        entries = []
        for index, grad, weight in triples:
            if index not in self.states:
                self.states[index] = opt.create_state(index, weight)
            leaves = _state_leaves(self.states[index])
            entries.append((
                index, weight, leaves,
                weight.data, grad.data, tuple(l.data for l in leaves),
            ))
        sig = tuple((e[0], tuple(l.shape for l in e[2])) for e in entries)
        if self._batch_fn is None or self._batch_sig != sig:

            def batch_fn(ws, gs, state_tuples, scalars):
                outs = []
                for i, (w, g, st) in enumerate(zip(ws, gs, state_tuples)):
                    outs.append(opt._fused(w, g, st, scalars[i, 0], scalars[i, 1], scalars[i, 2]))
                return tuple(outs)

            self._batch_fn = jax.jit(batch_fn)
            self._batch_sig = sig
        ws = tuple(e[3] for e in entries)
        gs = tuple(e[4] for e in entries)
        sts = tuple(e[5] for e in entries)
        # ONE packed (n,3) host array for all lr/wd/t (schedule_prefix
        # reads lr/wd before _update_count, the eager-update ordering)
        scalars = schedule_prefix(opt, [e[0] for e in entries], 1)[0]
        outs = self._batch_fn(ws, gs, sts, scalars)
        for (index, weight, leaves, *_), (new_w, new_leaves) in zip(entries, outs):
            weight._set_data(new_w)
            for l, v in zip(leaves, new_leaves):
                l._set_data(v)

    def set_states(self, states):
        self.states = pickle.loads(states)

    def get_states(self):
        return pickle.dumps(dict(self.states))


def get_updater(optimizer):
    return Updater(optimizer)
