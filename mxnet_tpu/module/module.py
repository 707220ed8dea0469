"""Module — concrete single-symbol module.

Parity: reference python/mxnet/module/module.py (bind:333, init_params:228,
init_optimizer:442, update:571-587, save/load_checkpoint:134,701).
"""
from __future__ import annotations

import logging

from .. import ndarray
from .. import optimizer as opt
from ..base import MXNetError
from ..context import cpu, current_context
from ..initializer import Uniform, InitDesc
from ..model import (
    BatchEndParam,
    _create_kvstore,
    _initialize_kvstore,
    _update_params,
    _update_params_on_kvstore,
    load_checkpoint,
    save_checkpoint,
)
from ..ndarray import zeros
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    """Single-symbol module over one or more contexts (parity: module.py Module)."""

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, mesh=None, sharding_map=None, group2ctx=None,
                 compute_dtype=None, mirror=None):
        """`mesh`/`sharding_map` expose user-facing tensor parallelism: pass
        a `jax.sharding.Mesh` (e.g. parallel.mesh.make_mesh({'data': -1,
        'model': 2})) plus {param_name: PartitionSpec} and the single SPMD
        executable shards those params over the 'model' axis, XLA inserting
        the ICI collectives.  `group2ctx` gives reference model-parallel
        scripts the same effect from ctx_group annotations."""
        super().__init__(logger=logger)
        self._mesh = mesh
        self._sharding_map = dict(sharding_map or {})
        self._group2ctx = group2ctx
        self._compute_dtype = compute_dtype
        # memory mirroring (reference MXNET_BACKWARD_DO_MIRROR): recompute
        # cheap activations in backward; None defers to the env var
        self._mirror = mirror
        if context is None:
            context = [current_context()]
        if not isinstance(context, list):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list
        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    # ------------------------------------------------------------------
    # checkpointing (parity: module.py save_checkpoint:134 / load:701)
    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..ckpt.atomic import replace_into

        with replace_into("%s-symbol.json" % prefix) as tmp:
            self._symbol.save(tmp)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, tuple(o.shape)) for n, o in zip(self._output_names,
                                                    self._exec_group.get_outputs())]

    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            logging.warning("Parameters already initialized and force_init=False. "
                            "init_params call ignored.")
            return
        assert self.binded, "call bind before initializing the parameters"

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        arr[:] = cache_arr
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(name, arr)
            else:
                if initializer is not None:
                    attrs = self._symbol.attr_dict()
                    desc = InitDesc(name, attrs.get(name, None) or {})
                    initializer(desc, arr)

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)
        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (parity: module.py bind:333)."""
        if force_rebind:
            if self.binded and self.params_initialized and self._params_dirty:
                # pull trained values off the device before discarding the
                # executor (same hazard reshape guards): the rebind below
                # seeds the fresh executor from the HOST params, which go
                # stale whenever update() ran outside fit's epoch sync
                self._sync_params_from_devices()
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        if not for_training:
            assert not inputs_need_grad
        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else None
        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and shared_module.binded and \
                shared_module.params_initialized
            shared_group = shared_module._exec_group
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names, mesh=self._mesh,
            param_shardings=self._sharding_map, group2ctx=self._group2ctx,
            compute_dtype=self._compute_dtype, mirror=self._mirror,
        )
        self._total_exec_bytes = 0
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)
        else:
            assert self._arg_params is None and self._aux_params is None
            self._arg_params = {
                name: zeros(x[0].shape, ctx=cpu(), dtype=x[0].dtype)
                for name, x in zip(self._param_names, self._exec_group.param_arrays)
            }
            self._aux_params = {
                name: zeros(x[0].shape, ctx=cpu(), dtype=x[0].dtype)
                for name, x in zip(self._aux_names, self._exec_group.aux_arrays)
            }
        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)
        if (self.optimizer_initialized and self._updater is not None
                and not self._update_on_kvstore):
            # binding with a live optimizer — a force_rebind on a trained
            # Module (init_optimizer early-returns, e.g. fit(frozen_bn=
            # True, force_rebind=True)) or a bucket module that just
            # borrowed the shared updater above — must arm the fused
            # single-dispatch update on the fresh executor; otherwise
            # update() silently falls back to the multi-dispatch
            # _update_params path (arming is name-keyed and idempotent)
            self._maybe_install_fused_update()

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    def _apply_frozen_bn(self, force_rebind=False):
        """Swap in the frozen-BN symbol and pin its gamma/beta params
        (the Module half of fit(frozen_bn=True); symbol.freeze_batchnorm
        is the graph half).  Idempotent: a second frozen fit reuses the
        transform; fit(frozen_bn=False) reverses it via
        _unapply_frozen_bn — the mode is per-fit, not a one-way latch."""
        from ..symbol import batchnorm_param_names, freeze_batchnorm

        if getattr(self, "_bn_frozen", False):
            return
        if self.binded and not force_rebind:
            raise MXNetError(
                "fit(frozen_bn=True) on an already-bound Module: the "
                "executor was compiled with trainable BN — pass "
                "force_rebind=True (host-side param values carry over)")
        self._pre_freeze_symbol = self._symbol
        bn_params = batchnorm_param_names(self._symbol)
        self._symbol = freeze_batchnorm(self._symbol)
        self._frozen_bn_params = [n for n in bn_params
                                  if n not in self._fixed_param_names]
        self._fixed_param_names.extend(self._frozen_bn_params)
        self._bn_frozen = True

    def _unapply_frozen_bn(self, force_rebind=False):
        """Reverse _apply_frozen_bn: restore the trainable-BN symbol and
        un-pin the BN params, so fit(frozen_bn=False) after a frozen fit
        really resumes normal training instead of silently keeping BN
        frozen.  No-op on a Module that was never frozen (the normal fit
        path calls this unconditionally)."""
        if not getattr(self, "_bn_frozen", False):
            return
        if self.binded and not force_rebind:
            raise MXNetError(
                "fit(frozen_bn=False) on a Module frozen by an earlier "
                "fit(frozen_bn=True): the executor was compiled with "
                "frozen BN — pass force_rebind=True (host-side param "
                "values carry over)")
        self._symbol = self._pre_freeze_symbol
        for n in self._frozen_bn_params:
            self._fixed_param_names.remove(n)
        self._frozen_bn_params = []
        self._bn_frozen = False

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        """Init optimizer + kvstore plumbing (parity: module.py:442)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size
        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {i * len(self._context) + k: n
                         for i, n in enumerate(self._exec_group.param_names)}
                    )
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad=%s != 1.0/batch=%s. "
                    "Is this intended?", optimizer.rescale_grad, rescale_grad)
        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
            self._maybe_install_fused_update()
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    # computation
    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        # reference parity (module.py forward:600): a batch whose shapes
        # differ from the bound ones reshapes the executor instead of
        # erroring.  (Bucketed flows rarely get here — BucketingModule
        # keys a module per (bucket_key, batch shape) — this is for plain
        # Modules fed variable shapes, e.g. a last partial batch.)  The
        # reshape rides Executor.reshape (executor_group bind_exec
        # reshape=True), which shares the parameter arrays and keeps the
        # fused updater armed.
        from ..io import desc_shape, redesc

        curr_shapes = [desc_shape(d) for d in self._data_shapes]
        new_shapes = [tuple(x.shape) for x in data_batch.data]
        if curr_shapes != new_shapes:
            if getattr(data_batch, "provide_data", None):
                new_dshape = data_batch.provide_data
            else:
                new_dshape = [redesc(d, x) for d, x
                              in zip(self._data_shapes, new_shapes)]
            if getattr(data_batch, "provide_label", None):
                new_lshape = data_batch.provide_label
            elif self._label_shapes and data_batch.label:
                new_lshape = [redesc(d, tuple(x.shape)) for d, x
                              in zip(self._label_shapes, data_batch.label)]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)
        self._exec_group.forward(data_batch, is_train)

    def forward_backward(self, data_batch):
        """One fused training step — or, given a StagedBlock, a K-step
        block: the stacked batches are staged on the executor and the
        whole fwd+bwd+update×K runs as ONE dispatch at update()."""
        from ..io import StagedBlock

        if isinstance(data_batch, StagedBlock):
            assert self._block_ready(), (
                "K-step block dispatch needs the fused updater armed "
                "(init_optimizer with a fused-capable optimizer, no "
                "kvstore-side update)")
            self._exec_group.stage_block(data_batch)
            return
        super().forward_backward(data_batch)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def _block_ready(self):
        """The K-step fused block path needs the single-dispatch fused
        updater armed (fused-capable optimizer, updater-side update,
        plain 'write' grad_req, no monitor)."""
        return (self.binded and self.optimizer_initialized
                and self._exec_group is not None
                and getattr(self._exec_group.execs[0], "_fused_updater",
                            None) is not None)

    def _run_epoch_block(self, train_data, epoch, eval_metric,
                         batch_end_callback, k, skip=0):
        """Blocked epoch body: K steps per dispatch, inputs double-
        buffered to the device by a background engine op, metrics
        consumed once per dispatch from the stacked outputs.  ``skip``
        continues the batch numbering after an exact resume — the data
        fast-forward already happened in _run_epoch, and checkpoints
        only cut at dispatch boundaries, so skip is a multiple of K and
        the block boundaries line up with the interrupted run's.

        The loop keeps ONE block queued on the devices behind the one
        that runs: a pass dispatches block n+1 and only then reads block
        n's outputs, feeds the metric and fires block n's callback, so
        the devices hold their next program whenever one ends.  That
        read is the fence: the loop never queues block n+2 before block
        n has finished.  The epoch's last pass dispatches nothing and
        reads the block still in flight, so every block has been read
        and called back when this returns.  ``batch_end_callback`` sees
        what it always saw — once a dispatch, in order, ``nbatch`` the
        last step of the block whose outputs were just read and
        ``eval_metric`` holding the blocks up to it — except that the
        module's parameters and outputs are by then ONE BLOCK NEWER
        than that metric.  The checkpoint manager is told of a block as
        soon as it is dispatched, with that block's cursor: a snapshot
        reads the arrays it names."""
        from .. import profiler, telemetry
        from ..io import DeviceStagedIter
        from ..obs import recorder
        from .base_module import _fire

        group = self._exec_group
        exe = group.execs[0]
        staged = DeviceStagedIter(train_data, steps_per_dispatch=k,
                                  place_fn=exe.place_step_input,
                                  stack_fn=exe.stack_block_input)
        nbatch = skip
        tel = telemetry.enabled()
        mgr = getattr(self, "_ckpt_mgr", None)
        sent = None     # (block, its stacked outputs, nbatch after it): unread
        unbooked = 0.0  # fit.block seconds _observe_steps has not seen

        def take():
            """The next staged block, None at the epoch's end.  While a
            dispatched block is unread the wait is a `stage_wait` span
            of the flight recorder, numbered like that dispatch: staging
            may queue device work behind the block in flight (the CPU
            backend's split and stack, a device-resident source's), so
            if that block hangs in a collective the loop waits HERE and
            not in the read, and the stall watchdog must see it."""
            if sent is None:
                return next(staged, None)
            rec = recorder.enabled()
            if rec:
                recorder.record("stage_wait", "enter", exe._train_dispatches)
            try:
                return next(staged, None)
            finally:
                if rec:
                    recorder.record("stage_wait", "exit",
                                    exe._train_dispatches)

        def read(sent):
            block, outputs, _ = sent
            if block.label_host is not None:
                # the first read of that dispatch's outputs: the loop
                # thread waiting for the device
                with profiler.span("fit.device_wait", cat="module",
                                   hist="module.device_wait_seconds"):
                    group.update_block_metric(eval_metric, block.label_host,
                                              outputs)

        def finish(sent, seconds):
            block, _, done = sent
            if tel:
                # one observation per DISPATCH (covering K steps): the
                # histogram count is the dispatch count and the MFU
                # gauge normalizes by block.count steps
                self._observe_steps(seconds, block.count)
            if batch_end_callback is not None:
                # one callback per dispatch (nbatch = last step index):
                # per-step callbacks would force per-step host sync,
                # defeating the amortization
                with profiler.span("fit.callback", cat="module"):
                    _fire(batch_end_callback,
                          BatchEndParam(epoch=epoch, nbatch=done - 1,
                                        eval_metric=eval_metric,
                                        locals=locals()))

        try:
            for block in iter(take, None):
                with profiler.span("fit.block", cat="module", k=block.count,
                                   block=block.seq) as disp:
                    self.forward_backward(block)
                    self.update()
                    nbatch += block.count
                    if mgr is not None:
                        # dispatch boundary: snapshot D2H sees the
                        # post-block arrays; the shard write overlaps the
                        # next dispatch
                        mgr.note_dispatch(self, epoch, nbatch,
                                          steps=block.count)
                    ahead = (block, exe.outputs, nbatch)
                    if sent is not None:
                        if tel:
                            telemetry.inc("module.runahead_blocks")
                        read(sent)
                unbooked += disp.seconds
                if sent is not None:
                    finish(sent, unbooked)
                    unbooked = 0.0
                sent = ahead
            if sent is not None:
                # the epoch's last pass: nothing to dispatch, one block
                # still unread
                with profiler.span("fit.block", cat="module",
                                   k=sent[0].count,
                                   block=sent[0].seq) as disp:
                    read(sent)
                finish(sent, unbooked + disp.seconds)
        finally:
            staged.close()  # the epoch owns train_data; fit resets it
        return nbatch

    def _maybe_install_fused_update(self):
        """Arm the single-dispatch fwd+bwd+update step when safe:
        fused-capable optimizer, no kvstore round-trip, plain 'write'
        grad_req, no input grads (those need materialized grad_dict)."""
        exe = self._exec_group.execs[0]
        # fixed params (fixed_param_names, e.g. frozen-BN gamma/beta) ride
        # the fused dispatch as non-donated static args — grad_req 'null'
        # for THOSE must not disarm the single-dispatch path; 'null' from
        # any other source (and 'add'/'add'-like reqs) still does
        fixed = set(self._fixed_param_names)
        reqs = {n: exe._grad_req.get(n) for n in self._param_names}
        if (
            self._optimizer.fused_supported
            and self._kvstore is None
            and not self.inputs_need_grad
            and all(r == "write" or (r == "null" and n in fixed)
                    for n, r in reqs.items())
            and any(r == "write" for r in reqs.values())
            and exe._monitor_callback is None
        ):
            # updater state is keyed by NAME (same contract as
            # model._update_params): positional keys cross-wire shared
            # optimizer state between executables with different param
            # orders, e.g. bucketing over different-depth graphs
            index_of_name = {name: name
                             for name in self._exec_group.param_names}
            exe.install_fused_update(self._updater, index_of_name)

    def update(self):
        """Apply optimizer using accumulated grads (parity: module.py update:571)."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        exe = self._exec_group.execs[0]
        if getattr(exe, "_pending_fused_block", False):
            exe.fused_update_block()
            return
        if getattr(exe, "_pending_fused", False):
            if getattr(exe, "_fused_updater", None) is not None:
                exe.fused_update()
                return
            # disarmed between backward and update (e.g. monitor installed):
            # materialize the deferred backward so grads are real
            exe._pending_fused = False
            exe.backward()
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                self._kvstore, self._exec_group.param_names,
            )
        else:
            _update_params(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                updater=self._updater, num_device=len(self._context),
                kvstore=self._kvstore, param_names=self._exec_group.param_names,
            )

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        from ..ckpt.atomic import replace_into

        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with replace_into(fname) as tmp, open(tmp, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        self._exec_group.install_monitor(mon)

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        if self._params_dirty:
            # pull current weights off the device before rebinding, or the
            # fresh executor would be seeded from stale host-side params
            self._sync_params_from_devices()
        self._exec_group.bind_exec(data_shapes, label_shapes, reshape=True)
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes
        self._exec_group.set_params(self._arg_params, self._aux_params)
