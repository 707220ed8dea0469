"""BaseModule — the high-level training interface.

Parity: reference python/mxnet/module/base_module.py (fit:375-530,
score, predict, forward_backward:188).  Structure is TPU-first: the
epoch body lives in `_run_epoch`, and each step is the fused
fwd+bwd(+update) single-dispatch path of the underlying Executor.
"""
from __future__ import annotations

import logging
import time
from collections import namedtuple

from .. import metric
from .. import ndarray
from ..base import MXNetError
from ..initializer import Uniform
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _as_list(obj):
    return obj if isinstance(obj, list) else [obj]


def _fire(callbacks, param):
    for cb in _as_list(callbacks):
        cb(param)


def _check_input_names(symbol, names, typename, throw):
    """Validate declared input names against the symbol's arguments."""
    args = symbol.list_arguments()
    bad = [n for n in names if n not in args]
    if not bad:
        return
    param_suffixes = ("_weight", "_bias", "_gamma", "_beta")
    candidates = [a for a in args if not a.endswith(param_suffixes)]
    msg = ("\033[91mYou created Module with Module(..., %s_names=%s) but "
           "input with name '%s' is not found in symbol.list_arguments(). "
           "Did you mean one of:\n\t%s\033[0m"
           % (typename, str(names), bad[0], "\n\t".join(candidates)))
    if throw:
        raise ValueError(msg)
    logging.warning(msg)


class BaseModule:
    """Base class for all modules (parity: base_module.py BaseModule)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------------
    # high-level interface
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        """One fused fwd+bwd step (parity: base_module.py:188)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _trimmed_outputs(self, batch):
        """Outputs with the last-batch padding rows removed."""
        pad = batch.pad or 0
        return [ndarray.NDArray(out.data[0:out.shape[0] - pad], out.ctx)
                for out in self.get_outputs()]

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0):
        """Evaluate on eval_data (parity: base_module.py score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric.EvalMetric):
            eval_metric = metric.create(eval_metric)
        eval_metric.reset()
        seen = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric, locals=locals()))
            seen += 1
        if score_end_callback:
            _fire(score_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=seen,
                                eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False):
        """Run prediction, collecting outputs (parity: base_module.py predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        collected = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            collected.append(self._trimmed_outputs(eval_batch))
        if not collected:
            return collected
        if not merge_batches:
            return collected
        width = len(collected[0])
        if any(len(outs) != width for outs in collected):
            raise MXNetError("Cannot merge batches: different number of outputs")
        merged = [ndarray.concatenate([outs[i] for outs in collected])
                  for i in range(width)]
        if width == 1 and not always_output_list:
            return merged[0]
        return merged

    def _block_ready(self):
        """Whether the K-step fused block path can run (Module overrides:
        requires the armed single-dispatch updater)."""
        return False

    def _apply_frozen_bn(self, force_rebind=False):
        """Rewrite the bound symbol for frozen-BN fine-tuning (Module
        overrides; see fit(frozen_bn=))."""
        raise MXNetError(
            "fit(frozen_bn=True) is not supported by %s — freeze at the "
            "symbol level instead (symbol.freeze_batchnorm + "
            "fixed_param_names=symbol.batchnorm_param_names(sym))"
            % type(self).__name__)

    def _unapply_frozen_bn(self, force_rebind=False):
        """Reverse a previous _apply_frozen_bn (Module overrides); no-op
        where freezing is unsupported — nothing can have been frozen."""

    def _flops_per_step(self):
        """Analytic FLOPs of one training step of the bound symbol, for
        the MFU gauge; 0.0 when no executor exposes a count."""
        group = getattr(self, "_exec_group", None)
        if group is None or not getattr(group, "execs", None):
            return 0.0
        return group.execs[0].flops_per_step(is_train=True)

    def _peak_flops(self):
        """Peak FLOP/s of the device(s) the bound executor computes on
        (chips x per-chip peak), or None when there is no executor or
        the device has no known peak (telemetry.peak_flops)."""
        from .. import telemetry

        group = getattr(self, "_exec_group", None)
        if group is None or not getattr(group, "execs", None):
            return None
        devices = group.execs[0].devices()
        peak = telemetry.peak_flops(devices[0])
        return peak * len(devices) if peak else None

    def _observe_steps(self, elapsed, steps):
        """Telemetry for one training dispatch covering `steps` steps:
        step-time histogram, the global step counter, and the per-step
        MFU gauge (bound symbol FLOPs / measured time / the peak of the
        device the executor runs on, telemetry.PEAK_FLOPS — not
        published on a device with no known peak).  `elapsed` is the
        `seconds` of the loop's `fit.step` / `fit.block` span."""
        from .. import telemetry

        if not telemetry.enabled():
            return
        telemetry.observe("module.step_seconds", elapsed)
        telemetry.inc("module.steps", steps)
        flops = self._flops_per_step()
        peak = self._peak_flops()
        if peak and flops > 0.0 and elapsed > 0.0:
            # clamp: the analytic count is approximate (bwd = 2x fwd by
            # convention), and MFU > 1 would only ever mean "count was
            # high", never "hardware beat its peak"
            mfu = min(1.0, flops * steps / elapsed / peak)
            telemetry.set_gauge("module.mfu", mfu)

    def _run_epoch(self, train_data, epoch, eval_metric, batch_end_callback,
                   monitor, skip=0):
        """Train one epoch; returns the batch count.  ``skip`` > 0 is
        the exact-resume path (ckpt/resume.py): fast-forward the data
        pipeline past the batches the interrupted run already consumed
        and continue the numbering from there."""
        eval_metric.reset()
        if skip:
            from ..ckpt import resume as _ckpt_resume

            _ckpt_resume.fast_forward(train_data, epoch, skip)
        k = getattr(self, "_steps_per_dispatch", 1)
        if k > 1:
            if monitor is None and self._block_ready():
                return self._run_epoch_block(train_data, epoch, eval_metric,
                                             batch_end_callback, k,
                                             skip=skip)
            self.logger.warning(
                "steps_per_dispatch=%d requested but the fused K-step "
                "block path is unavailable (non-fused optimizer, "
                "kvstore-side update, inputs_need_grad, or a monitor is "
                "installed); falling back to one dispatch per step", k)
        from .. import profiler, telemetry

        tel = telemetry.enabled()
        mgr = getattr(self, "_ckpt_mgr", None)
        nbatch = skip - 1
        for nbatch, data_batch in enumerate(train_data, skip):
            if monitor is not None:
                monitor.tic()
            with profiler.span("fit.step", cat="module") as step:
                self.forward_backward(data_batch)
                self.update()
                self.update_metric(eval_metric, data_batch.label)
            if tel:
                # update_metric read the outputs back, so the elapsed
                # time covers the real device step, not just dispatch
                self._observe_steps(step.seconds, 1)
            if mgr is not None:
                # the dispatch boundary: the snapshot D2H reads the
                # post-update arrays and the shard write overlaps the
                # next dispatches (ckpt/snapshot.py)
                mgr.note_dispatch(self, epoch, nbatch + 1, steps=1)
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric, locals=locals()))
        return nbatch + 1

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None, monitor=None,
            steps_per_dispatch=None, frozen_bn=False, resume_from=None,
            checkpoint_dir=None, checkpoint_every_steps=0):
        """Full training loop (parity: base_module.py fit:375-530).

        `steps_per_dispatch` (default: ``MXTPU_STEPS_PER_DISPATCH``) sets
        the fused block size K: each device dispatch executes K full
        fwd+bwd+update steps via one jitted lax.scan, with input blocks
        double-buffered to the device by a background engine op
        (io.DeviceStagedIter) — see docs/perf.md.  K=1 keeps the classic
        one-dispatch-per-step loop.

        `frozen_bn` turns the run into a
        frozen-BatchNorm fine-tune: every BatchNorm runs with
        ``use_global_stats`` (running stats carried bit-identical, never
        recomputed) and the BN gamma/beta parameters are excluded from
        the optimizer update (``fixed_param_names`` -> grad_req 'null',
        on both the per-step and the K-step fused dispatch paths).
        Pass pretrained ``arg_params``/``aux_params`` — frozen BN
        normalizes with whatever statistics it is given.  See
        docs/perf.md "MFU sinks" (+17.9% measured on ResNet-50).

        `checkpoint_dir` (default: ``MXTPU_CKPT_DIR``) with
        `checkpoint_every_steps` > 0 arms async
        distributed checkpoints: every rank writes write-then-rename
        shard files overlapped with the next dispatches; rank 0 commits
        the mxtpu-ckpt-v1 manifest.  `resume_from` (default:
        ``MXTPU_CKPT_RESUME``) restores the newest committed manifest
        (or an explicit manifest file) and continues the run exactly —
        params, optimizer state, lr counters, RNG streams, and data
        cursor all replay, so the resumed loss trajectory is
        bit-identical to the uninterrupted run (docs/checkpoint.md;
        a mid-epoch resume restarts epoch-cumulative metric
        accumulation at the resume batch).  An explicit `resume_from`
        with nothing committed is an error; the env-var path starts
        fresh instead (the elastic supervisor's generation-0 case)."""
        assert num_epoch is not None, "please specify number of epochs"
        if steps_per_dispatch is None:
            from .. import config

            steps_per_dispatch = config.get("MXTPU_STEPS_PER_DISPATCH")
        self._steps_per_dispatch = max(1, int(steps_per_dispatch))
        if frozen_bn:
            self._apply_frozen_bn(force_rebind)
        else:
            # an earlier fit(frozen_bn=True) must not latch: restore the
            # trainable-BN graph (no-op on never-frozen modules)
            self._unapply_frozen_bn(force_rebind)
        from .. import telemetry

        if telemetry.enabled():
            # mode gauge: a run's telemetry record says whether BN was
            # frozen (parse_log --telemetry renders the column)
            telemetry.set_gauge("module.frozen_bn", 1 if frozen_bn else 0)
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        validation_metric = validation_metric or eval_metric
        if not isinstance(eval_metric, metric.EvalMetric):
            eval_metric = metric.create(eval_metric)

        from ..ckpt import CheckpointManager
        from ..ckpt import resume as ckpt_resume

        mgr = CheckpointManager(directory=checkpoint_dir,
                                every_steps=checkpoint_every_steps)
        self._ckpt_mgr = mgr if mgr.enabled else None
        resume_required = resume_from is not None
        if resume_from is None:
            from .. import config

            resume_from = config.get("MXTPU_CKPT_RESUME") or None
        skip = 0
        if resume_from is not None:
            state = ckpt_resume.load(resume_from, required=resume_required)
            if state is not None:
                begin_epoch, skip = ckpt_resume.apply(self, state)
                mgr.set_global_step(state.step)
                self.logger.info(
                    "Resumed from checkpoint step %d (epoch %d, batch %d)"
                    " — %s", state.step, begin_epoch, skip,
                    state.manifest_file)

        try:
            for epoch in range(begin_epoch, num_epoch):
                epoch_start = time.time()
                self._run_epoch(train_data, epoch, eval_metric,
                                batch_end_callback, monitor,
                                skip=skip if epoch == begin_epoch else 0)
                self._fit_epoch_end(
                    train_data, eval_data, epoch, epoch_start, eval_metric,
                    validation_metric, epoch_end_callback,
                    eval_end_callback, eval_batch_end_callback)
                if self._ckpt_mgr is not None:
                    # epoch-boundary service: commit the pending
                    # snapshot; on an elastic regrow request, cut a
                    # boundary checkpoint and yield the shrunken slots
                    self._ckpt_mgr.epoch_end(self, epoch + 1)
                    if self._ckpt_mgr.yielded:
                        self.logger.info(
                            "Yielding at epoch %d boundary for elastic "
                            "regrow (ckpt/elastic.py)", epoch + 1)
                        break
        finally:
            if self._ckpt_mgr is not None:
                self._ckpt_mgr.finalize()
            # the elastic worker's exit contract: a shrunken generation
            # checks this after fit and exits elastic.YIELD_EXIT_CODE so
            # the supervisor relaunches at full width
            self._ckpt_yielded = mgr.yielded
            self._ckpt_mgr = None

    def _fit_epoch_end(self, train_data, eval_data, epoch, epoch_start,
                       eval_metric, validation_metric, epoch_end_callback,
                       eval_end_callback, eval_batch_end_callback):
        """Per-epoch bookkeeping split out of fit(): logging, telemetry
        flush, host param sync, user callbacks, eval, iterator reset."""
        for name, val in eval_metric.get_name_value():
            self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
        self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                         time.time() - epoch_start)
        from .. import telemetry

        if telemetry.enabled():
            # one JSONL record per epoch when MXTPU_TELEMETRY_FILE is
            # set (Speedometer adds intra-epoch records); see
            # docs/observability.md and tools/parse_log.py --telemetry
            telemetry.flush(extra={"epoch": epoch})
        # pull params to the host copy (and broadcast back), so
        # epoch_end checkpoints see the trained values
        trained_args, trained_aux = self.get_params()
        self.set_params(trained_args, trained_aux)
        if epoch_end_callback is not None:
            for cb in _as_list(epoch_end_callback):
                cb(epoch, self.symbol, trained_args, trained_aux)
        if eval_data:
            res = self.score(eval_data, validation_metric,
                             score_end_callback=eval_end_callback,
                             batch_end_callback=eval_batch_end_callback,
                             epoch=epoch)
            for name, val in res:
                self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
        train_data.reset()

    # ------------------------------------------------------------------
    # symbol/params accessors
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False, force_init=True,
                   allow_extra=False):
        self.init_params(
            initializer=None, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init, allow_extra=allow_extra,
        )

    def save_params(self, fname):
        from ..ckpt.atomic import replace_into

        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        with replace_into(fname) as tmp:
            ndarray.save(tmp, save_dict)

    def load_params(self, fname):
        loaded = ndarray.load(fname)
        arg_params, aux_params = {}, {}
        for k, value in loaded.items():
            kind, _, name = k.partition(":")
            if kind == "arg":
                arg_params[name] = value
            elif kind == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    # ------------------------------------------------------------------
    # computation interface
    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
