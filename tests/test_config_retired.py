"""config.RETIRED: a variable the registry once held is accepted, has no
effect, says so once, and the value it defaulted to is what its
replacement — an argument's default, a constant, or nothing — gives."""
import inspect
import warnings

import pytest

from mxnet_tpu import config


def _default(fn, arg):
    return lambda: inspect.signature(fn).parameters[arg].default


def _constant(module, name):
    return lambda: getattr(module, name)


def _gone(owner, attr):
    """A deleted path: the value in force is that nothing is there."""
    return lambda: hasattr(owner, attr)


def _in_force():
    """name -> (reads the value now in force, the default the registry
    held at the parent of PR 43)."""
    from mxnet_tpu import io, quant, router, serving
    from mxnet_tpu.ckpt import CheckpointManager
    from mxnet_tpu.data import DataService
    from mxnet_tpu.executor import Executor
    from mxnet_tpu.module import Module
    from mxnet_tpu.obs import memory, recorder, tracing
    from mxnet_tpu.ops import nn
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.serving import server

    tenant = serving.ModelServer.add_generative_tenant
    return {
        # "auto" armed nothing on one process; 4.0 MB sized its buckets
        "MXTPU_COMM_BUCKETED": (_gone(Executor, "_comm_mode"), False),
        "MXTPU_COMM_BUCKET_MB": (_gone(Executor, "measure_comm"), False),
        "MXNET_TPU_PALLAS_BN": (
            lambda: "pallas" in inspect.getsource(nn.batch_norm), False),
        "MXNET_BN_STATS_SAMPLE": (
            lambda: "sample" in inspect.getsource(nn.batch_norm), False),
        "MXTPU_FROZEN_BN": (_default(Module.fit, "frozen_bn"), False),
        "MXTPU_SERVE_MAX_BATCH": (
            _default(serving.ModelServer, "max_batch"), 32),
        # "" meant powers of two, as None does
        "MXTPU_SERVE_BUCKETS": (
            lambda: _default(serving.ModelServer, "buckets")() or "", ""),
        "MXTPU_SERVE_TIMEOUT_MS": (
            _default(serving.ModelServer, "timeout_ms"), 5000.0),
        "MXTPU_SERVE_MAX_SESSIONS": (_default(tenant, "max_sessions"), 8),
        "MXTPU_SERVE_MAX_DECODE_TOKENS": (
            _default(tenant, "max_decode_tokens"), 64),
        "MXTPU_SERVE_DECODE_WINDOW_MS": (
            _constant(server, "DECODE_WINDOW_MS"), 2.0),
        "MXTPU_SERVE_KV_MAX_LEN": (_default(tenant, "max_len"), 256),
        "MXTPU_STAGE_BUFFERS": (_default(io.DeviceStagedIter, "buffers"), 2),
        "MXTPU_DATA_WORKERS": (_default(DataService, "num_workers"), 2),
        "MXTPU_DATA_RING_SLOTS": (_default(DataService, "ring_slots"), 4),
        "MXTPU_ROUTER_POLL_MS": (_default(router.Router, "poll_ms"), 200.0),
        "MXTPU_ROUTER_REDISPATCH": (
            _default(router.Router, "redispatch_cap"), 2),
        "MXTPU_ROUTER_ADAPT_WINDOW_S": (
            _default(router.Router, "adapt_window_s"), 10.0),
        "MXTPU_QUANT_CALIB_MODE": (_default(quant.calibrate, "mode"),
                                   "minmax"),
        "MXTPU_QUANT_PERCENTILE": (
            _default(quant.calibrate, "percentile"), 99.99),
        "MXTPU_QUANT_HIST_BINS": (
            _default(quant.calibrate, "hist_bins"), 2048),
        "MXTPU_QUANT_SKIP_FIRST_LAST": (
            _default(quant.quantize_symbol, "skip_first_last"), 1),
        "MXTPU_TRACE_BUFFER": (_constant(tracing, "_CAP"), 4096),
        "MXTPU_OBS_RECORDER": (_constant(recorder, "_ENABLED"), 1),
        "MXTPU_OBS_RING_SLOTS": (recorder.ring_slots, 512),
        "MXTPU_MEM_CENSUS": (memory.census_enabled, 1),
        "MXTPU_CKPT_EVERY_STEPS": (
            _default(Module.fit, "checkpoint_every_steps"), 0),
        "MXTPU_CKPT_ASYNC": (_default(CheckpointManager, "async_write"), 1),
        "MXNET_KVSTORE_PULL_TIMEOUT": (_constant(dist, "PULL_TIMEOUT"), 60.0),
        "MXNET_KVSTORE_REGISTER_TIMEOUT": (
            _constant(dist, "REGISTER_TIMEOUT"), 600.0),
    }


def test_the_table_is_the_thirty_and_the_registry_the_sixty():
    assert len(config.RETIRED) == 30 and len(config.REGISTRY) == 60
    assert not set(config.RETIRED) & {v.name for v in config.REGISTRY}
    assert set(_in_force()) == set(config.RETIRED)
    table = config.describe()
    assert all(name in table for name in config.RETIRED)


@pytest.mark.parametrize("name", sorted(config.RETIRED))
def test_a_retired_variable_warns_once_and_its_default_holds(name):
    environ = {name: "1", "MXTPU_STEPS_PER_DISPATCH": "4"}
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        config.warn_retired(environ)
    assert len(seen) == 1, [str(w.message) for w in seen]
    said = str(seen[0].message)
    assert name in said and config.RETIRED[name] in said
    assert environ == {name: "1", "MXTPU_STEPS_PER_DISPATCH": "4"}
    with pytest.raises(KeyError, match="retired") as err:
        config.get(name)
    assert config.RETIRED[name] in str(err.value)
    read, registered = _in_force()[name]
    assert read() == registered
