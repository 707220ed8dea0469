"""mxnet_tpu — a TPU-native deep learning framework.

A from-scratch framework with the capabilities of MXNet v0.10 (the
reference at /root/reference; blueprint in SURVEY.md), re-designed for
TPU hardware: JAX/XLA is the compute path (one compiled executable per
bound graph, MXU-friendly ops, SPMD sharding over device meshes for
parallelism), native host-side components handle IO, and the public API
mirrors the reference (`mx.nd`, `mx.sym`, `mx.mod`, `mx.io`, `mx.kv`,
optimizers/metrics/initializers) so reference training scripts run
unmodified with `mx.tpu()` contexts.
"""
from __future__ import annotations

from . import base
from .base import MXNetError

# one compile cache, placed before any backend can initialise (base.py)
base.compile_cache_dir()
from . import config
from .context import Context, cpu, gpu, tpu, current_context, num_tpus, num_gpus
from . import ops
from . import engine
from . import ndarray
from . import ndarray as nd
from . import lazy
from .ndarray import waitall
from . import symbol
from . import symbol as sym
from .symbol import Symbol, Variable, Group
from . import executor
from .executor import Executor
from . import random
from . import attribute
from .attribute import AttrScope
from . import name
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import data
from . import recordio
from . import kvstore
from . import kvstore as kv
from .kvstore import KVStore
from . import callback
from . import predict
from .predict import Predictor
from . import serving
from . import router
from . import quant
from . import image
from . import rtc
from . import monitor
from . import monitor as mon
from .monitor import Monitor
from . import profiler
from . import telemetry
from . import module
from . import module as mod
from .module import Module
from . import model
from .model import FeedForward
from . import rnn
from . import contrib
from . import visualization
from . import visualization as viz
from . import test_utils
from . import operator
from . import parallel
from . import executor_manager
from . import log
from . import registry
from . import notebook
from . import torch
from .torch import th

__version__ = "0.1.0"
