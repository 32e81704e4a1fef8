"""mxnet_tpu: a TPU-native deep learning framework.

A ground-up rebuild of the capability surface of Apache MXNet 0.11
(reference at /root/reference, analysed in SURVEY.md) designed for
TPU/XLA: imperative NDArray and symbolic Symbol APIs, Module training,
KVStore-style distribution over XLA collectives, Gluon-style imperative
blocks — with compute expressed as pure JAX so whole graphs compile into
single XLA modules instead of per-op kernel dispatch.
"""
import time
_import_start = time.perf_counter()     # the first statement: see import_s

__version__ = '0.1.0'

from . import base
from .base import MXNetError, NameManager, Prefix
from . import context
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus
from . import ops
from . import ndarray
from . import ndarray as nd
from . import random
from . import random as rnd
from . import autograd
from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from . import executor
from .executor import Executor
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import callback
from . import kvstore
from . import model
from . import module
from . import module as mod
from .module import Module
from . import parallel
from .io import DataBatch, DataIter, NDArrayIter, DataDesc
from . import engine
from . import rnn
from . import contrib
from . import profiler
from . import monitor
from . import monitor as mon
from . import visualization
from . import visualization as viz
from . import operator
from . import rtc
from . import registry
from . import log
from . import kvstore_server
from . import executor_manager
from . import torch_bridge
from . import torch_bridge as th
from . import predictor
from . import serving
from . import serving_fleet
from . import fleet_supervisor
from . import elastic
from . import dist
from . import pallas_ops
from .model import FeedForward
from . import recordio
from . import image
from . import gluon
from . import test_utils

# seconds this package took to import (profiler.setup_stats reads it):
# what was imported before it, jax in a process that had it, is not in
import_s = time.perf_counter() - _import_start
