"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Brand-new design (not a port) of the reference ``MXNetEdge/incubator-mxnet``
per ``SURVEY.md``: imperative NDArray + per-op autograd, Gluon blocks with
``hybridize()`` -> XLA jit, KVStore over ICI/DCN collectives, RecordIO data
pipeline.  Compute substrate: JAX/XLA/PJRT.

Typical use mirrors the reference::

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd

    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
__version__ = "0.1.0"

import os as _os

# Persistent XLA compilation cache: wherever JAX_COMPILATION_CACHE_DIR
# says, else inside the checkout (base.compile_cache_dir).
from .base import CACHE_ROOT as _CACHE_ROOT
from .base import compile_cache_dir as _compile_cache_dir
import jax as _jax
_cache_dir = _compile_cache_dir()
if _cache_dir is not None:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)
# Source paths in HLO metadata are relative to the checkout, so that the
# programs whose cache key holds their metadata (base.scopes_in_cache_key)
# hit wherever the checkout lies.
import re as _re
_jax.config.update(
    "jax_hlo_source_file_canonicalization_regex",
    "^" + _re.escape(_os.path.dirname(_CACHE_ROOT) + _os.sep))

# Transfer guard (sharding sanitizer runtime wiring): with
# MXNET_TPU_TRANSFER_GUARD=disallow, an IMPLICIT host<->device transfer
# inside the step -- a Python scalar leaking into dispatch, an un-placed
# index array -- raises at the transfer instead of silently stalling the
# pipeline behind a device round-trip every iteration.  Applied before
# any framework dispatch so import-time ops are covered too; a bad mode
# string fails loudly here (jax names the valid options).  Scoped use:
# mxnet_tpu.analysis.sharding.transfer_guard(mode).  docs/sharding.md.
_transfer_guard_mode = _os.environ.get("MXNET_TPU_TRANSFER_GUARD", "")
if _transfer_guard_mode:
    import jax as _jax_guard
    _jax_guard.config.update("jax_transfer_guard", _transfer_guard_mode)

from . import base
from .base import MXNetError
from . import sync
from . import telemetry
from . import obs
from . import chaos
from .context import (Context, cpu, cpu_pinned, current_context, gpu,
                      num_gpus, num_tpus, tpu)
from . import engine
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd
from . import initializer
from . import initializer as init
from . import metric
from . import optimizer
from .optimizer import lr_scheduler
from . import symbol
from . import symbol as sym
from . import executor
from .executor import Executor
from . import gluon
from . import kvstore
from . import kvstore as kv
from . import recordio
from . import io
from . import image
from . import dataio
from . import parallel
from . import amp
from . import model
from . import callback
from . import module
from . import module as mod
from . import profiler
from . import profiling
from . import kernels
from . import bucketing
from . import runtime
from .distributed import distributed_init
from . import numpy as np
from . import numpy_extension as npx
from . import predictor
from .predictor import Predictor, CompiledPredictor
from . import serving
from . import visualization as viz
visualization = viz
from . import onnx
from . import contrib
from . import env
from . import checkpoint
from . import preemption
from . import horovod
from . import analysis
from . import name
from . import attribute
from .attribute import AttrScope
from .optimizer import lr_scheduler as lr_scheduler
from . import test_utils
