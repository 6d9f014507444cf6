"""mxnet_tpu — a TPU-native deep-learning framework with the MXNet-1.x
programming model.

A ground-up rebuild of the capabilities of the reference MXNet fork
(see SURVEY.md) designed TPU-first: NDArray storage is XLA device
buffers in HBM, eager ops dispatch through jit-cached XLA programs,
hybridized blocks compile to single XLA programs, and distribution is
`jax.sharding` collectives over ICI — no CUDA anywhere.

Usage mirrors the reference::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""

from __future__ import annotations

# the start of ``setup::import`` (telemetry.setup_phase), which can be
# recorded only once the package's own modules are importable: the
# last lines of this file
import time as _time
_IMPORT_T0 = _time.perf_counter()

# TPU-hardware PRNG by default: the threefry generator costs ~8.7 ms/step
# of pure RNG on BERT-base (batch 32, seq 128, dropout 0.1 — measured r3);
# "rbg" lowers jax.random to the on-chip generator. Set
# MXNET_PRNG_IMPL=threefry2x32 for bit-exact legacy random streams.
import os as _os

# NOTE: the PRNG impl (MXNET_PRNG_IMPL, default 'rbg' = TPU hardware PRNG)
# is applied only to keys this library creates (mxnet_tpu.random.take_key
# passes impl= explicitly). The process-global jax_default_prng_impl is
# NOT touched: importing mxnet_tpu must not change jax.random streams for
# unrelated code in the same process.

__version__ = "0.1.0"

from .base import MXNetError
from .context import (Context, cpu, cpu_pinned, current_context, gpu,
                      num_gpus, num_tpus, tpu)
from . import engine
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray, waitall
from . import autograd
from . import random
from . import initializer
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import kvstore
from .kvstore import KVStore
from . import io
from . import gluon
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import callback
from . import profiler
from . import telemetry
from . import test_utils
from . import util
from . import runtime
from . import module as mod  # legacy Module API namespace
from . import module
from . import model
from .model import (save_checkpoint, load_checkpoint,
                    load_latest_checkpoint, wait_checkpoints)
from . import faultinject
from . import staticcheck   # installs the graph/race hooks (ISSUE 9)
from . import guardrails
from .guardrails import GradGuard
from . import modelwatch
from . import perfwatch
# crash postmortems (ISSUE 11): guard raise / engine poison / watchdog
# events dump a bundle when MXNET_CRASH_BUNDLE_DIR is set (checked
# live at fire time — the listener itself is one dict append otherwise)
telemetry.install_crash_bundler()
from . import parallel
from . import recordio
from . import image
from . import dist
from . import numpy as np
from . import numpy_extension as npx
from . import monitor
from .monitor import Monitor
from . import operator
from . import visualization
from . import visualization as viz
from . import rtc
from .util import is_np_array

# AMP lives under contrib to mirror the reference layout
from . import contrib

with telemetry.setup_phase("import") as _span:
    _span.backdate(_IMPORT_T0)
del _span, _IMPORT_T0, _time
