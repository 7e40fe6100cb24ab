"""mxnet_tpu — a TPU-native deep-learning framework with the MXNet 1.2 API.

Brand-new design for TPU (JAX/XLA/Pallas era) with the capabilities of the
reference (huangzehao/mxnet, an Apache MXNet 1.2.1 fork). See SURVEY.md for the
capability map. Import as `import mxnet_tpu as mx` — reference scripts written
against `import mxnet as mx` run with only the import line changed (or via
`sys.modules` aliasing in examples/).
"""
import time as _time

_t_import = _time.time()  # the import's own stamp: profiler.record_import

# first, so that its compile listeners see every compile from here on
from . import profiler  # noqa: E402
from .libinfo import __version__  # noqa: E402

# Join the launcher's process group BEFORE anything can touch a backend
# (several op modules build small jnp constants at import). The analog of
# ps-lite's rendezvous-at-startup (reference: kvstore_dist.h Customer init).
import os as _os

if int(_os.environ.get("JAX_NUM_PROCESSES", "1") or "1") > 1:
    from .parallel import collectives as _collectives
    try:
        _collectives.ensure_distributed()
    except RuntimeError as _e:  # backend already touched before this import
        import logging as _logging
        _logging.warning("mxnet_tpu: jax.distributed init skipped (%s); "
                         "call parallel.collectives.ensure_distributed() "
                         "before any jax computation", _e)

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus
from . import base
from . import operator  # registers the Custom op before namespace generation
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import random
from . import autograd
from .ops import list_ops

# populated by later phases; keep imports at bottom to respect dependency order
from . import initializer
from . import initializer as init
from .initializer import init_registry  # noqa: F401
from . import optimizer
from . import metric
from . import lr_scheduler
from . import callback
from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from . import io
from . import kvstore as kvs
from .kvstore import KVStore, create as _kv_create


class kvstore:  # namespace shim so `mx.kvstore.create(...)` works
    create = staticmethod(_kv_create)
    KVStore = KVStore


kv = kvstore  # reference alias: mx.kv.create(...)


from . import module
from . import module as mod
from . import serving
from .serving import InferenceEngine
from . import model
from .model import save_checkpoint, load_checkpoint, FeedForward
from . import checkpoint
from .checkpoint import CheckpointManager
from . import resilience
from . import gluon
from . import rnn
from . import recordio
from . import visualization
from . import monitor
from .monitor import Monitor
from . import image
from . import rtc
from . import contrib
from . import storage
from . import name
from . import log
from . import engine
from . import registry
from . import libinfo
from . import test_utils
from . import random as rnd  # reference: mx.rnd alias

viz = visualization

profiler.record_import(_t_import, _time.time())
