"""kungfu_tpu — a TPU-native adaptive distributed training framework.

A ground-up JAX/XLA re-design with the capabilities of KungFu
(https://github.com/lsds/KungFu): synchronous SGD, synchronous model
averaging, gossip pair-averaging, online training monitoring (gradient noise
scale, variance, throughput), runtime-swappable collective strategies, and
elastic cluster resizing — with the data plane lowered to XLA collectives
(psum/ppermute/all_gather/reduce_scatter) over an ICI/DCN device mesh and
zero NCCL/CUDA.

Top-level API mirrors the reference's `kungfu.python` surface
(srcs/python/kungfu/python/__init__.py:36-103): `current_rank`,
`cluster_size`, `local_rank`, `run_barrier`, ... — see kungfu_tpu/api.py.
"""

import time as _time

#: the first statement the program runs in any process, and (at the bottom)
#: the last of this import: `boot:interpreter` ends and `boot:imports`
#: starts here (utils/trace.py `package_import_mono`, monitor/boot.py)
_IMPORT_T0 = _time.monotonic()
_IMPORT_T1 = None

__version__ = "0.1.0"

from .api import (  # noqa: F401
    init,
    finalize,
    current_rank,
    current_cluster,
    cluster_size,
    current_local_rank,
    current_local_size,
    host_count,
    detached,
    uid,
    run_barrier,
    propose_new_size,
    save_variable,
    request_variable,
    calc_stats,
    log_stats,
    egress_rates,
    check_interference,
    get_peer_latencies,
    minimum_spanning_tree,
    set_tree,
    set_strategy,
    get_variable,
    set_variable,
)

_IMPORT_T1 = _time.monotonic()


def __getattr__(name):
    # lazy heavyweight exports (importing them pulls in jax at module scope)
    if name == "DataParallelTrainer":
        from .train import DataParallelTrainer

        return DataParallelTrainer
    if name == "MeshTrainer":
        from .trainer import MeshTrainer

        return MeshTrainer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
