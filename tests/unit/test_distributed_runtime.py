"""The ported jax.distributed bootstrap (kungfu_tpu/distributed.py) on two
local CPU processes: client and service come from jax._src.lib._jax, and a
dirty teardown is followed by a working re-init at the next fenced port."""
import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_WORKER = r'''
import sys
rank, root = int(sys.argv[1]), int(sys.argv[2])
import jax
from jax._src import distributed as jd
from jax._src.lib import _jax

def _forbidden(*a, **kw):
    raise AssertionError("jax.distributed.initialize must not be called")
jax.distributed.initialize = _forbidden

from kungfu_tpu.distributed import (
    init_distributed_runtime, teardown_distributed_runtime)
from kungfu_tpu.peer import coordinator_port

for version in (0, 1):
    addr = f"127.0.0.1:{coordinator_port(root, version)}"
    init_distributed_runtime(addr, 2, rank)
    state = jd.global_state
    assert isinstance(state.client, _jax.DistributedRuntimeClient), state.client
    assert (state.service is not None) == (rank == 0)
    assert (state.num_processes, state.process_id) == (2, rank)
    # the runtime answers: each rank reads what the other one wrote
    state.client.key_value_set(f"v{version}/r{rank}", f"hello-{rank}")
    got = state.client.blocking_key_value_get(f"v{version}/r{1 - rank}", 60_000)
    assert got == f"hello-{1 - rank}", got
    if version == 0:
        assert jax.process_count() == 2  # the CPU backend saw the client
    state.client.wait_at_barrier(f"done-{version}", 60_000)
    teardown_distributed_runtime(graceful=False)
    assert state.client is None and state.service is None
    assert (state.num_processes, state.process_id) == (1, 0)
print(f"WORKER_OK {rank}", flush=True)
'''


def _free_root_port() -> int:
    """A worker port whose two fenced coordinator ports are free now."""
    from kungfu_tpu.peer import coordinator_port

    for _ in range(50):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            root = s.getsockname()[1] % 20000 + 10000
        try:
            for version in (0, 1):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", coordinator_port(root, version)))
        except OSError:
            continue
        return root
    raise RuntimeError("no free coordinator ports")


def test_init_dirty_teardown_reinit_two_processes():
    root = _free_root_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(rank), str(root)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for rank in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert f"WORKER_OK {rank}" in out, out[-3000:]
