"""Worker process construction: env injection + device slot assignment.

Reference: srcs/go/kungfu/job/{job,gpu_resource,cuda_visible_device}.go —
Job.NewProc builds each worker's env (KUNGFU_* contract + CUDA_VISIBLE_DEVICES
from a GPUPool).  TPU equivalent: the KFT_* contract (kungfu_tpu/env.py) plus
TPU chip slots via TPU_VISIBLE_CHIPS (or virtual CPU devices for testing).
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List, Optional

from ..env import worker_env
from ..plan import Cluster, PeerID, Strategy


#: libtpu's grid of one-chip processes on one host, by how many of them
#: form the slice.  Established on a v5e 2x2 host; other shapes are refused
#: rather than guessed.
_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1"}
#: libtpu's default port for the processes of one slice to find each other
_TPU_PROCESS_PORT = 8476


def chip_env(chip: int, n_procs: int = 1, index: int = 0) -> Dict[str, str]:
    """The libtpu variables that hand one process one chip of its host.

    TPU_VISIBLE_CHIPS alone leaves libtpu expecting the host's whole
    topology.  n_procs == 1 makes the chip a world of its own (a serving
    replica, a lone worker).  n_procs > 1 makes the process number `index`
    of `n_procs` one-chip processes that together form one slice of the
    host, so that jax.distributed sees n_procs devices, one local to each.
    """
    if n_procs not in _PROCESS_BOUNDS:
        raise ValueError(
            f"{n_procs} one-chip TPU workers on one host: libtpu forms a "
            f"multi-process slice here only from {sorted(_PROCESS_BOUNDS)} "
            "processes (a v5e 2x2 host); use one worker that owns every chip "
            "(-np 1 without -chips-per-host), or -platform cpu"
        )
    port = _TPU_PROCESS_PORT + (chip if n_procs == 1 else 0)
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _PROCESS_BOUNDS[n_procs],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{port + i}" for i in range(n_procs)),
        "TPU_PROCESS_PORT": str(port + index),
        "CLOUD_TPU_TASK_ID": str(index),
    }


class ChipPool:
    """Smallest-free-id device slot allocator (reference gpu_resource.go:10-45)."""

    def __init__(self, n: int):
        self._free = list(range(n))

    def get(self) -> Optional[int]:
        return self._free.pop(0) if self._free else None

    def put(self, i: int) -> None:
        if i >= 0:
            self._free.append(i)
            self._free.sort()


@dataclasses.dataclass
class Proc:
    name: str
    args: List[str]
    env: Dict[str, str]
    peer: PeerID
    chip: int = -1


@dataclasses.dataclass
class Job:
    prog: str
    args: List[str]
    strategy: Strategy
    config_server: str = ""
    platform: str = ""  # "" = inherit; "cpu" forces CPU backend in workers
    devices_per_worker: int = 1
    chips_per_host: int = 0  # 0 = don't manage chip visibility
    heal: bool = False  # arm the workers' suspected-dead-peer recovery path
    heartbeat_dir: str = ""  # workers touch a per-peer file every step

    def new_proc(self, peer: PeerID, chip: int, cluster: Cluster, version: int,
                 parent: Optional[PeerID] = None) -> Proc:
        env = dict(os.environ)
        env.update(
            worker_env(
                self_id=peer,
                cluster=cluster,
                version=version,
                strategy=self.strategy,
                parent=parent,
                config_server=self.config_server,
            )
        )
        if self.heal:
            env["KFT_HEAL"] = "1"
            # recovery re-rendezvous must fail fast enough for the retry
            # loop to chase newer cluster documents (default init timeout is
            # 300s — longer than most heal budgets); user env wins
            env.setdefault("KFT_INIT_TIMEOUT_S", "45")
            # peer-death detection belongs to the HEALER (heartbeats +
            # suspicion window), not to XLA's coordination service: its
            # ~100s missed-heartbeat broadcast reaches still-blocked peers
            # through the error-poll channel, which jaxlib handles by
            # terminating the process from a C++ thread (std::bad_cast) —
            # turning one death into a fleet kill.  Push it past every
            # drill/heal horizon; user env wins
            env.setdefault("KFT_MAX_MISSING_HEARTBEATS", "100")
        if self.heartbeat_dir:
            # keyed on peer identity, not rank: ranks shift across resizes
            env["KFT_HEARTBEAT_FILE"] = os.path.join(
                self.heartbeat_dir, f"hb-{peer.host}-{peer.port}"
            )
            # a wedge INSIDE a monitored op keeps the heartbeat fresh (the
            # stall watchdog touches it), so hang detection needs the hard
            # deadline armed as its complement; user env wins
            env.setdefault("KFT_STALL_DEADLINE_S", "120")
        if self.platform:
            env["KFT_PLATFORM"] = self.platform
            if self.platform == "cpu":
                flags = env.get("XLA_FLAGS", "")
                if "xla_force_host_platform_device_count" not in flags:
                    env["XLA_FLAGS"] = (
                        flags + f" --xla_force_host_platform_device_count={self.devices_per_worker}"
                    ).strip()
        if self.chips_per_host > 0 and chip >= 0 and self.platform != "cpu":
            # reference sets CUDA_VISIBLE_DEVICES (cuda_visible_device.go:17-33);
            # a TPU chip needs libtpu's process grid set beside it
            # (a pre-set visible list is respected, as the reference does)
            pre = env.get("TPU_VISIBLE_CHIPS")
            if pre:
                visible = pre.split(",")
                chip = int(visible[chip % len(visible)])
            local = [p for p in cluster.workers if p.host == peer.host]
            env.update(chip_env(chip, n_procs=len(local), index=local.index(peer)))
        args = [self.prog] + list(self.args)
        return Proc(
            name=f"{cluster.workers.rank(peer)}", args=args, env=env, peer=peer, chip=chip
        )
