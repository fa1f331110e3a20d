"""Process controller for ``python -m paddle_tpu.distributed.launch``.

Reference parity: python/paddle/distributed/launch (SURVEY.md §1 L9,
§3.3) — the controller spawns N trainer processes per node, assigns
ranks, seeds the rendezvous env (PADDLE_MASTER / PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM), streams per-worker logs, and (elastic mode,
SURVEY.md §5 failure-detection) relaunches the gang on worker failure so
training resumes from the latest checkpoint.

TPU-native design: the rendezvous the env seeds is consumed by
``jax.distributed.initialize`` (the TCPStore analog is jax's
coordination service; rank 0's address is the master).  ONE process
drives all chips of a TPU host: a chip belongs to one process at a time,
every worker inherits this process's whole environment and no chip of
its own, so ``--nproc_per_node N`` on a TPU host would make N processes
claim the same chips and all but one would fail or hang.  The
controller refuses that up front (``_refuse_shared_chips``);
``--nproc_per_node`` > 1 is for CPU simulation (``JAX_PLATFORMS=cpu``).
The controller itself never initialises a JAX backend.
"""
from __future__ import annotations

import glob
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

__all__ = ["LaunchConfig", "Controller", "free_port"]


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _tpu_device_nodes(dev="/dev", sysfs="/sys") -> List[str]:
    """Device nodes of this host's TPU chips, read without touching
    JAX: ``/dev/accel*`` (the accel driver), or the vfio groups that
    hold a PCI function of Google's vendor id (a vfio group of any
    other device is not a TPU)."""
    nodes = glob.glob(os.path.join(dev, "accel*"))
    for group in glob.glob(os.path.join(dev, "vfio", "[0-9]*")):
        for vendor in glob.glob(os.path.join(
                sysfs, "kernel", "iommu_groups", os.path.basename(group),
                "devices", "*", "vendor")):
            with open(vendor) as f:
                if f.read().strip().lower() == _GOOGLE_PCI_VENDOR:
                    nodes.append(group)
                    break
    return nodes


@dataclass
class LaunchConfig:
    script: str = ""
    script_args: List[str] = field(default_factory=list)
    nnodes: int = 1
    node_rank: int = 0
    nproc_per_node: int = 1
    master: Optional[str] = None      # "host:port"; default localhost:rand
    log_dir: Optional[str] = None
    elastic_level: int = 0            # 0: fail fast; 1: relaunch gang
    max_restarts: int = 3
    env: Dict[str, str] = field(default_factory=dict)
    module: bool = False              # run script with -m


class Controller:
    """Spawns and supervises the local trainer gang."""

    def __init__(self, cfg: LaunchConfig):
        self.cfg = cfg
        if cfg.master is None:
            cfg.master = f"127.0.0.1:{free_port()}"
        self.procs: List[subprocess.Popen] = []
        self._logs = []

    # -- env per worker ------------------------------------------------------
    def _worker_env(self, local_rank: int) -> Dict[str, str]:
        cfg = self.cfg
        rank = cfg.node_rank * cfg.nproc_per_node + local_rank
        env = dict(os.environ)
        env.update(cfg.env)
        env.update({
            "PADDLE_MASTER": cfg.master,
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(cfg.nnodes * cfg.nproc_per_node),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_LOCAL_SIZE": str(cfg.nproc_per_node),
            "PADDLE_NNODES": str(cfg.nnodes),
            # jax coordination service must not route via any proxy
            "NO_PROXY": env.get("NO_PROXY", "") + ",127.0.0.1,localhost",
            "no_proxy": env.get("no_proxy", "") + ",127.0.0.1,localhost",
        })
        return env

    # -- lifecycle -----------------------------------------------------------
    def _spawn_one(self, local_rank: int) -> subprocess.Popen:
        cfg = self.cfg
        cmd = [sys.executable]
        if cfg.module:
            cmd += ["-m", cfg.script]
        else:
            cmd += [cfg.script]
        cmd += list(cfg.script_args)
        stdout = stderr = None
        if cfg.log_dir:
            os.makedirs(cfg.log_dir, exist_ok=True)
            rank = cfg.node_rank * cfg.nproc_per_node + local_rank
            f = open(os.path.join(cfg.log_dir, f"workerlog.{rank}"), "ab")
            self._logs.append(f)
            stdout, stderr = f, subprocess.STDOUT
        return subprocess.Popen(cmd, env=self._worker_env(local_rank),
                                stdout=stdout, stderr=stderr)

    def _refuse_shared_chips(self):
        """Several workers on one TPU host would all claim the same
        chips.  Decided from the workers' environment and the device
        nodes alone — asking JAX would make THIS process the one that
        holds the chips."""
        if self.cfg.nproc_per_node <= 1:
            return
        plats = self._worker_env(0).get("JAX_PLATFORMS", "")
        if plats and "tpu" not in plats.split(","):
            return
        if _tpu_device_nodes():
            raise RuntimeError(
                f"nproc_per_node={self.cfg.nproc_per_node} on a TPU "
                f"host: one process drives all chips of a host (a chip "
                f"belongs to one process at a time, and every worker "
                f"would claim the same chips).  Launch ONE process per "
                f"host and let its mesh span jax.devices(); for a CPU "
                f"simulation of several workers set JAX_PLATFORMS=cpu.")

    def start(self):
        self._refuse_shared_chips()
        self.procs = [self._spawn_one(i)
                      for i in range(self.cfg.nproc_per_node)]

    def stop(self, sig=signal.SIGTERM):
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        for f in self._logs:
            f.close()
        self._logs = []

    def _poll_gang(self) -> Optional[int]:
        """None while all running; else first non-zero exit code, or 0
        when every worker exited cleanly."""
        codes = [p.poll() for p in self.procs]
        for c in codes:
            if c is not None and c != 0:
                return c
        if all(c == 0 for c in codes):
            return 0
        return None

    def run(self) -> int:
        """Supervise until the gang exits.  Elastic level 1: on worker
        failure kill + relaunch the whole gang (fresh rendezvous port —
        ranks re-init) up to max_restarts times; recovery is
        checkpoint-based (the trainer script reloads its latest ckpt,
        reference elastic manager semantics)."""
        restarts = 0
        self.start()
        while True:
            code = self._poll_gang()
            if code is None:
                time.sleep(0.2)
                continue
            if code == 0:
                self.stop()
                return 0
            if self.cfg.elastic_level >= 1 and restarts < self.cfg.max_restarts:
                restarts += 1
                sys.stderr.write(
                    f"[launch] worker failed (exit {code}); relaunching "
                    f"gang (restart {restarts}/{self.cfg.max_restarts})\n")
                self.stop()
                # fresh coordinator port: the old coordination service
                # died with rank 0
                self.cfg.master = f"127.0.0.1:{free_port()}"
                self.start()
                continue
            self.stop()
            return code
