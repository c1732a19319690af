"""FleetRunner: spawn N host processes against one coordinator directory.

Each host is a fresh ``python tests/fleet/train_host.py`` subprocess with:

* ``XLA_FLAGS=--xla_force_host_platform_device_count=<num_hosts * devices
  _per_host>`` — the whole fleet's devices exist in every process, so the
  global mesh (and hence the SPMD program) is identical everywhere
  (SNIPPETS.md snippet 1; same isolation pattern as tests/test_multidevice).
* ``FLEET_*`` env describing its rank, the shared coordinator dir, iteration
  count, gradient compression, and (optionally) an iteration at which to
  SIGKILL itself mid-run (elastic-recovery tests).

Every host is forced onto the CPU (``JAX_PLATFORMS=cpu``) on purpose: this
is a simulation of a multi-host fleet, never a chip run — on a TPU machine a
child process could not reach the chip its parent holds.

Artifacts are one JSON file per host (params digest, per-iteration metric
history, membership/epoch view, exchange + buffer stats); tests assert the
cross-host invariants on those.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HOST_PROGRAM = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "train_host.py")


class FleetRunner:
    def __init__(
        self,
        workdir: str,
        *,
        num_hosts: int = 2,
        devices_per_host: int = 4,
        iters: int = 3,
        compression: str = "none",
        seed: int = 0,
        dead_after_s: float = 8.0,
        extra_env: Optional[Dict[str, str]] = None,
    ):
        self.workdir = str(workdir)
        self.num_hosts = num_hosts
        self.devices_per_host = devices_per_host
        self.iters = iters
        self.compression = compression
        self.seed = seed
        self.dead_after_s = dead_after_s
        self.extra_env = dict(extra_env or {})
        self.coordinator = os.path.join(self.workdir, "coord")
        os.makedirs(self.coordinator, exist_ok=True)
        self.procs: Dict[int, subprocess.Popen] = {}
        self._logs: Dict[int, str] = {}

    # -------------------------------------------------------------- #
    def artifact_path(self, host: int) -> str:
        return os.path.join(self.workdir, f"artifact.host{host}.json")

    def _env(self, host: int, solo: bool, die_at: int) -> Dict[str, str]:
        n = self.num_hosts * self.devices_per_host
        env = dict(os.environ)
        env.update(self.extra_env)
        env.update({
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.path.join(REPO, "src"),
            "FLEET_COORD": self.coordinator,
            "FLEET_NUM_HOSTS": str(self.num_hosts),
            "FLEET_PROCESS_ID": str(host),
            "FLEET_ITERS": str(self.iters),
            "FLEET_COMPRESSION": self.compression,
            "FLEET_SEED": str(self.seed),
            "FLEET_DIE_AT": str(die_at),
            "FLEET_DEAD_AFTER_S": str(self.dead_after_s),
            "FLEET_SOLO": "1" if solo else "0",
            "FLEET_ARTIFACT": self.artifact_path(host),
            "FLEET_WORKDIR": self.workdir,
        })
        return env

    def launch(self, *, die_at: Optional[Dict[int, int]] = None) -> None:
        """Start every host process (die_at: host -> iteration to SIGKILL
        itself at, for recovery tests)."""
        die_at = die_at or {}
        for h in range(self.num_hosts):
            self.launch_host(h, die_at=die_at.get(h, -1))

    def launch_host(self, host: int, *, die_at: int = -1,
                    solo: bool = False) -> subprocess.Popen:
        log = os.path.join(self.workdir, f"host{host}.log")
        self._logs[host] = log
        with open(log, "wb") as f:
            proc = subprocess.Popen(
                [sys.executable, HOST_PROGRAM],
                env=self._env(host, solo, die_at),
                stdout=f, stderr=subprocess.STDOUT, cwd=REPO,
            )
        self.procs[host] = proc
        return proc

    def run_solo_reference(self, *, timeout: float = 600.0) -> dict:
        """Single-host reference on the flat (data, model) mesh over the
        same device count — the parity baseline. Runs host id ``num_hosts``
        so its artifact never collides with fleet hosts'."""
        h = self.num_hosts  # out-of-band id
        self.launch_host(h, solo=True)
        self.wait(hosts=[h], timeout=timeout)
        return self.artifact(h)

    # -------------------------------------------------------------- #
    def kill(self, host: int) -> None:
        """SIGKILL a host (no cleanup, no goodbye — the failure under test)."""
        self.procs[host].send_signal(signal.SIGKILL)

    def wait(self, *, hosts: Optional[List[int]] = None,
             timeout: float = 600.0, expect_failure: tuple = ()) -> None:
        """Join host processes; raise (with the host's log tail) if any exits
        nonzero, except hosts listed in ``expect_failure`` (the killed ones)."""
        hosts = list(self.procs) if hosts is None else hosts
        deadline = time.monotonic() + timeout
        for h in hosts:
            left = max(deadline - time.monotonic(), 1.0)
            try:
                rc = self.procs[h].wait(timeout=left)
            except subprocess.TimeoutExpired:
                self.procs[h].kill()
                raise AssertionError(
                    f"host {h} timed out\n{self.log_tail(h)}")
            if rc != 0 and h not in expect_failure:
                raise AssertionError(
                    f"host {h} exited {rc}\n{self.log_tail(h)}")

    def log_tail(self, host: int, lines: int = 40) -> str:
        try:
            with open(self._logs[host], errors="replace") as f:
                return "".join(f.readlines()[-lines:])
        except OSError:
            return "<no log>"

    def artifact(self, host: int) -> dict:
        path = self.artifact_path(host)
        assert os.path.exists(path), (
            f"host {host} wrote no artifact\n{self.log_tail(host)}")
        with open(path) as f:
            return json.load(f)

    def artifacts(self, hosts: Optional[List[int]] = None) -> Dict[int, dict]:
        hosts = hosts if hosts is not None else list(range(self.num_hosts))
        return {h: self.artifact(h) for h in hosts}
