"""Elastic multi-host controller: spawn, watch, resize, resume.

The restart-the-world elasticity model (the one torchelastic made
standard): host processes of one *generation* run as a gang; when a
member dies, the controller SIGKILLs the survivors, shrinks the world,
and spawns the next generation with a fresh coordinator — every survivor
resumes from the newest atomic checkpoint with
``DistributedRunner.resume(..., allow_resize=True)``, which revalidates
the row partitioning on the new world size through
:func:`repro.core.partition.plan_resize`.  Checkpoint + deterministic
seekable streams make this "live migration as checkpoint-and-restart":
the resumed run is bit-identical to a run that had started on the small
mesh from that same snapshot (proven in ``tests/chaos/``).

Host programs are ordinary argv commands following the ``REPRO_*``
environment contract of :mod:`repro.core.hostmesh` plus::

    REPRO_GENERATION    generation index (0 = first launch)
    REPRO_RESUME        "1" when a checkpoint should be picked up

Chaos fault specs (:mod:`repro.testing.chaos`) are forwarded to
generation 0 only — a kill fault is keyed to a deterministic stream step,
and the resumed generation replays through that step, so re-arming it
would kill the run forever.

Exit-code protocol: ``0`` success, :data:`repro.testing.chaos.
DROP_EXIT_CODE` graceful departure (the remaining gang keeps running —
the SSP lane absorbs it in place, no restart), anything else a death that
triggers a generation restart.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

from repro.core.hostmesh import free_port
from repro.testing.chaos import DROP_EXIT_CODE, ENV_VAR as CHAOS_ENV

__all__ = ["HostExit", "Generation", "ElasticReport", "ElasticController"]


@dataclasses.dataclass
class HostExit:
    """One host process's final word."""

    host_id: int
    returncode: int
    stdout: str
    stderr: str
    #: True when the controller itself SIGKILLed this (healthy) host to
    #: break up a generation after a peer's death — not an organic death,
    #: so it must not shrink the world a second time
    evicted: bool = False

    @property
    def died(self) -> bool:
        return not self.evicted and self.returncode not in (0, DROP_EXIT_CODE)

    @property
    def dropped(self) -> bool:
        return self.returncode == DROP_EXIT_CODE


@dataclasses.dataclass
class Generation:
    """One gang launch: its world size, coordinator, and every exit."""

    index: int
    num_hosts: int
    coordinator: str
    exits: List[HostExit] = dataclasses.field(default_factory=list)
    started: float = 0.0
    ended: float = 0.0

    @property
    def deaths(self) -> List[HostExit]:
        return [e for e in self.exits if e.died]

    @property
    def duration(self) -> float:
        return self.ended - self.started


@dataclasses.dataclass
class ElasticReport:
    """What an elastic run did: every generation plus recovery timing."""

    generations: List[Generation]
    #: seconds from each death detection to the next generation's spawn
    restart_seconds: List[float] = dataclasses.field(default_factory=list)

    @property
    def final(self) -> Generation:
        return self.generations[-1]

    @property
    def resized(self) -> bool:
        return len(self.generations) > 1

    def host_output(self, host_id: int, generation: int = -1) -> str:
        gen = self.generations[generation]
        for e in gen.exits:
            if e.host_id == host_id:
                return e.stdout
        raise KeyError(f"no host {host_id} in generation {gen.index}")


class ElasticController:
    """Gang-spawns host subprocesses and restarts the world on a death.

    Parameters
    ----------
    argv:
        Host program command line (every host runs the same SPMD program;
        rank arrives via ``REPRO_HOST_ID``).
    num_hosts:
        Generation-0 world size.
    devices_per_host:
        Forced CPU device count per host (appended to ``XLA_FLAGS``).
    env:
        Extra environment for every host of every generation.
    faults:
        :class:`repro.testing.chaos.Fault` list — forwarded to
        generation 0 only (see module docstring).
    max_restarts:
        Generation restarts allowed before giving up.
    min_hosts:
        Smallest world size worth restarting with; below it the
        controller raises instead of respawning.
    timeout:
        Per-generation wall-clock limit (seconds).
    poll:
        Seconds between liveness scans.
    global_mesh:
        ``True`` (BSP): hand every host a shared coordinator so they join
        one ``jax.distributed`` mesh.  ``False`` (SSP exchange lane): no
        coordinator — hosts stay independent single-process programs.
    """

    def __init__(self, argv: Sequence[str], num_hosts: int, *,
                 devices_per_host: int = 1,
                 env: Optional[Dict[str, str]] = None,
                 faults: Sequence = (),
                 max_restarts: int = 2, min_hosts: int = 1,
                 timeout: float = 300.0, poll: float = 0.05,
                 global_mesh: bool = True):
        if num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
        self.argv = list(argv)
        self.num_hosts = int(num_hosts)
        self.devices_per_host = int(devices_per_host)
        self.env = dict(env or {})
        self.faults = list(faults)
        self.max_restarts = int(max_restarts)
        self.min_hosts = int(min_hosts)
        self.timeout = float(timeout)
        self.poll = float(poll)
        self.global_mesh = bool(global_mesh)

    # ------------------------------------------------------------------ #
    def _host_env(self, generation: int, num_hosts: int, host_id: int,
                  coordinator: str) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.env)
        # simulated hosts are CPU processes: on a machine with a chip, each
        # child would otherwise try to take it from the parent and the others
        env["JAX_PLATFORMS"] = "cpu"
        base_flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{base_flags} --xla_force_host_platform_device_count="
            f"{self.devices_per_host}").strip()
        env.update({
            "REPRO_NUM_HOSTS": str(num_hosts),
            "REPRO_HOST_ID": str(host_id),
            "REPRO_GENERATION": str(generation),
            "REPRO_RESUME": "1" if generation > 0 else "0",
        })
        if self.global_mesh:
            env["REPRO_COORDINATOR"] = coordinator
        else:
            env.pop("REPRO_COORDINATOR", None)
        if generation == 0 and self.faults:
            from repro.testing.chaos import faults_to_env

            env.update(faults_to_env(self.faults))
        else:
            env.pop(CHAOS_ENV, None)
        return env

    def _spawn(self, generation: int, num_hosts: int) -> tuple:
        port = free_port()
        coordinator = f"127.0.0.1:{port}"
        procs = []
        for h in range(num_hosts):
            out = tempfile.TemporaryFile(mode="w+")
            err = tempfile.TemporaryFile(mode="w+")
            p = subprocess.Popen(
                self.argv, env=self._host_env(generation, num_hosts, h,
                                              coordinator),
                stdout=out, stderr=err, text=True)
            procs.append((h, p, out, err))
        return coordinator, procs

    @staticmethod
    def _collect(h: int, p: subprocess.Popen, out, err,
                 evicted: bool = False) -> HostExit:
        out.seek(0)
        err.seek(0)
        exit_ = HostExit(host_id=h, returncode=p.returncode,
                         stdout=out.read(), stderr=err.read(),
                         evicted=evicted)
        out.close()
        err.close()
        return exit_

    @staticmethod
    def _kill_survivors(procs) -> None:
        for _, p, _, _ in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGKILL)
                except ProcessLookupError:  # pragma: no cover - exit race
                    pass
        for _, p, _, _ in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass

    # ------------------------------------------------------------------ #
    def run(self) -> ElasticReport:
        """Run generations until one finishes cleanly (or restarts are
        exhausted / the world shrinks below ``min_hosts``)."""
        report = ElasticReport(generations=[])
        world = self.num_hosts
        for generation in range(self.max_restarts + 1):
            coordinator, procs = self._spawn(generation, world)
            gen = Generation(index=generation, num_hosts=world,
                             coordinator=coordinator)
            gen.started = time.monotonic()
            report.generations.append(gen)
            deadline = gen.started + self.timeout

            death_at = None
            pending = list(procs)
            while pending and death_at is None:
                still = []
                for h, p, out, err in pending:
                    rc = p.poll()
                    if rc is None:
                        still.append((h, p, out, err))
                        continue
                    exit_ = self._collect(h, p, out, err)
                    gen.exits.append(exit_)
                    if exit_.died:
                        death_at = time.monotonic()
                pending = still
                if death_at is None and pending:
                    if time.monotonic() > deadline:
                        self._kill_survivors(pending)
                        for h, p, out, err in pending:
                            gen.exits.append(self._collect(h, p, out, err,
                                                           evicted=True))
                        gen.ended = time.monotonic()
                        raise TimeoutError(
                            f"generation {generation} exceeded "
                            f"{self.timeout:.0f}s; killed "
                            f"{len(pending)} hosts")
                    time.sleep(self.poll)

            if death_at is not None:
                # a member died: the gang is broken (a BSP collective would
                # hang on it forever) — kill survivors, shrink, respawn.
                # The survivors' SIGKILLs are evictions, not deaths: only
                # the organic deaths shrink the world.
                self._kill_survivors(pending)
                for h, p, out, err in pending:
                    gen.exits.append(self._collect(h, p, out, err,
                                                   evicted=True))
                gen.ended = time.monotonic()
                world = world - len(gen.deaths)
                if generation == self.max_restarts:
                    raise RuntimeError(
                        f"host(s) {[e.host_id for e in gen.deaths]} died in "
                        f"generation {generation} and no restarts remain; "
                        f"stderr of first death:\n"
                        f"{gen.deaths[0].stderr[-2000:]}")
                if world < self.min_hosts:
                    raise RuntimeError(
                        f"world shrank to {world} host(s), below "
                        f"min_hosts={self.min_hosts}")
                report.restart_seconds.append(time.monotonic() - death_at)
                continue

            gen.ended = time.monotonic()
            bad = [e for e in gen.exits if e.died]
            assert not bad  # deaths are handled above
            return report
        raise AssertionError("unreachable")  # pragma: no cover
