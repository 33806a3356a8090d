"""Fault-tolerance machinery: failure injection, straggler detection,
comm-mode degradation -- the paper's section 3.1 recovery story.

The paper proposes switching from peer-to-peer mode back to master-relay
mode while coping with faults, then resuming peer-to-peer. Here that is a
*backend swap on restart*, exercised against two failure sources:

- **simulated** (SPMD runtime): the supervisor loop in ``launch/train.py``
  catches a ``SimulatedFailure`` from ``FailureInjector``, restores the
  latest checkpoint and rebuilds the train step with ``backend="linear"``
  (master relay) for ``recovery_steps`` steps before swapping back;
- **real** (cluster runtime): ``core.cluster.ClusterSupervisor`` reacts to
  genuine executor-process death -- detected by the driver's heartbeat
  monitor -- with the same ``RecoveryPolicy``/``SupervisorState`` schedule,
  restoring the checkpoint and relaunching degraded executor processes.

Stragglers: per-step wall time is tracked with an EWMA; a step slower
than ``threshold`` x the EWMA marks a straggler event. In a multi-host
deployment the mitigation is speculative re-execution of the slow host's
shard (MapReduce-style backup tasks); single-process here, the detector
records the event and the supervisor's hook decides (tested
deterministically with a fake clock).
"""
from __future__ import annotations

import dataclasses


class SimulatedFailure(RuntimeError):
    """Raised by FailureInjector to model a node loss."""


@dataclasses.dataclass
class FailureInjector:
    """Deterministically fail at given global steps (each fires once)."""
    fail_at: frozenset[int] = frozenset()

    def __post_init__(self):
        self._fired: set[int] = set()

    def check(self, step: int):
        if step in self.fail_at and step not in self._fired:
            self._fired.add(step)
            raise SimulatedFailure(f"injected node failure at step {step}")


@dataclasses.dataclass
class StragglerDetector:
    """EWMA step-time monitor. ``observe`` returns True on a straggler."""
    alpha: float = 0.1
    threshold: float = 3.0
    warmup: int = 5

    def __post_init__(self):
        self.ewma: float | None = None
        self.n = 0
        self.events: list[tuple[int, float, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        self.n += 1
        if self.ewma is None:
            self.ewma = dt
            return False
        is_straggler = (self.n > self.warmup
                        and dt > self.threshold * self.ewma)
        if is_straggler:
            # do not poison the EWMA with the outlier
            self.events.append((step, dt, self.ewma))
        else:
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return is_straggler


@dataclasses.dataclass
class RecoveryPolicy:
    """What the supervisor does after a failure."""
    degrade_backend: str = "linear"   # paper phase-1 master relay
    recovery_steps: int = 8           # steps to run degraded after restart
    max_restarts: int = 8


@dataclasses.dataclass
class SupervisorState:
    restarts: int = 0
    degraded_until: int = -1
    straggler_events: int = 0
    #: recoveries served by shrink-to-survivors (no relaunch) -- a
    #: subset of ``restarts``, which counts every recovery either way
    shrinks: int = 0

    def on_failure(self, step: int, policy: RecoveryPolicy) -> str:
        self.restarts += 1
        if self.restarts > policy.max_restarts:
            raise RuntimeError("restart budget exhausted")
        self.degraded_until = step + policy.recovery_steps
        return policy.degrade_backend

    def on_straggler(self, step: int, dt: float, ewma: float) -> None:
        """Record a straggler event surfaced by ``StragglerDetector`` --
        the supervisor calls this (and its user hook) instead of the
        counter being write-only."""
        self.straggler_events += 1

    def backend_for(self, step: int, fast_backend: str,
                    policy: RecoveryPolicy) -> str:
        return (policy.degrade_backend if step <= self.degraded_until
                else fast_backend)
