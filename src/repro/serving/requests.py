"""Request lifecycle objects for the serving engine."""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Optional

import numpy as np


class RequestStatus(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    SHED = "shed"           # rejected by an admission-control scheduler
    FAILED = "failed"       # lost to a fault (crash/preempt/timeout)


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: Optional[np.ndarray]        # token ids; None in sim-only mode
    prompt_len: int
    max_new_tokens: int
    arrival_time: float = 0.0
    # SLO (set by the client or repro.serving.slo.assign_slos)
    priority: int = 0                   # higher = more important
    deadline_s: float = math.inf        # latency SLO relative to arrival
    slo_tier: Optional[str] = None
    # scheduling (set by a repro.serving.scheduler policy; None means the
    # request is handed to the engine at its raw arrival time)
    release_time: Optional[float] = None
    shed_reason: Optional[str] = None
    # workflow membership (set by repro.workflows.WorkflowSource)
    task_id: Optional[int] = None       # owning task graph
    step: Optional[str] = None          # WorkflowStep name
    kv_parent: Optional[int] = None     # req_id whose KV prefix we fork
    kv_pin: int = 0                     # children that will fork our KV
    # lifecycle
    status: RequestStatus = RequestStatus.QUEUED
    t_prefill_start: float = -1.0
    t_first_token: float = -1.0
    t_done: float = -1.0
    # host clock (time.perf_counter() seconds) of the executed path:
    # when the engine took the request, and when its prefill program
    # was launched; -1.0 in the simulator
    t_submit_host: float = -1.0
    t_launch_host: float = -1.0
    prefilled_tokens: int = 0           # prompt tokens whose KV exists
    tokens_generated: int = 0
    generated: list = dataclasses.field(default_factory=list)
    # accounting
    energy_j: float = 0.0
    # resilience (set by repro.faults fault-injection runs)
    n_attempts: int = 0                 # failed attempts before this one
    wasted_energy_j: float = 0.0        # joules billed to failed attempts
    fail_reason: Optional[str] = None   # "crash"/"preempt"/"timeout"/...
    hedge_of: Optional[int] = None      # req_id this request duplicates

    @property
    def effective_arrival(self) -> float:
        """When the engine first sees this request: the scheduler's
        release time if one shaped it, else the raw arrival."""
        return (self.release_time if self.release_time is not None
                else self.arrival_time)

    @property
    def abs_deadline(self) -> float:
        return self.arrival_time + self.deadline_s

    @property
    def latency(self) -> float:
        """Arrival-to-completion; NaN while unfinished (t_done is the
        -1.0 sentinel until the engine completes the request)."""
        if self.t_done < 0:
            return math.nan
        return self.t_done - self.arrival_time

    @property
    def ttft(self) -> float:
        """Arrival-to-first-token; NaN before the first token exists."""
        if self.t_first_token < 0:
            return math.nan
        return self.t_first_token - self.arrival_time

    @property
    def met_deadline(self) -> bool:
        """Completed within its latency SLO (shed/unfinished = missed,
        unless the deadline is infinite and the request finished)."""
        if self.t_done < 0:
            return False
        return self.latency <= self.deadline_s + 1e-12

    @property
    def energy_wh(self) -> float:
        return self.energy_j / 3600.0
