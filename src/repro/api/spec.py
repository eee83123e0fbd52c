"""Declarative experiment specs: one frozen dataclass per AsGrad run.

Compact spec strings keep configs one-line:

* scheduler — ``"name[:k=v,...]"`` over :data:`repro.core.REGISTRY`, e.g.
  ``"pure"``, ``"fedbuff:b=4"``, ``"shuffled:reshuffle=0"``.
* timing — ``"pattern[:k=v,...]"`` over :data:`repro.core.PATTERNS`, e.g.
  ``"poisson:slow=8"`` (workers linearly spread over [1, slow] compute time).
* stepsize — a float (constant γ), a sequence (grid-searched γ), a
  :class:`StepsizePolicy`, or a string ``"constant:0.01"`` /
  ``"grid:0.005,0.002"`` / ``"delay_adaptive:0.05"``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import numpy as np

from ..core import (TimingModel, build_schedule, heterogeneous_speeds,
                    make_scheduler)
from ..core.engine import Schedule
from ..core.schedulers import REGISTRY


def _parse_kv(text: str) -> dict:
    """``"b=4,reshuffle=0"`` → ``{"b": 4, "reshuffle": 0}`` (numbers coerced)."""
    out: dict[str, Any] = {}
    if not text:
        return out
    for item in text.split(","):
        if "=" not in item:
            raise ValueError(f"malformed spec option {item!r} (want key=value)")
        k, v = item.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def parse_compact(spec: str) -> tuple[str, dict]:
    """``"name:k=v,k=v"`` → ``(name, kwargs)``."""
    name, _, rest = spec.partition(":")
    return name, _parse_kv(rest)


@dataclasses.dataclass(frozen=True)
class StepsizePolicy:
    """How the server stepsize γ is chosen.

    * ``constant`` — one replay at ``gammas[0]``.
    * ``grid`` — all of ``gammas`` replayed against one shared schedule (a
      single batched scan on the simulator backend); the paper's selection
      protocol (best tail grad-norm with small fluctuations) picks a winner.
    * ``delay_adaptive`` — γ_t = γ·min(1, τ_C/(τ_t+1)), the [Koloskova et
      al. 22]-style stepsize that removes the τ_max dependence (Table 1
      note b).
    """

    kind: str = "constant"          # constant | grid | delay_adaptive
    gammas: tuple = (0.01,)

    KINDS = ("constant", "grid", "delay_adaptive")

    def __post_init__(self):
        if self.kind not in self.KINDS:
            raise ValueError(f"unknown stepsize kind {self.kind!r}")
        object.__setattr__(self, "gammas",
                           tuple(float(g) for g in self.gammas))
        if not self.gammas:
            raise ValueError("stepsize policy needs at least one gamma")

    @property
    def gamma(self) -> float:
        return self.gammas[0]

    @classmethod
    def coerce(cls, value) -> "StepsizePolicy":
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            kind, _, rest = value.partition(":")
            gammas = tuple(float(g) for g in rest.split(",") if g)
            return cls(kind, gammas or (0.01,))
        if isinstance(value, (int, float)):
            return cls("constant", (float(value),))
        if isinstance(value, (tuple, list, np.ndarray)):
            return cls("grid", tuple(float(g) for g in value))
        raise TypeError(f"cannot coerce {value!r} to a StepsizePolicy")


def constant(gamma: float) -> StepsizePolicy:
    return StepsizePolicy("constant", (gamma,))


def grid(*gammas: float) -> StepsizePolicy:
    return StepsizePolicy("grid", tuple(gammas))


def delay_adaptive(gamma: float) -> StepsizePolicy:
    return StepsizePolicy("delay_adaptive", (gamma,))


@dataclasses.dataclass(frozen=True)
class TrainJob:
    """Objective for the trainer backend: arch + data for ``AsyncTrainer``.

    ``ExperimentSpec.T`` counts server *rounds* here (one aggregated model
    update per round); the schedule realises ``T·wait_b`` gradient receipts.
    """

    arch: str = "qwen2-0.5b"
    reduced: bool = True
    remat: Optional[str] = "none"
    arch_overrides: tuple = ()          # ((field, value), ...)
    global_batch: int = 8
    seq_len: int = 64
    heterogeneity: float = 1.0
    delay_rounds: int = 1               # 0 = synchronous baseline
    microbatches: int = 1
    opt: str = "adam"
    clip_norm: Optional[float] = 1.0
    #: how the server update executes: "reference" (tree of elementwise
    #: jnp ops) | "pallas" (fused per-leaf TPU kernels) |
    #: "pallas_pooled" (whole state flattened into per-dtype pool buffers,
    #: ONE kernel per dtype under shard_map — see repro.optim.pool) |
    #: the "*_interpret" twins (same kernels, Pallas interpreter; compiled
    #: impls degrade to these off-TPU with a one-time warning)
    update_impl: str = "reference"
    #: guard rails (:class:`repro.faults.GuardConfig` defaults): per-round
    #: non-finite detection skips the apply in-mask, a per-worker health
    #: channel backs off repeat offenders' effective γ and recovers it on
    #: clean rounds — the runtime survives injected ``fault:`` channels
    guards: bool = False

    def make_arch(self):
        from ..configs import get_arch
        cfg = get_arch(self.arch)
        if self.reduced:
            cfg = cfg.reduced()
        if self.remat is not None:
            cfg = cfg.with_(remat=self.remat)
        if self.arch_overrides:
            cfg = cfg.with_(**dict(self.arch_overrides))
        return cfg


@dataclasses.dataclass(frozen=True)
class ServeJob:
    """Objective for the serve backend: greedy/temperature decoding with
    continuous batching.

    ``ExperimentSpec.T`` counts decode steps (per-request token budget).
    ``n_requests`` requests (default ``batch``) flow through ``n_slots``
    persistent decode lanes (default ``batch``) of
    :class:`repro.distributed.SlotServer`; ``admission`` picks which
    queued request fills a freed slot (scheduler-registry compact spec,
    e.g. ``"pure"`` / ``"fedbuff:b=2"``) and ``arrival`` draws
    inter-arrival gaps from the timing registry (``"pattern[:gap=G]"``,
    e.g. ``"poisson:gap=4"``; ``None`` = all requests queued at step 0).
    """

    arch: str = "qwen2-0.5b"
    reduced: bool = True
    batch: int = 4
    prompt_len: int = 12
    temperature: float = 0.0
    arch_overrides: tuple = ()          # ((field, value), ...)
    n_slots: Optional[int] = None       # default: batch
    n_requests: Optional[int] = None    # default: batch
    admission: str = "pure"             # scheduler-registry compact spec
    arrival: Optional[str] = None       # timing-registry "pattern[:gap=G]"
    steps_per_launch: int = 8           # decode steps per chunk launch
    #: queue-wait budget in decode steps: a request still queued past it
    #: is timed out at the admission sweep, never admitted, and surfaced
    #: in the result's timeout map / τ-report
    deadline: Optional[int] = None
    #: retry budget: total admission attempts per request
    #: (1 = detect-and-discard); > 1 re-queues evicted/timed-out requests
    #: with exponential backoff ``retry_backoff · 2^(failures−1)`` steps
    max_retries: int = 1
    retry_backoff: int = 4              # backoff base, in decode steps
    #: bounded admission queue: eligible waiters beyond the cap are shed
    #: under ``shed_policy``
    queue_cap: Optional[int] = None
    shed_policy: str = "reject-new"     # "reject-new" | "drop-oldest"
    #: graceful drain: stop admitting at this decode step, finish
    #: in-flight lanes, cancel (account) the rest
    drain_after: Optional[int] = None

    def __post_init__(self):
        if self.n_slots is not None and self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.steps_per_launch < 1:
            raise ValueError("steps_per_launch must be >= 1")
        if self.deadline is not None and self.deadline < 0:
            raise ValueError("deadline must be >= 0")
        if self.drain_after is not None and self.drain_after < 0:
            raise ValueError("drain_after must be >= 0")
        if self.max_retries < 1:
            raise ValueError("max_retries must be >= 1 (1 = no retry)")
        if self.retry_backoff < 0:
            raise ValueError("retry_backoff must be >= 0")
        # constructing the policies validates queue_cap/shed_policy too
        from ..distributed.slot_serve import OverloadPolicy
        if self.queue_cap is not None:
            OverloadPolicy(self.queue_cap, self.shed_policy)
        elif self.shed_policy not in ("reject-new", "drop-oldest"):
            raise ValueError(
                f"unknown shed policy {self.shed_policy!r}")
        from ..distributed.admission import parse_admission
        parse_admission(self.admission)     # fail fast on grammar errors
        if self.arrival:
            from ..distributed.admission import draw_arrivals
            draw_arrivals(1, self.arrival)

    def make_arch(self):
        from ..configs import get_arch
        cfg = get_arch(self.arch)
        if self.reduced:
            cfg = cfg.reduced().with_(remat="none")
        if self.arch_overrides:
            cfg = cfg.with_(**dict(self.arch_overrides))
        return cfg


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One AsGrad experiment, declaratively.

    Field → paper notation (Algorithm 1):

    * ``scheduler`` — the job-assignment policy producing the ordering
      (i_t, π_t); ``wait_b`` variants update once per b received gradients.
    * ``timing`` — worker compute-time distribution; with the scheduler it
      fully determines the realised delays τ_t = t − π_t.
    * ``T`` — horizon: gradient receipts on the simulator backend, server
      rounds on the trainer backend, decode steps on the serve backend.
    * ``stepsize`` — the server stepsize γ (policy, see
      :class:`StepsizePolicy`); waiting variants apply γ/b per gradient.
    * ``objective`` — the functions f_i: a problem object exposing
      ``grad_fn``/``full_grad`` (simulator), a :class:`TrainJob` (trainer),
      or a :class:`ServeJob` (serve).
    * ``runtime`` — how the trainer backend dispatches rounds:
      ``"scan"`` (compiled whole-run executor, ``rounds_per_launch``
      rounds per XLA launch — the default) or ``"eager"`` (one launch +
      one host sync per round; the parity oracle).  ``None`` defers to the
      backend's own default; simulator/serve backends ignore both fields,
      so one spec object still describes any tier.
    * ``metrics`` — how scan-runtime metrics reach the host: ``"chunk"``
      (read back at chunk boundaries — the default; ``on_step`` sees the
      end-of-chunk state, checkpoint barriers work), ``"tap"`` (streamed
      per round through a device-side io_callback — live logging at any
      ``rounds_per_launch``, but ``on_step`` receives ``state=None``) or
      ``"none"`` (discarded on device — fastest, no curves).  ``None``
      defers to the backend default; ignored by the eager runtime and the
      other tiers.
    * ``scenario`` — optional non-stationary world spec
      (:mod:`repro.scenarios` grammar, e.g.
      ``"straggler:k=2,factor=8;elastic:every=32"``): the scheduler and
      timing model are wrapped in the scenario's transforms before the
      schedule is realised.  Schedule-level transforms (drift, straggler,
      elastic) affect every backend that realises a schedule; the data
      (``data_drift``) and update (``sparsify``) channels lower into the
      trainer backend's ``RunPlan`` only.  ``None`` (the default) takes
      the plain stationary path; ``""`` is the identity scenario
      (wrapped path, bit-identical schedule — the parity gate).
    """

    RUNTIMES = (None, "scan", "eager")
    METRIC_MODES = (None, "chunk", "tap", "none")

    scheduler: str = "pure"
    timing: str = "fixed:slow=5"
    objective: Any = None
    T: int = 1000
    n_workers: Optional[int] = None     # default: objective.n
    stepsize: Any = 0.01                # coerced to StepsizePolicy
    stochastic: bool = False
    clip: Optional[float] = None
    log_every: int = 100
    speeds: Optional[tuple] = None      # explicit per-worker speeds override
    seed: int = 0
    runtime: Optional[str] = None       # None → backend default ("scan")
    rounds_per_launch: int = 8          # scan runtime: K rounds per launch
    metrics: Optional[str] = None       # None → backend default ("chunk")
    scenario: Optional[str] = None      # None → stationary world

    def __post_init__(self):
        object.__setattr__(self, "stepsize",
                           StepsizePolicy.coerce(self.stepsize))
        if self.runtime not in self.RUNTIMES:
            raise ValueError(
                f"unknown runtime {self.runtime!r}; want one of "
                f"{[r for r in self.RUNTIMES if r]} (or None)")
        if self.metrics not in self.METRIC_MODES:
            raise ValueError(
                f"unknown metrics mode {self.metrics!r}; want one of "
                f"{[m for m in self.METRIC_MODES if m]} (or None)")
        if self.rounds_per_launch < 1:
            raise ValueError("rounds_per_launch must be >= 1")
        if self.speeds is not None:
            object.__setattr__(self, "speeds",
                               tuple(float(s) for s in self.speeds))
        name, _ = parse_compact(self.scheduler)
        if name not in REGISTRY:
            raise ValueError(
                f"unknown scheduler {name!r}; want one of {sorted(REGISTRY)}")
        if self.scenario is not None:
            from ..scenarios import parse_scenario
            parse_scenario(self.scenario)   # fail fast on grammar errors

    # ---- resolved pieces ---------------------------------------------------
    @property
    def n(self) -> int:
        if self.n_workers is not None:
            return int(self.n_workers)
        n = getattr(self.objective, "n", None)
        if n is None:
            raise ValueError(
                "n_workers not set and objective does not define .n")
        return int(n)

    def make_scheduler(self, n: Optional[int] = None):
        name, kw = parse_compact(self.scheduler)
        b = int(kw.pop("b", 1))
        return make_scheduler(name, n or self.n, b=b, seed=self.seed, **kw)

    def make_timing(self, n: Optional[int] = None) -> TimingModel:
        pattern, kw = parse_compact(self.timing)
        n = n or self.n
        slow = float(kw.pop("slow", 5.0))
        base = float(kw.pop("base", 1.0))
        if kw:
            raise ValueError(f"unknown timing options {sorted(kw)}")
        if self.speeds is not None:    # explicit profile overrides slow/base
            if len(self.speeds) != n:
                raise ValueError("speeds length must equal n_workers")
            speeds = np.asarray(self.speeds)
        else:
            speeds = heterogeneous_speeds(n, slow_factor=slow, base=base)
        return TimingModel(speeds, pattern, seed=self.seed)

    def make_scenario(self):
        """The parsed :class:`repro.scenarios.Scenario` (empty when the
        spec has none — the identity scenario)."""
        from ..scenarios import parse_scenario
        return parse_scenario(self.scenario or "")

    def build_world(self, T: Optional[int] = None,
                    n: Optional[int] = None):
        """Realise the (possibly non-stationary) world for this spec:
        the scenario-wrapped schedule plus the per-round channels
        (availability / zipf trajectory / grad density) the trainer
        backend folds into the :class:`repro.runtime.RunPlan`.  With no
        scenario this is the identity wrap — same schedule bit-for-bit as
        :meth:`build_schedule`."""
        from ..scenarios import realise_world
        sched = self.make_scheduler(n)
        return realise_world(self.make_scenario(), sched,
                             self.make_timing(n), T or self.T,
                             seed=self.seed)

    def build_schedule(self, T: Optional[int] = None,
                       n: Optional[int] = None) -> Schedule:
        """Realise the ordering (i_t, π_t) for this spec (through the
        scenario wrap when one is set)."""
        if self.scenario is not None:
            return self.build_world(T, n).schedule
        sched = self.make_scheduler(n)
        return build_schedule(sched, self.make_timing(n), T or self.T)
