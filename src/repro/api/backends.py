"""The three ways to execute an :class:`ExperimentSpec`.

* :class:`SimulatorBackend` — schedule + exact jittable replay (theory tier).
  Grid stepsize policies replay every γ against ONE shared schedule in a
  single batched scan (:func:`repro.core.simulator.replay_grid`): the
  schedule is gradient-value-independent, so rebuilding it per γ — what the
  benchmarks used to do — is pure waste.
* :class:`TrainerBackend` — schedule → device-resident
  :class:`repro.runtime.RunPlan` → ``AsyncTrainer`` steps through the
  whole-run executor (production tier): ``runtime="scan"`` compiles K
  rounds per XLA launch, ``runtime="eager"`` is the per-round parity
  oracle.  Same schedulers as the simulator, identical ordering by
  construction.
* :class:`ServeBackend` — continuous batching through ``distributed.SlotServer``.

All three return a :class:`RunResult`.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Protocol, runtime_checkable

import jax
import numpy as np

from ..core import delay_adaptive_stepsizes, replay, replay_grid, round_masks
from ..core.trace import summarize
from ..runtime import compile_plan, execute
from .result import RunResult
from .spec import ExperimentSpec, ServeJob, StepsizePolicy, TrainJob


@runtime_checkable
class Backend(Protocol):
    name: str

    def run(self, spec: ExperimentSpec) -> RunResult: ...


def _grid_score(grad_norms: np.ndarray) -> float:
    """The paper's selection protocol (App. A.1): best final grad norm with
    small fluctuations — tail mean plus half the tail standard deviation."""
    tail = float(np.mean(grad_norms[-3:]))
    fluct = float(np.std(grad_norms[-5:]))
    return tail + 0.5 * fluct


class SimulatorBackend:
    """Exact replay of Algorithm 1: x_{t+1} = x_t − γ̃ g_{i_t}(x_{π_t})."""

    name = "simulator"

    def run(self, spec: ExperimentSpec) -> RunResult:
        prob = spec.objective
        if prob is None or not hasattr(prob, "grad_fn"):
            raise TypeError(
                "SimulatorBackend needs an objective exposing grad_fn "
                f"(got {type(prob).__name__})")
        t0 = time.time()
        schedule = spec.build_schedule()
        grad_fn = prob.grad_fn(stochastic=spec.stochastic)
        full_grad = getattr(prob, "full_grad", None)
        loss = getattr(prob, "loss", None)
        x0 = np.zeros(prob.d, dtype=np.float32)
        policy: StepsizePolicy = spec.stepsize
        kw = dict(key=jax.random.PRNGKey(spec.seed), clip=spec.clip,
                  log_every=spec.log_every, full_grad_fn=full_grad,
                  loss_fn=loss)

        if policy.kind == "grid":
            if full_grad is None:
                raise ValueError(
                    "grid stepsize selection scores grad norms; the "
                    "objective must expose full_grad")
            results = replay_grid(schedule, grad_fn, x0, policy.gammas, **kw)
            best_i, best_score = 0, None
            grid_info = {}
            for i, (g, res) in enumerate(zip(policy.gammas, results)):
                score = _grid_score(res.grad_norms)
                grid_info[g] = {"grad_norms": res.grad_norms,
                                "losses": res.losses, "score": score}
                if best_score is None or score < best_score:
                    best_i, best_score = i, score
            gamma, res = policy.gammas[best_i], results[best_i]
        else:
            gamma = policy.gamma
            if policy.kind == "delay_adaptive":
                steps = delay_adaptive_stepsizes(gamma, schedule.delays,
                                                 schedule.tau_c())
            else:
                steps = gamma
            res = replay(schedule, grad_fn, x0, steps, **kw)
            grid_info = None

        return RunResult(
            spec=spec, backend=self.name, x=res.x, xs=res.xs,
            log_ts=res.log_ts, grad_norms=res.grad_norms, losses=res.losses,
            gamma=gamma, grid=grid_info, schedule=schedule,
            trace=summarize(schedule), seconds=time.time() - t0)


class TrainerBackend:
    """Schedule → device-resident :class:`repro.runtime.RunPlan` →
    ``AsyncTrainer`` steps, dispatched by the ``repro.runtime`` executor.

    ``mesh``/``rules`` default to this host's devices and the repo sharding
    rules; ``on_step(i, state, metrics)`` is invoked once per round (for
    logging / checkpointing without owning the loop).  ``runtime`` selects
    the dispatch layer: ``"scan"`` (default) compiles
    ``rounds_per_launch`` rounds into one XLA launch; ``"eager"`` launches
    one round at a time — the parity oracle.  ``metrics`` selects the
    scan executor's metric transport (``"chunk"`` default: ``on_step``
    fires at chunk boundaries with the end-of-chunk state; ``"tap"``:
    per-round streaming, ``state=None``; ``"none"``: no curves).
    Constructor args override the spec's ``runtime``/``rounds_per_launch``
    /``metrics`` fields; both unset falls back to the defaults.

    A grid stepsize policy on the scan runtime executes ALL γ points in
    one vmapped program per chunk (the plan's γ-axis +
    :meth:`repro.runtime.PlanExecutor.run_grid`) — one trainer, one
    compile, shared masks/batches — instead of N sequential runs; the
    eager runtime keeps the sequential loop as the oracle.

    Fault tolerance rides the same lanes: a ``fault:`` scenario lowers
    its per-round gain channel into the plan, ``TrainJob(guards=True)``
    arms the trainer's non-finite guard rails, ``snapshot`` (an
    :class:`repro.checkpoint.AsyncSnapshotter`) gives scan runs
    barrier-free periodic checkpoints and ``breaker`` (a
    :class:`repro.faults.DivergenceBreaker`, ``metrics="tap"`` only)
    stops launching chunks once the loss diverges.
    """

    name = "trainer"
    default_runtime = "scan"
    default_metrics = "chunk"

    def __init__(self, mesh=None, rules=None,
                 on_step: Optional[Callable] = None,
                 runtime: Optional[str] = None,
                 rounds_per_launch: Optional[int] = None,
                 metrics: Optional[str] = None,
                 snapshot=None, breaker=None, recorder=None):
        self.mesh = mesh
        self.rules = rules
        self.on_step = on_step
        self.runtime = runtime
        self.rounds_per_launch = rounds_per_launch
        self.metrics = metrics
        self.snapshot = snapshot
        self.breaker = breaker
        self.recorder = recorder      # repro.obs.Recorder | None

    # ---- pieces shared with tests -----------------------------------------
    @staticmethod
    def world_for(spec: ExperimentSpec, n_groups: Optional[int] = None):
        """The realised :class:`repro.scenarios.ScenarioWorld` for
        ``spec.T`` rounds (identity wrap when the spec has no scenario —
        bit-identical schedule to the stationary path)."""
        sched = spec.make_scheduler(n_groups)
        return spec.build_world(T=spec.T * sched.wait_b, n=n_groups)

    @staticmethod
    def masks_for(spec: ExperimentSpec, n_groups: Optional[int] = None):
        """((rounds, n_groups) participation masks, realised Schedule) for
        ``spec.T`` rounds.  The masks are the raw schedule lowering —
        elastic availability is folded in later, at plan compile time."""
        world = TrainerBackend.world_for(spec, n_groups)
        return round_masks(world.schedule), world.schedule

    def resolve_runtime(self, spec: ExperimentSpec):
        """(runtime, rounds_per_launch, metrics): constructor overrides
        spec, both-unset → the scan/chunk defaults."""
        runtime = self.runtime or spec.runtime or self.default_runtime
        k = self.rounds_per_launch if self.rounds_per_launch is not None \
            else spec.rounds_per_launch
        metrics = self.metrics or spec.metrics or self.default_metrics
        return runtime, int(k), metrics

    def run(self, spec: ExperimentSpec) -> RunResult:
        job = spec.objective
        if not isinstance(job, TrainJob):
            raise TypeError("TrainerBackend needs a TrainJob objective")
        policy: StepsizePolicy = spec.stepsize
        if policy.kind == "grid":
            runtime, _, _ = self.resolve_runtime(spec)
            # the vmapped lane has no per-round callback hook, so an
            # on_step consumer keeps the sequential loop
            if runtime == "scan" and len(policy.gammas) > 1 \
                    and self.on_step is None:
                return self._run_grid(spec, job)
            best = None
            for g in policy.gammas:
                # scoring needs loss curves, so the sequential grid loop
                # overrides a metrics="none" resolution (as the vmapped
                # lane does)
                res = self._run_single(spec, job, g, adaptive=False,
                                       metrics_floor="chunk")
                score = float(np.mean(res.losses[-3:]))
                if best is None or score < best[0]:
                    best = (score, res)
            return best[1]
        return self._run_single(spec, job, policy.gamma,
                                adaptive=policy.kind == "delay_adaptive")

    # ---- shared construction ----------------------------------------------
    def _make_trainer(self, spec: ExperimentSpec, job: TrainJob, lr: float,
                      adaptive: bool):
        from ..distributed import AsyncTrainer, AsyncConfig, DEFAULT_RULES
        from ..faults import GuardConfig
        from ..launch.mesh import make_host_mesh
        from ..optim import OptConfig

        cfg = job.make_arch()
        mesh = self.mesh if self.mesh is not None else make_host_mesh()
        rules = self.rules if self.rules is not None else DEFAULT_RULES
        tr = AsyncTrainer(
            cfg, mesh,
            opt=OptConfig(name=job.opt, lr=lr, clip_norm=job.clip_norm,
                          update_impl=job.update_impl),
            async_cfg=AsyncConfig(delay_rounds=job.delay_rounds,
                                  delay_adaptive=adaptive,
                                  microbatches=job.microbatches,
                                  guards=GuardConfig() if job.guards
                                  else None),
            rules=rules)
        n_groups = spec.n_workers or tr.n_groups
        tr.n_groups = n_groups
        if job.global_batch % n_groups:
            raise ValueError(
                f"the {n_groups} worker groups must divide "
                f"global_batch={job.global_batch}")
        return tr, cfg, n_groups

    def compile_step(self, spec: ExperimentSpec):
        """The spec's train step compiled from shapes alone, allocating no
        state: ``memory_analysis()`` sizes a batch before the run, and
        ``as_text()`` shows the kernels and collectives the run executes.
        The run itself scans this step, so both hold the same program."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        job = spec.objective
        if not isinstance(job, TrainJob):
            raise TypeError("TrainerBackend needs a TrainJob objective")
        policy: StepsizePolicy = spec.stepsize
        adaptive = policy.kind == "delay_adaptive"
        tr, _, n_groups = self._make_trainer(spec, job, policy.gamma,
                                             adaptive)
        step = tr.jit_train_step((job.global_batch, job.seq_len),
                                 with_delay_scale=adaptive)
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tr.abstract_state(), tr.state_shardings())
        repl = NamedSharding(tr.mesh, P())
        scalars = (jax.ShapeDtypeStruct((), np.float32, sharding=repl),) \
            if adaptive else ()
        mask = jax.ShapeDtypeStruct((n_groups,), np.float32, sharding=repl)
        return step.lower(state, tr.batch_struct(job.global_batch,
                                                 job.seq_len),
                          mask, *scalars).compile()

    def _run_single(self, spec: ExperimentSpec, job: TrainJob, lr: float,
                    adaptive: bool,
                    metrics_floor: Optional[str] = None) -> RunResult:
        """One (γ, adaptive) run.  ``metrics_floor`` replaces a resolved
        ``"none"`` with a curve-producing mode for callers that must read
        the losses back (grid scoring)."""
        import jax

        t0 = time.time()
        tr, cfg, n_groups = self._make_trainer(spec, job, lr, adaptive)
        world = self.world_for(spec, n_groups)
        schedule = world.schedule
        masks = round_masks(schedule)
        state = tr.init_state(jax.random.PRNGKey(spec.seed))

        rounds = min(spec.T, masks.shape[0])
        # the whole run lowered ONCE: round masks, per-round γ-scales (the
        # delay-adaptive scale at round i belongs to the gradient APPLIED
        # at i; AsyncTrainer's single swapped-every-round gbuf makes the
        # realised extra staleness exactly one round whenever
        # delay_rounds > 0), and the folded per-round data keys.  The
        # executor replays plan slices with no per-round host work.
        # Scenario channels (elastic availability, drifting data law,
        # sparsified grads) ride into the plan as extra per-round arrays
        plan = compile_plan(schedule, job, rounds=rounds, n_groups=n_groups,
                            seed=spec.seed, adaptive=adaptive,
                            availability=world.availability,
                            zipf_as=world.zipf_as,
                            grad_density=world.grad_density,
                            fault_gain=world.fault_gain)
        runtime, rounds_per_launch, metrics = self.resolve_runtime(spec)
        if metrics == "none" and metrics_floor is not None:
            metrics = metrics_floor
        kw = {}
        if runtime == "scan":           # durability/breaker: scan-only lanes
            kw = {"snapshot": self.snapshot, "breaker": self.breaker}
        exec_res = execute(tr, plan, state, runtime=runtime,
                           rounds_per_launch=rounds_per_launch,
                           metrics=metrics, on_step=self.on_step,
                           recorder=self.recorder, **kw)

        have_curves = bool(exec_res.metrics)
        obs = self.recorder.summary(rounds=rounds) \
            if self.recorder is not None else None
        return RunResult(
            spec=spec, backend=self.name, x=exec_res.state,
            log_ts=np.arange(rounds),
            losses=exec_res.metrics["loss"].astype(np.float64)
            if have_curves else None,
            grad_norms=exec_res.metrics["grad_norm"].astype(np.float64)
            if have_curves else None,
            gamma=lr, schedule=schedule, trace=summarize(schedule),
            seconds=time.time() - t0,
            extra={"metrics": exec_res.rows, "masks": masks,
                   "arch": cfg.name, "n_groups": n_groups,
                   "update_impl": tr.update_impl,
                   "delay_scales": plan.delay_scales if adaptive else None,
                   "scenario": spec.scenario,
                   "plan_summary": plan.summary(),
                   "runtime": runtime,
                   "rounds_per_launch": rounds_per_launch,
                   "metrics_mode": metrics if runtime == "scan" else "chunk",
                   "launches": exec_res.launches,
                   "host_syncs": exec_res.host_syncs,
                   "tap_events": exec_res.tap_events,
                   "snapshots": exec_res.stats.snapshots,
                   "tripped_round": exec_res.stats.tripped_round,
                   "obs": obs})

    def _run_grid(self, spec: ExperimentSpec, job: TrainJob) -> RunResult:
        """All grid γ points in one vmapped scan program (the plan's
        γ-axis): one trainer built at γ_base = gammas[0], per-γ stepsize
        rows folded into ``plan.grid_scales``, every point scored by the
        same tail-loss protocol as the sequential loop."""
        import jax
        from ..runtime import PlanExecutor

        t0 = time.time()
        policy: StepsizePolicy = spec.stepsize
        gammas = policy.gammas
        tr, cfg, n_groups = self._make_trainer(spec, job, gammas[0],
                                               adaptive=False)
        world = self.world_for(spec, n_groups)
        schedule = world.schedule
        masks = round_masks(schedule)
        rounds = min(spec.T, masks.shape[0])
        plan = compile_plan(schedule, job, rounds=rounds, n_groups=n_groups,
                            seed=spec.seed, grid_gammas=gammas,
                            availability=world.availability,
                            zipf_as=world.zipf_as,
                            grad_density=world.grad_density,
                            fault_gain=world.fault_gain)
        _, rounds_per_launch, _ = self.resolve_runtime(spec)
        ex = PlanExecutor(tr, plan, recorder=self.recorder)
        # scoring needs curves, so the grid lane always reads them back
        # (one deferred sync for the whole grid)
        res = ex.run_grid(tr.init_state(jax.random.PRNGKey(spec.seed)),
                          rounds_per_launch=rounds_per_launch,
                          metrics="chunk")

        losses = res.metrics["loss"]          # (n_grid, rounds)
        gnorms = res.metrics["grad_norm"]
        scores = [float(np.mean(losses[i, -3:])) for i in range(len(gammas))]
        best = int(np.argmin(scores))
        grid_info = {g: {"losses": losses[i].astype(np.float64),
                         "grad_norms": gnorms[i].astype(np.float64),
                         "score": scores[i]}
                     for i, g in enumerate(gammas)}
        best_state = jax.tree_util.tree_map(lambda x: x[best], res.state)
        best_rows = [{k: float(res.metrics[k][best, q]) for k in res.metrics}
                     for q in range(rounds)]
        return RunResult(
            spec=spec, backend=self.name, x=best_state,
            log_ts=np.arange(rounds),
            losses=losses[best].astype(np.float64),
            grad_norms=gnorms[best].astype(np.float64),
            gamma=float(gammas[best]), grid=grid_info, schedule=schedule,
            trace=summarize(schedule), seconds=time.time() - t0,
            extra={"metrics": best_rows, "masks": masks,
                   "arch": cfg.name, "n_groups": n_groups,
                   "update_impl": tr.update_impl,
                   "delay_scales": None,
                   "scenario": spec.scenario,
                   "plan_summary": plan.summary(),
                   "runtime": "scan", "grid_lane": True,
                   "n_grid": len(gammas),
                   "rounds_per_launch": rounds_per_launch,
                   "metrics_mode": "chunk",
                   "launches": res.launches,
                   "host_syncs": res.host_syncs,
                   "tap_events": res.tap_events,
                   "snapshots": res.stats.snapshots,
                   "tripped_round": res.stats.tripped_round,
                   "obs": self.recorder.summary(rounds=rounds)
                   if self.recorder is not None else None})


class ServeBackend:
    """Serve a :class:`ServeJob` through the continuous-batching
    :class:`repro.distributed.SlotServer`: ``n_requests`` requests flow
    through ``n_slots`` persistent decode slots (both default to
    ``batch``) under a scheduler-registry admission policy, and the
    realised admission trace lowers to an ordinary ``Schedule``
    (``extra["schedule"]`` / ``extra["tau_report"]``).
    """

    name = "serve"

    def __init__(self, mesh=None, rules=None, recorder=None):
        self.mesh = mesh
        self.rules = rules
        self.recorder = recorder      # repro.obs.Recorder | None

    def _setup(self, spec: ExperimentSpec):
        import jax
        from ..distributed.sharding import DEFAULT_RULES
        from ..launch.mesh import make_host_mesh
        from ..models import init_params

        job = spec.objective
        if not isinstance(job, ServeJob):
            raise TypeError("ServeBackend needs a ServeJob objective")
        cfg = job.make_arch()
        mesh = self.mesh if self.mesh is not None else make_host_mesh()
        rules = self.rules if self.rules is not None else DEFAULT_RULES
        params = init_params(cfg, jax.random.PRNGKey(spec.seed))
        return job, cfg, mesh, rules, params

    def run(self, spec: ExperimentSpec) -> RunResult:
        """``n_requests`` requests through ``n_slots`` decode lanes;
        admissions follow the job's scheduler-registry policy, arrivals its
        timing-registry pattern."""
        from ..distributed import (SlotServer, SlotConfig, draw_arrivals,
                                   parse_admission, RetryPolicy,
                                   OverloadPolicy)
        from ..scenarios import tau_report

        t0 = time.time()
        job, cfg, mesh, rules, params = self._setup(spec)
        n_req = job.n_requests or job.batch
        n_slots = job.n_slots or job.batch
        ctx = job.prompt_len + spec.T
        retry = (RetryPolicy(max_attempts=job.max_retries,
                             backoff_base=job.retry_backoff)
                 if job.max_retries > 1 else None)
        overload = (OverloadPolicy(job.queue_cap, job.shed_policy)
                    if job.queue_cap is not None else None)
        server = SlotServer(
            cfg, mesh,
            SlotConfig(n_slots=n_slots, ctx_len=ctx,
                       temperature=job.temperature, seed=spec.seed,
                       steps_per_launch=job.steps_per_launch),
            rules=rules, recorder=self.recorder)
        prompts = np.random.default_rng(spec.seed).integers(
            0, cfg.vocab, (n_req, job.prompt_len)).astype(np.int32)
        arrivals = draw_arrivals(n_req, job.arrival, seed=spec.seed)
        faults = None
        if spec.scenario:
            # the spec's scenario lowers onto the decode-step clock too:
            # slot_poison / serve_preempt cells realise here, training
            # transforms contribute nothing
            from ..faults import realise_serve_faults

            attempts = job.max_retries
            fault_horizon = (2 * (int(arrivals.max(initial=0))
                                  + n_req * spec.T * attempts
                                  + job.steps_per_launch)
                             + 4 * job.steps_per_launch)
            faults = realise_serve_faults(spec.scenario, n_req,
                                          fault_horizon, seed=spec.seed)
        t_dec = time.time()
        res = server.serve(params, prompts, spec.T,
                           admission=job.admission, arrivals=arrivals,
                           deadline=job.deadline, retry=retry,
                           overload=overload, drain_after=job.drain_after,
                           faults=faults)
        dt = time.time() - t_dec
        return RunResult(
            spec=spec, backend=self.name, x=res.tokens,
            schedule=res.schedule, seconds=time.time() - t0,
            extra={"prompts": prompts, "arch": cfg.name,
                   "decode_seconds": dt,
                   "tok_per_s": n_req * (spec.T - 1) / max(dt, 1e-9),
                   "n_slots": n_slots, "admission": job.admission,
                   "arrivals": arrivals, "ttft_steps": res.ttft_steps,
                   "occupancy": res.occupancy,
                   "decode_steps": res.decode_steps, "chunks": res.chunks,
                   "tap_rows": res.tap_rows,
                   "evictions": res.evictions, "timeouts": res.timeouts,
                   "shed": res.shed, "drained": res.drained,
                   "attempts": res.attempts,
                   "resumed_from": res.resumed_from,
                   "obs": self.recorder.summary(rounds=res.decode_steps)
                   if self.recorder is not None else None,
                   "tau_report": tau_report(
                       res.schedule, parse_admission(job.admission)[0],
                       concurrency=n_slots,
                       scenario_spec=job.arrival or "",
                       evictions=res.evictions,
                       timeouts=res.timeouts,
                       shed=res.shed, drained=res.drained,
                       attempts=res.attempts)})


def run(spec: ExperimentSpec, backend: Optional[Backend] = None) -> RunResult:
    """Execute a spec on the right backend (dispatched on the objective)."""
    if backend is None:
        if isinstance(spec.objective, TrainJob):
            backend = TrainerBackend()
        elif isinstance(spec.objective, ServeJob):
            backend = ServeBackend()
        else:
            backend = SimulatorBackend()
    return backend.run(spec)
