"""Streaming whole-run executor: K rounds per XLA launch, three metric paths.

The eager dispatch loop pays three per-round costs the hardware never asked
for: a Python dispatch of the jitted step, a host-built batch shipped to
device, and a device→host sync to read the metrics.  The scan executor
removes all three — the :class:`RunPlan` is device-resident, batches are
synthesised on device from the plan's folded PRNG keys, and how metrics
reach the host is the ``metrics`` mode:

* ``"chunk"`` (default) — metrics accumulate into the stacked ys of the
  scan and cross to host once per chunk.  With an ``on_step`` callback the
  host blocks on every chunk (the PR-4 path: callbacks see values, so the
  readback is the barrier); WITHOUT a callback the host never blocks
  mid-run — chunk c+1 is enqueued while chunk c executes (the carry is
  donated, so XLA chains the launches) and all metric buffers are read
  back at the end in ONE sync.
* ``"tap"`` — a :func:`jax.experimental.io_callback` inside the scan body
  streams each round's metric row to the host as the device reaches it.
  ``on_step`` fires per ROUND (not per chunk) with no readback barrier at
  all, which is what lets ``rounds_per_launch`` grow to the whole run
  while keeping live logging.  The callback sees metric values only — the
  mid-scan train state never materialises on host, so ``on_step`` receives
  ``state=None`` (checkpoint barriers need ``"chunk"``).
* ``"none"`` — the scan body discards metrics entirely: zero host syncs,
  zero tap events, the fastest path when only the final state matters.

``rounds_per_launch`` (K) is the dispatch-vs-control-granularity trade-off:
K = 1 degenerates to eager dispatch, K = rounds is one launch for the whole
run, and intermediate K bounds retrace cost and (in ``"chunk"`` mode) sets
the ``on_step``/checkpoint barrier cadence.

:func:`PlanExecutor.run_grid` is the vmapped γ-grid lane: a plan compiled
with a γ-axis (``compile_plan(..., grid_gammas=...)``) executes ALL grid
points in one compiled program — the chunk body is ``vmap``-ed over the
per-γ state and per-γ stepsize scales while the plan's masks, keys and
synthesised batches stay shared, exactly mirroring the simulator tier's
batched grid search.

:func:`run_eager` is the same plan executed one round per launch — the
parity oracle the scan executor is gated against (same step function, same
device-synthesised batches, same plan slices; only the dispatch differs).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np

from ..obs import CompileWatch, span
from .plan import RunPlan

#: fixed metric order of the on-device accumulator row; mirrors the dict
#: returned by ``AsyncTrainer.train_step_fn`` (``skipped``/``gscale`` are
#: the guard-rail channels — 0.0/1.0 on an unguarded trainer)
METRICS = ("loss", "ce", "aux", "grad_norm", "participation",
           "skipped", "gscale")

_LOSS_IDX = METRICS.index("loss")
_SKIP_IDX = METRICS.index("skipped")
_GSCALE_IDX = METRICS.index("gscale")

#: metric transport modes of the scan executor
METRIC_MODES = ("chunk", "tap", "none")


@dataclasses.dataclass
class ExecStats:
    """Honest dispatch accounting, one counter per mechanism.

    * ``launches`` — XLA dispatches of the train step / chunk program.
      The eager loop's separate batch-synthesis jit is NOT counted (it is
      a synthesis detail, not a round dispatch — the scan executor fuses
      it into the chunk, so counting it would make the eager/scan columns
      incomparable).
    * ``host_syncs`` — times the host BLOCKED on a device→host metric
      readback mid-run (eager: every round; scan ``"chunk"`` with
      ``on_step``: every chunk; scan ``"chunk"`` without ``on_step``: one
      deferred readback at the end; ``"tap"``/``"none"``: zero — the
      end-of-run ``block_until_ready`` on the carried state is a
      completion barrier, not a metric transfer).
    * ``tap_events`` — metric rows streamed host-ward by the io_callback
      tap (one per round in ``"tap"`` mode, zero otherwise).
    * ``snapshots`` — async device snapshots offered to the run's
      :class:`repro.checkpoint.AsyncSnapshotter` (zero without one).
    * ``tripped_round`` — round at which the divergence breaker tripped
      through the tap lane (None = never tripped / no breaker): the run
      stopped launching after the chunk containing it.
    * ``rows_computed`` — batch rows put through forward and backward in
      the run (rounds × :attr:`PlanExecutor.step_rows`, times the grid
      points on the γ-grid lane).
    """

    launches: int = 0
    host_syncs: int = 0
    tap_events: int = 0
    snapshots: int = 0
    tripped_round: Optional[int] = None
    rows_computed: int = 0


@dataclasses.dataclass
class ExecResult:
    """Final carried state + per-round metric curves (host numpy).

    ``metrics`` maps each name in :data:`METRICS` to a ``(rounds,)`` array
    — or ``(n_grid, rounds)`` for :meth:`PlanExecutor.run_grid` results —
    and is EMPTY under ``metrics="none"``.
    """

    state: object
    metrics: dict
    stats: ExecStats = dataclasses.field(default_factory=ExecStats)

    # convenience views (older call sites and the benches read these)
    @property
    def launches(self) -> int:
        return self.stats.launches

    @property
    def host_syncs(self) -> int:
        return self.stats.host_syncs

    @property
    def tap_events(self) -> int:
        return self.stats.tap_events

    @property
    def rows(self) -> list:
        """Metrics as one dict per round (the eager loop's legacy shape).
        Only defined for single-run (1-D) curves — grid results keep the
        (n_grid, rounds) arrays."""
        if not self.metrics:
            return []
        first = next(iter(self.metrics.values()))
        if first.ndim != 1:
            raise ValueError(
                "rows is a single-run view; grid results carry "
                f"(n_grid, rounds) curves (got shape {first.shape})")
        return [{k: float(v[i]) for k, v in self.metrics.items()}
                for i in range(len(first))]


def make_batch_fn(plan: RunPlan, cfg) -> Callable:
    """``batch_of(key, cdf_i=None) -> batch dict``, entirely on device.

    Tokens: inverse-CDF Zipf draws (``searchsorted`` on the plan's
    cumulative pmf) pushed through each group's vocab permutation — the
    same marginal law and heterogeneity structure as the host
    ``HeterogeneousTokenPipeline``, as a pure jittable function of the
    round key.  Non-token modalities (vision patches / audio frames) are
    the same stubbed normal draws the host path used, keyed per-modality
    via ``fold_in``.

    ``cdf_i`` is the data-drift phase index (``plan.cdf_index[q]``): on a
    drifting plan round q samples from ``cdf_bank[cdf_i]`` — one extra
    device gather — instead of the static ``token_cdf``.  Static plans
    ignore it, so stationary call sites stay one-argument.
    """
    import jax
    import jax.numpy as jnp
    from ..models import batch_specs

    specs = batch_specs(cfg, plan.global_batch, plan.seq_len)
    cdf = jnp.asarray(plan.token_cdf)
    bank = None if plan.cdf_bank is None else jnp.asarray(plan.cdf_bank)
    perms = jnp.asarray(plan.group_perms)
    per = plan.global_batch // plan.n_groups
    gidx = jnp.repeat(jnp.arange(plan.n_groups), per)

    def batch_of(key, cdf_i=None):
        cdf_q = cdf if bank is None or cdf_i is None else bank[cdf_i]
        out = {}
        for j, (k, sp) in enumerate(sorted(specs.items())):
            kj = jax.random.fold_in(key, j)
            if sp.dtype == "int32":          # tokens (possibly shortened)
                u = jax.random.uniform(kj, (plan.global_batch, sp.shape[1]))
                ranks = jnp.clip(jnp.searchsorted(cdf_q, u), 0,
                                 cdf_q.shape[0] - 1).astype(jnp.int32)
                out[k] = perms[gidx[:, None], ranks]
            else:                            # stubbed modality embeddings
                out[k] = jax.random.normal(kj, sp.shape, jnp.float32)
        return out

    return batch_of


def _metrics_row(m: dict):
    import jax.numpy as jnp
    return jnp.stack([jnp.asarray(m[k], jnp.float32) for k in METRICS])


def _row_dict(row) -> dict:
    return {k: float(v) for k, v in zip(METRICS, row)}


def _chunk_bounds(rounds: int, rounds_per_launch: int, start: int):
    k = max(int(rounds_per_launch), 1)
    lo = start
    while lo < rounds:
        hi = min(lo + k, rounds)
        yield lo, hi
        lo = hi


class PlanExecutor:
    """Holds the compiled artifacts for one (trainer × plan): build once,
    run many.  The jitted chunk programs are cached on the instance (one
    per metric mode, plus one per grid width), so repeated runs
    (benchmark warm timings, grid restarts, resumed runs) pay
    tracing/compilation only on first use per (mode, chunk length) — a
    fresh closure per run would silently recompile every time.
    """

    def __init__(self, trainer, plan: RunPlan, *, donate: bool = True,
                 recorder=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.trainer = trainer
        self.plan = plan
        self.donate = donate
        self.recorder = recorder      # repro.obs.Recorder | None
        self.watch = CompileWatch(recorder)   # retrace sentinel over the jits
        self._batch_of = make_batch_fn(plan, trainer.cfg)
        #: rows each round's step computes; below the batch only where the
        #: plan carries a ``groups`` channel and the trainer can gather
        #: (otherwise the channel stays off the program altogether)
        self.step_rows = plan.global_batch if plan.groups is None else \
            trainer.step_rows(plan.global_batch, plan.groups.shape[1])
        self._gather = self.step_rows < plan.global_batch
        self._repl = NamedSharding(trainer.mesh, P())   # plan slices
        self._step = trainer.train_step_fn()
        self._eager = None            # lazily built parity-oracle pair
        self._chunk_jits = {}         # metric mode -> jitted chunk
        self._grid_jits = {}          # (n_grid, mode) -> jitted grid chunk
        self._stack_jit = None        # cached γ-axis state tiler
        self._tap_sink = None         # per-run host consumer of tap rows

    def compile_counts(self) -> dict:
        """Traced-signature counts of the cached jits (the executor twin
        of ``SlotServer.compile_counts`` — warm reruns must not grow
        these beyond the first run's, incl. its ragged-tail length)."""
        return self.watch.counts()

    # ------------------------------------------------------------- chunk body
    def _scan_body(self, *, force_scale: bool = False):
        """Shared round body: synthesise batch, pin it replicated, step.

        The pin matters: GSPMD otherwise propagates the data-axis sharding
        back into the RNG ops, and legacy (non-partitionable) threefry
        generates DIFFERENT bits per shard than the replicated generation
        the eager oracle uses — 2% loss divergence, not FMA noise.

        ``force_scale``: only an ADAPTIVE plan carries a real per-round
        γ-scale; for a neutral plan the step is called 3-arg so the
        trainer's own static ``AsyncConfig.delay_adaptive`` rule stays in
        charge (an explicit all-ones scale would silently override it).
        The γ-grid lane forces the explicit-scale step — its scale rows
        ARE the whole stepsize policy per grid point.  A sparsified plan
        (``grad_density`` channel) also forces it: the density is the
        step's 5th positional argument, so the scale slot must be filled
        (scan and eager agree, so parity is unaffected).

        Scenario channels ride the same xs dict: ``xs["cdf"]`` (data-drift
        phase index) feeds the batch synthesiser, ``xs["dens"]``
        (keep-density) feeds the step's sparsifier, ``xs["gain"]``
        (per-worker fault gains) feeds the step's fault channel, and
        ``xs["groups"]`` (the round's participating group ids) tells the
        step which rows to compute.
        """
        import jax

        step, batch_of, repl = self._step, self._batch_of, self._repl
        with_density = self.plan.grad_density is not None
        with_gain = self.plan.fault_gain is not None
        with_scale = self.plan.adaptive or force_scale or with_density
        gather = self._gather

        def body(st, xs):
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, repl),
                batch_of(xs["key"], xs.get("cdf")))
            kw = {}
            if with_scale:
                kw["delay_scale"] = xs["scale"]
            if with_density:
                kw["grad_density"] = xs["dens"]
            if with_gain:
                kw["fault_gain"] = xs["gain"]
            if gather:
                kw["groups"] = xs["groups"]
            st, m = step(st, batch, xs["mask"], **kw)
            return st, m

        return body

    def _emit_tap(self, idx, row):
        """Host side of the io_callback tap (bound once so the jitted
        program is stable across runs; the per-run consumer swaps in via
        ``_tap_sink``)."""
        sink = self._tap_sink
        if sink is not None:
            with span(self.recorder, "sink", "tap"):
                sink(int(idx), np.asarray(row))

    def _chunk_jit(self, mode: str):
        """Jitted ``chunk(state, xs)`` for one metric mode, where ``xs``
        is the per-round slice dict from :meth:`_slices`; ``"chunk"``
        additionally returns the stacked metric rows."""
        if mode in self._chunk_jits:
            return self._chunk_jits[mode]
        import jax
        from jax.experimental import io_callback

        body = self._scan_body()
        emit = self._emit_tap

        def round_fn(st, xs):
            st, m = body(st, xs)
            if mode == "chunk":
                return st, _metrics_row(m)
            if mode == "tap":
                # ordered: rows must reach the host in round order (the
                # sink builds the curve and fires on_step sequentially)
                io_callback(emit, None, xs["idx"], _metrics_row(m),
                            ordered=True)
            return st, None

        def chunk(state, xs):
            state, ys = jax.lax.scan(round_fn, state, xs)
            return (state, ys) if mode == "chunk" else state

        state_sh = self.trainer.state_shardings()
        # self._repl is a pytree PREFIX: every plan slice in xs replicated
        fn = self.watch.wrap(f"chunk[{mode}]", jax.jit(
            chunk,
            in_shardings=(state_sh, self._repl),
            out_shardings=(state_sh, None) if mode == "chunk" else state_sh,
            donate_argnums=(0,) if self.donate else ()))
        self._chunk_jits[mode] = fn
        return fn

    def _grid_jit(self, n_grid: int, mode: str):
        """Jitted ``chunk(states, shared, grid_scales)`` vmapped over the
        γ-axis: states carry a leading ``(n_grid,)`` axis, ``grid_scales``
        is ``(n_grid, K)``, and the shared xs dict (masks, keys, scenario
        channels, batches) is broadcast across grid points (the ordering
        and the data stream do not depend on γ — the same observation
        behind the simulator tier's batched ``replay_grid``)."""
        key = (n_grid, mode)
        if key in self._grid_jits:
            return self._grid_jits[key]
        import jax

        body = self._scan_body(force_scale=True)

        def one_gamma(st, scales, shared):
            def round_fn(s, xs):
                s, m = body(s, xs)
                return s, (_metrics_row(m) if mode == "chunk" else None)

            return jax.lax.scan(round_fn, st, dict(shared, scale=scales))

        def chunk(states, shared, grid_scales):
            states, ys = jax.vmap(one_gamma, in_axes=(0, 0, None))(
                states, grid_scales, shared)
            return (states, ys) if mode == "chunk" else states

        fn = self.watch.wrap(f"grid[{n_grid},{mode}]",
                             jax.jit(chunk, donate_argnums=(0,)
                                     if self.donate else ()))
        self._grid_jits[key] = fn
        return fn

    def _slices(self, lo: int, hi: int) -> dict:
        """Per-round xs dict for rounds ``[lo, hi)``: always idx / mask /
        key / scale, plus the plan's scenario channels when present."""
        import jax.numpy as jnp

        masks, keys, scales = self.plan.device_slices(lo, hi)
        xs = {"idx": jnp.arange(lo, hi, dtype=jnp.int32),
              "mask": masks, "key": keys, "scale": scales}
        if self.plan.cdf_index is not None:
            xs["cdf"] = jnp.asarray(self.plan.cdf_index[lo:hi])
        if self.plan.grad_density is not None:
            xs["dens"] = jnp.asarray(self.plan.grad_density[lo:hi])
        if self.plan.fault_gain is not None:
            xs["gain"] = jnp.asarray(self.plan.fault_gain[lo:hi])
        if self._gather:
            xs["groups"] = jnp.asarray(self.plan.groups[lo:hi])
        return xs

    def _maybe_snapshot(self, snapshot, hi: int, state, stats) -> None:
        """Offer the end-of-chunk carry to the async snapshotter.  The
        offer dispatches a non-donating device copy and starts the host
        fetch, then returns — the device pipeline never drains (the next
        chunk is already free to launch), which is the barrier-free
        durability contract."""
        if snapshot is not None and snapshot.due(hi, self.plan.rounds):
            with span(self.recorder, "snapshot_offer", "snapshot",
                      round=hi):
                snapshot.offer(hi, state)
            stats.snapshots += 1

    def _attach_obs(self, snapshot, breaker=None) -> None:
        """Thread this run's recorder into the collaborators that emit
        their own spans (snapshot finalise happens inside the
        snapshotter, possibly a whole cadence after the offer)."""
        rec = self.recorder
        if rec is None:
            return
        if snapshot is not None and getattr(snapshot, "recorder",
                                            None) is None:
            snapshot.recorder = rec

    def _record_stats(self, stats: "ExecStats", rounds: int,
                      lanes: int = 1) -> None:
        """Count the rows the run computed (``lanes`` γ-grid points per
        round), then fold the run's dispatch accounting into the obs
        counters (and let the retrace sentinel stamp any compile events it
        missed)."""
        stats.rows_computed = rounds * lanes * self.step_rows
        rec = self.recorder
        if rec is None:
            return
        self.watch.observe()
        rec.count("rounds", rounds)
        rec.count("rows_computed", stats.rows_computed)
        rec.count("launches", stats.launches)
        rec.count("host_syncs", stats.host_syncs)
        rec.count("tap_events", stats.tap_events)
        rec.count("snapshots", stats.snapshots)

    # ------------------------------------------------------------------ scan
    def run_scan(self, state, *, rounds_per_launch: int = 8,
                 metrics: str = "chunk",
                 on_step: Optional[Callable] = None,
                 start_round: int = 0,
                 snapshot=None, breaker=None) -> ExecResult:
        """Execute plan rounds ``[start_round, rounds)``, K per launch.

        One XLA launch covers K = ``rounds_per_launch`` rounds; the
        carried state is donated launch-to-launch (the chunk's input
        buffers are reused, so state never doubles in memory).  A ragged
        tail (``rounds % K != 0``) costs at most one extra compile for the
        remainder length.

        ``metrics`` selects the transport (module docstring):

        * ``"chunk"`` — ``on_step(i, state, metrics_i)`` fires for every
          round at chunk boundaries with the END-of-chunk state
          (checkpoint barriers land on multiples of K; align
          ``ckpt_every`` with K for exact-resume semantics).  Without
          ``on_step`` the host never blocks mid-run: chunks overlap and
          ONE deferred readback at the end assembles the curves.
        * ``"tap"`` — ``on_step(i, None, metrics_i)`` fires per round from
          the device-side tap; no mid-run readback, state is not
          available to the callback.
        * ``"none"`` — no metrics at all (``on_step`` is rejected).

        ``start_round > 0`` resumes mid-plan: the data keys are a pure
        function of (seed, round), so a restored run regenerates the
        identical batch stream.  ``start_round == rounds`` is an exact
        no-op (zero launches, empty curves, state returned untouched).

        ``snapshot`` (any metric mode) is a
        :class:`repro.checkpoint.AsyncSnapshotter`: chunk-boundary carries
        it declares due are offered barrier-free — a non-donating device
        copy plus an async host fetch, finalised to an atomic checkpoint
        while later chunks keep the device busy — which is what gives
        ``"tap"``/``"none"`` runs durability without mid-run host
        barriers.  ``breaker`` (tap mode only) is a
        :class:`repro.faults.DivergenceBreaker` fed each round's loss from
        the tap sink; once tripped, no further chunks are launched
        (enqueued ones drain normally) and the trip round is reported in
        ``stats.tripped_round`` with the curves truncated to the rounds
        actually launched.
        """
        import jax

        if metrics not in METRIC_MODES:
            raise ValueError(f"unknown metrics mode {metrics!r}; want one "
                             f"of {METRIC_MODES}")
        if metrics == "none" and on_step is not None:
            raise ValueError(
                'metrics="none" discards metrics on device; an on_step '
                'callback would never fire — use "tap" or "chunk"')
        if breaker is not None and metrics != "tap":
            raise ValueError(
                'the divergence breaker trips through the tap lane — run '
                'with metrics="tap" (chunk/none never stream per-round '
                'losses to the host mid-run)')
        plan = self.plan
        fn = self._chunk_jit(metrics)
        stats = ExecStats()
        rec = self.recorder
        self._attach_obs(snapshot, breaker)
        bounds = list(_chunk_bounds(plan.rounds, rounds_per_launch,
                                    start_round))

        if metrics == "tap":
            tap_rows = {}
            tripped_seen = [False]

            def sink(i, row):
                tap_rows[i] = row
                stats.tap_events += 1
                if rec is not None:
                    # host boundary that already exists (the io_callback
                    # sink runs per round regardless) — one instant, plus
                    # the guard-rail channels when they fire
                    rec.instant("tap_round", lane="tap", round=i)
                    if row[_SKIP_IDX] > 0:
                        rec.instant("guard_skip", lane="faults", round=i,
                                    gscale=float(row[_GSCALE_IDX]))
                    elif row[_GSCALE_IDX] != 1.0:
                        rec.gauge("gscale", float(row[_GSCALE_IDX]),
                                  lane="faults")
                if breaker is not None:
                    breaker.observe(i, row[_LOSS_IDX])
                    if breaker.tripped and not tripped_seen[0]:
                        tripped_seen[0] = True
                        if rec is not None:
                            rec.instant("breaker_trip", lane="faults",
                                        round=breaker.tripped_round)
                if on_step is not None:
                    on_step(i, None, _row_dict(row))

            launched_hi = start_round
            self._tap_sink = sink
            try:
                for lo, hi in bounds:
                    if breaker is not None and breaker.tripped:
                        break               # stop launching; queue drains
                    with span(rec, "launch", "executor", lo=lo, hi=hi):
                        state = fn(state, self._slices(lo, hi))
                    stats.launches += 1
                    launched_hi = hi
                    self._maybe_snapshot(snapshot, hi, state, stats)
                # completion barrier (not a metric transfer): flushes the
                # enqueued chunks, then drains the callback queue — array
                # readiness alone does NOT guarantee pending io_callbacks
                # have run on every backend
                with span(rec, "barrier", "executor"):
                    state = jax.block_until_ready(state)
                    jax.effects_barrier()
            finally:
                self._tap_sink = None
            if snapshot is not None:
                snapshot.drain()
            if breaker is not None:
                stats.tripped_round = breaker.tripped_round
            n_rounds = launched_hi - start_round
            if len(tap_rows) != n_rounds:
                raise RuntimeError(
                    f"metrics tap delivered {len(tap_rows)}/{n_rounds} "
                    f"rows — an io_callback was dropped or the run was "
                    f"interrupted mid-chunk")
            all_ms = (np.stack([tap_rows[i] for i in
                                range(start_round, launched_hi)])
                      if n_rounds else np.zeros((0, len(METRICS)),
                                                np.float32))
            self._record_stats(stats, n_rounds)
            return ExecResult(
                state=state,
                metrics={k: all_ms[:, j] for j, k in enumerate(METRICS)},
                stats=stats)

        if metrics == "none":
            for lo, hi in bounds:
                with span(rec, "launch", "executor", lo=lo, hi=hi):
                    state = fn(state, self._slices(lo, hi))
                stats.launches += 1
                self._maybe_snapshot(snapshot, hi, state, stats)
            with span(rec, "barrier", "executor"):
                state = jax.block_until_ready(state)
            if snapshot is not None:
                snapshot.drain()
            self._record_stats(stats,
                               bounds[-1][1] - start_round if bounds else 0)
            return ExecResult(state=state, metrics={}, stats=stats)

        # metrics == "chunk"
        rows = []
        for lo, hi in bounds:
            with span(rec, "launch", "executor", lo=lo, hi=hi):
                state, ms = fn(state, self._slices(lo, hi))
            stats.launches += 1
            self._maybe_snapshot(snapshot, hi, state, stats)
            if on_step is not None:
                with span(rec, "host_sync", "executor", lo=lo, hi=hi):
                    ms = np.asarray(ms)      # blocking readback per chunk
                stats.host_syncs += 1
                for i in range(lo, hi):
                    on_step(i, state, _row_dict(ms[i - lo]))
            rows.append(ms)                  # device buffer when deferred
        if on_step is None and rows:
            # overlapped path: every chunk is already enqueued; block once
            # and read all metric buffers back in one sync point
            with span(rec, "host_sync", "executor", deferred=True):
                rows = [np.asarray(r) for r in jax.block_until_ready(rows)]
            stats.host_syncs = 1
        with span(rec, "barrier", "executor"):
            state = jax.block_until_ready(state)
        if snapshot is not None:
            snapshot.drain()
        all_ms = np.concatenate([np.asarray(r) for r in rows], axis=0) \
            if rows else np.zeros((0, len(METRICS)), np.float32)
        if rec is not None and all_ms.size:
            # guard-skip events from the materialised rows (the chunk
            # transport has no per-round host boundary; args carry the
            # round, the timestamp is the readback that surfaced it)
            for i in np.nonzero(all_ms[:, _SKIP_IDX] > 0)[0]:
                rec.instant("guard_skip", lane="faults",
                            round=int(i) + start_round,
                            gscale=float(all_ms[i, _GSCALE_IDX]))
        self._record_stats(stats, int(all_ms.shape[0]))
        return ExecResult(
            state=state,
            metrics={k: all_ms[:, j] for j, k in enumerate(METRICS)},
            stats=stats)

    # ------------------------------------------------------------------ grid
    def stack_state(self, state):
        """Tile one initial state with a leading ``(n_grid,)`` axis — every
        grid point starts from the same iterate, as in the sequential
        grid search.  The tiler jit is cached on the executor: a fresh
        closure per call would retrace (and recompile) every run."""
        import jax
        import jax.numpy as jnp

        if self._stack_jit is None:
            g = self.plan.n_grid
            self._stack_jit = self.watch.wrap("stack_state", jax.jit(
                lambda s: jax.tree_util.tree_map(
                    lambda x: jnp.repeat(x[None], g, axis=0), s)))
        return self._stack_jit(state)

    def run_grid(self, state, *, rounds_per_launch: int = 8,
                 metrics: str = "chunk",
                 start_round: int = 0, snapshot=None) -> ExecResult:
        """Execute ALL grid points of a γ-axis plan in one compiled
        program per chunk (vmap over γ).

        ``state`` may be a single trainer state (tiled via
        :meth:`stack_state`) or an already-stacked ``(n_grid, ...)`` tree
        (a resumed grid run).  Metrics come back as ``(n_grid, rounds)``
        curves under ``"chunk"`` (deferred single readback — there is no
        per-γ ``on_step``; the grid lane is a search, not a logging loop)
        or not at all under ``"none"``.  ``"tap"`` is rejected: io_callback
        rows interleave unordered across vmapped lanes, so a per-round
        stream would be misleading.

        ``snapshot`` offers the STACKED ``(n_grid, ...)`` carry at due
        chunk boundaries — a restored grid snapshot feeds straight back in
        as the already-stacked state of a resumed grid run.
        """
        import jax

        plan = self.plan
        if plan.grid_scales is None:
            raise ValueError(
                "plan has no γ-axis; compile it with grid_gammas=... to "
                "use the grid lane")
        if metrics not in ("chunk", "none"):
            raise ValueError(
                f'grid lane supports metrics="chunk"|"none" (got '
                f'{metrics!r})')
        g = plan.n_grid
        fn = self._grid_jit(g, metrics)
        # single vs already-stacked state: every AsyncTrainer state carries
        # a scalar "step" counter, so a vectorised one shows ndim == 1
        if isinstance(state, dict) and "step" in state:
            stacked = getattr(state["step"], "ndim", 0) == 1
        else:
            leaves = jax.tree_util.tree_leaves(state)
            stacked = bool(leaves) and \
                getattr(leaves[0], "shape", ())[:1] == (g,)
        states = state if stacked else self.stack_state(state)

        stats = ExecStats()
        rec = self.recorder
        self._attach_obs(snapshot)
        rows = []
        last_hi = start_round
        for lo, hi in _chunk_bounds(plan.rounds, rounds_per_launch,
                                    start_round):
            shared = self._slices(lo, hi)
            del shared["scale"]          # per-γ rows replace the base scale
            scales = plan.grid_slice(lo, hi)
            with span(rec, "launch", "executor", lo=lo, hi=hi, grid=g):
                out = fn(states, shared, scales)
            states, ms = out if metrics == "chunk" else (out, None)
            stats.launches += 1
            last_hi = hi
            self._maybe_snapshot(snapshot, hi, states, stats)
            if ms is not None:
                rows.append(ms)
        if rows:
            with span(rec, "host_sync", "executor", deferred=True):
                rows = [np.asarray(r) for r in jax.block_until_ready(rows)]
            stats.host_syncs = 1
        with span(rec, "barrier", "executor"):
            states = jax.block_until_ready(states)
        if snapshot is not None:
            snapshot.drain()
        all_ms = np.concatenate(rows, axis=1) if rows else None
        self._record_stats(stats, last_hi - start_round, lanes=g)
        return ExecResult(
            state=states,
            metrics=({} if all_ms is None else
                     {k: all_ms[:, :, j] for j, k in enumerate(METRICS)}),
            stats=stats)

    # ----------------------------------------------------------------- eager
    def run_eager(self, state, *, on_step: Optional[Callable] = None,
                  start_round: int = 0) -> ExecResult:
        """The parity oracle: the same plan, one launch + one host sync
        per round (the pre-runtime dispatch loop, kept as the semantic
        reference).  ``launches`` counts the train-step dispatches; the
        batch-synthesis jit that precedes each one is a data detail, not a
        round launch (see :class:`ExecStats`)."""
        import jax
        import jax.numpy as jnp

        plan = self.plan
        with_density = plan.grad_density is not None
        with_gain = plan.fault_gain is not None
        with_scale = plan.adaptive or with_density
        if self._eager is None:
            self._eager = (
                self.watch.wrap("eager_batch", jax.jit(self._batch_of)),
                self.watch.wrap("eager_step", self.trainer.jit_train_step(
                    (plan.global_batch, plan.seq_len),
                    donate=self.donate,
                    with_delay_scale=with_scale,
                    with_grad_density=with_density,
                    with_fault_gain=with_gain,
                    with_groups=self._gather)))
        batch_of, step = self._eager
        rec = self.recorder
        rows = []
        stats = ExecStats()
        for i in range(start_round, plan.rounds):
            key = jnp.asarray(plan.data_keys[i])
            batch = batch_of(key, jnp.int32(plan.cdf_index[i])) \
                if plan.cdf_index is not None else batch_of(key)
            args = (state, batch, jnp.asarray(plan.masks[i]))
            if with_scale:          # neutral plans: the trainer's own
                args += (jnp.float32(plan.delay_scales[i]),)  # static rule
            if with_density:
                args += (jnp.float32(plan.grad_density[i]),)
            if with_gain:
                args += (jnp.asarray(plan.fault_gain[i]),)
            if self._gather:
                args += (jnp.asarray(plan.groups[i]),)
            with span(rec, "launch", "executor", lo=i, hi=i + 1):
                state, m = step(*args)
            stats.launches += 1
            with span(rec, "host_sync", "executor", lo=i, hi=i + 1):
                row = {k: float(m[k]) for k in METRICS}  # host sync / round
            stats.host_syncs += 1
            rows.append([row[k] for k in METRICS])
            if on_step is not None:
                on_step(i, state, row)
        all_ms = np.asarray(rows, np.float32) if rows else \
            np.zeros((0, len(METRICS)), np.float32)
        self._record_stats(stats, plan.rounds - start_round)
        return ExecResult(
            state=state,
            metrics={k: all_ms[:, j] for j, k in enumerate(METRICS)},
            stats=stats)


def run_scan(trainer, plan: RunPlan, state, *, rounds_per_launch: int = 8,
             metrics: str = "chunk", on_step: Optional[Callable] = None,
             start_round: int = 0, donate: bool = True,
             snapshot=None, breaker=None, recorder=None) -> ExecResult:
    """One-shot convenience over :meth:`PlanExecutor.run_scan` (compiles
    fresh; hold a :class:`PlanExecutor` to reuse compiled chunks)."""
    return PlanExecutor(trainer, plan, donate=donate,
                        recorder=recorder).run_scan(
        state, rounds_per_launch=rounds_per_launch, metrics=metrics,
        on_step=on_step, start_round=start_round,
        snapshot=snapshot, breaker=breaker)


def run_eager(trainer, plan: RunPlan, state, *,
              on_step: Optional[Callable] = None, start_round: int = 0,
              donate: bool = True, recorder=None) -> ExecResult:
    """One-shot convenience over :meth:`PlanExecutor.run_eager`."""
    return PlanExecutor(trainer, plan, donate=donate,
                        recorder=recorder).run_eager(
        state, on_step=on_step, start_round=start_round)


def run_grid(trainer, plan: RunPlan, state, *, rounds_per_launch: int = 8,
             metrics: str = "chunk", start_round: int = 0,
             donate: bool = True, snapshot=None, recorder=None) -> ExecResult:
    """One-shot convenience over :meth:`PlanExecutor.run_grid`."""
    return PlanExecutor(trainer, plan, donate=donate,
                        recorder=recorder).run_grid(
        state, rounds_per_launch=rounds_per_launch, metrics=metrics,
        start_round=start_round, snapshot=snapshot)


RUNTIMES = {"scan": run_scan, "eager": run_eager}


def execute(trainer, plan: RunPlan, state, *, runtime: str = "scan",
            rounds_per_launch: int = 8, metrics: str = "chunk",
            **kw) -> ExecResult:
    """Dispatch on ``runtime`` (`"scan"` | `"eager"`).  ``metrics`` applies
    to the scan runtime only — eager reads every round back by
    construction."""
    if runtime not in RUNTIMES:
        raise ValueError(
            f"unknown runtime {runtime!r}; want one of {sorted(RUNTIMES)}")
    if runtime == "scan":
        kw["rounds_per_launch"] = rounds_per_launch
        kw["metrics"] = metrics
    return RUNTIMES[runtime](trainer, plan, state, **kw)
