"""Slot-based continuous-batching serving: one compiled decode loop.

``n_slots`` persistent decode lanes, each carrying its own position /
activity / budget, are stepped by ONE compiled program — the serving
analogue of the executor's per-round participation masks.  Every
``ServeJob`` runs here (``api.ServeBackend``).

Design, mirroring the repo's schedule-is-value-independent thesis:

* **Device**: a chunk of ``steps_per_launch`` decode steps runs as a
  ``lax.scan`` whose body calls ``models.decode_step`` with per-slot
  ``pos`` (the cache's positions buffer is per row as well).
  Inactive slots freeze (token/pos/remaining held by the active mask) and
  their ring re-writes are idempotent, so masking replaces control flow —
  the program never retraces as requests come and go.  Each step streams
  ``(step, tokens, active)`` host-ward through an ordered ``io_callback``
  tap (the PR 5 idiom), so per-request consumers receive tokens while the
  device keeps decoding — the host never barriers the loop.
* **Host**: with a fixed per-request token budget there is no
  content-dependent exit, so admissions, completions, occupancy and TTFT
  are pure bookkeeping — ZERO device readbacks steer the loop.  Admission
  (which queued request fills a freed slot, at chunk boundaries) is a
  registry scheduler via :class:`~repro.distributed.admission.AdmissionPolicy`,
  and the realised trace lowers to an ordinary ``Schedule`` for
  ``scenarios.tau_report``.
* **Prefill** is folded in per admitted request: a cached batch-1 prefill
  jit produces the first token + a ctx-length cache, and a cached ``admit``
  jit writes the row into the slot cache at a *traced* slot index — one
  compile covers every admission.
* **Sampling state is per-request**, not per-pool: each slot carries its
  own PRNG key, reset at admission to ``fold_in(PRNGKey(seed), rid)`` and
  split once per decode step.  A request's sampled token stream is a pure
  function of (seed, rid, step-within-request) — independent of slot
  assignment, pool size and whatever else is decoding alongside it.
* **Degradation is masked, not crashed**: an active lane whose decode
  logits go non-finite is QUARANTINED on device (its budget zeroed, no
  token emitted) and the eviction surfaces host-side through the tap so
  the admission trace records it; queued requests whose wait exceeds a
  ``deadline`` are timed out at admission sweeps without ever occupying a
  slot.  Both degrade per-request — the pool keeps serving.
* **Degraded requests get a bounded second chance** (:class:`RetryPolicy`):
  quarantine-evicted and deadline-timed-out requests re-enter the
  admission queue after a deterministic exponential backoff in decode
  steps, their already-emitted prefix replayed through prefill
  (``prompt + tokens-so-far``) so completed work is never discarded, and
  attempt ``a`` re-seeds the slot key as ``fold_in(fold_in(key, rid), a)``
  — retried token streams are reproducible.  Attempts are capped; the
  final failure is accounted in ``evictions``/``timeouts`` with its
  attempt count.  With no retry policy the PR-7/8 detect-and-discard
  semantics are unchanged.
* **The server itself is durable**: pass an
  :class:`~repro.checkpoint.AsyncSnapshotter` and every due chunk
  boundary offers a non-donating device copy of the decode state PLUS the
  host ledger (queue, rid→slot map, emitted tokens, retry/backoff state,
  admission-policy RNG) as snapshot metadata; ``serve(resume_from=dir)``
  restores both and continues — unaffected requests' token streams are
  bitwise identical to an uninterrupted run (the SIGKILL gate pins it).
* **Overload degrades predictably** (:class:`OverloadPolicy`): a bounded
  admission queue sheds to ``queue_cap`` at every sweep under
  ``reject-new`` (drop the newest arrivals) or ``drop-oldest`` (drop the
  head of the queue); ``drain_after=k`` stops admitting at step k,
  finishes in-flight lanes and cancels the rest.  Shed and drained
  requests are terminal and explicitly accounted — no silent loss.
* **Faults are injectable deterministically**: a
  ``repro.faults.ServeFaults`` bundle poisons chosen (rid, decode-step)
  cells to NaN inside the chunk program (an all-false mask is bitwise
  identity — clean runs keep token parity) and schedules driver
  preemptions that raise :class:`ServePreempted` at chunk boundaries
  after forcing a snapshot offer — the chaos-soak substrate.

Compiled artifacts are cached on the instance (the PlanExecutor rule: a
fresh closure per call would silently recompile every run), asserted by
:meth:`SlotServer.compile_counts`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import model as M
from ..models.specs import Spec
from ..obs import CompileWatch, span
from .admission import AdmissionPolicy, AdmissionTrace, parse_admission
from .sharding import Rules, DEFAULT_RULES, sharded_trace, tree_shardings


@dataclasses.dataclass
class SlotConfig:
    """Knobs of the slot loop.

    ``steps_per_launch`` is the decode analogue of the executor's
    ``rounds_per_launch``: admissions land at chunk boundaries, so it
    trades admission latency against dispatch amortisation.
    """

    n_slots: int
    ctx_len: int
    temperature: float = 0.0     # 0 = greedy
    seed: int = 0
    steps_per_launch: int = 8

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.steps_per_launch < 1:
            raise ValueError("steps_per_launch must be >= 1")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded re-admission of degraded requests.

    A quarantine eviction or deadline timeout consumes one *attempt*;
    while ``attempts consumed < max_attempts`` the request re-enters the
    admission queue after ``backoff_steps(failures)`` decode steps
    (deterministic exponential backoff:
    ``backoff_base · backoff_factor^(failures−1)``, in decode-step
    units), replaying its already-emitted token prefix through prefill.
    At the cap the LAST failure is terminal and lands in
    ``ServeResult.evictions`` / ``.timeouts`` with the attempt count in
    ``.attempts``.  ``max_attempts=1`` reproduces the no-retry
    detect-and-discard semantics exactly.
    """

    max_attempts: int = 2
    backoff_base: int = 4
    backoff_factor: float = 2.0

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1 (got {self.max_attempts})")
        if self.backoff_base < 0:
            raise ValueError(
                f"backoff_base must be >= 0 (got {self.backoff_base})")
        if self.backoff_factor < 1.0:
            raise ValueError(
                f"backoff_factor must be >= 1 (got {self.backoff_factor})")

    def backoff_steps(self, failures: int) -> int:
        """Decode steps to wait after the ``failures``-th failure."""
        return int(round(self.backoff_base
                         * self.backoff_factor ** (max(failures, 1) - 1)))


SHED_POLICIES = ("reject-new", "drop-oldest")


@dataclasses.dataclass(frozen=True)
class OverloadPolicy:
    """Bounded admission queue: at every sweep, eligible-but-waiting
    requests beyond ``queue_cap`` are SHED (terminal, accounted in
    ``ServeResult.shed``) — ``reject-new`` drops the newest entrants,
    ``drop-oldest`` drops the head of the queue to make room for them.
    """

    queue_cap: int
    shed: str = "reject-new"

    def __post_init__(self):
        if self.queue_cap < 1:
            raise ValueError(
                f"queue_cap must be >= 1 (got {self.queue_cap})")
        if self.shed not in SHED_POLICIES:
            raise ValueError(
                f"unknown shed policy {self.shed!r}; want one of "
                f"{SHED_POLICIES}")


class ServePreempted(RuntimeError):
    """Raised by ``serve`` at a scheduled ``serve_preempt`` boundary
    (after forcing a snapshot offer, when a snapshotter is attached).
    Carries the decode step the driver died at; harnesses catch it and
    resume via ``serve(resume_from=...)``."""

    def __init__(self, step: int, at: int):
        super().__init__(
            f"serve driver preempted at decode-step boundary {step} "
            f"(scheduled at step {at})")
        self.step = int(step)
        self.at = int(at)


@dataclasses.dataclass
class ServeResult:
    """Per-request token matrix + the realised admission world.

    Degraded requests pad: an evicted request's ``tokens`` row holds −1
    from its (last attempt's) quarantine point on — any prefix recovered
    by earlier attempts is kept; a timed-out / shed / drained request
    that was never admitted has an all −1 row and a −1 ``ttft_steps``
    entry.  Every submitted request lands in exactly one of: a full
    token row, ``evictions``, ``timeouts``, ``shed`` or ``drained`` —
    the no-silent-loss invariant the chaos suite asserts.
    """

    tokens: np.ndarray           # (n_requests, max_new) int32, −1 padded
    schedule: object             # repro.core.engine.Schedule of admissions
    ttft_steps: np.ndarray       # (n_requests,) admission − arrival (steps)
    occupancy: float             # mean fraction of busy slot-steps
    decode_steps: int            # launched scan steps (incl. drained tail)
    chunks: int                  # XLA launches of the chunk program
    tap_rows: int                # ordered io_callback rows delivered
    evictions: dict = dataclasses.field(default_factory=dict)
    #: rid -> decode step its lane was quarantined (non-finite logits);
    #: with retries, only TERMINAL (attempt-exhausted) evictions
    timeouts: dict = dataclasses.field(default_factory=dict)
    #: rid -> decode step its queue wait exceeded the deadline (terminal)
    shed: dict = dataclasses.field(default_factory=dict)
    #: rid -> decode step overload control shed it (terminal)
    drained: dict = dataclasses.field(default_factory=dict)
    #: rid -> decode step a graceful drain cancelled it (terminal)
    attempts: dict = dataclasses.field(default_factory=dict)
    #: rid -> failed attempts consumed (retried requests only)
    resumed_from: Optional[int] = None
    #: decode step this serve resumed a snapshot at (None = fresh run)


def _tok_int(x) -> int:
    """Host int from a deferred device tok0 (or an already-read int)."""
    return x if isinstance(x, int) else int(np.asarray(x).reshape(-1)[0])


class _Ledger:
    """Host-side bookkeeping of one serve run.

    Everything the sweep loop needs to steer admission, retries, shedding
    and accounting lives here — and it is JSON-serialisable
    (:meth:`to_json` / :meth:`from_json`), so a snapshot restores the
    DRIVER's world, not just the device carry.  Request lifecycle:
    ``queued`` (waiting / backing off, ``eligible[rid]`` = step it may be
    admitted from) → ``inflight`` (occupies a slot, ``fin[rid]`` = its
    deterministic completion step) → ``done`` (completed or terminally
    failed).
    """

    def __init__(self, n_req: int, n_slots: int, arrivals):
        self.t = 0                   # decode-step clock (chunk boundaries)
        self.chunks = 0              # lifetime chunk count (across resumes)
        self.busy_steps = 0
        self.slot_rid = [-1] * n_slots
        self.state_of = {r: "queued" for r in range(n_req)}
        self.eligible = {r: int(arrivals[r]) for r in range(n_req)}
        self.fin = {}          # rid -> completion step of CURRENT attempt
        self.admit_t = {}      # rid -> FIRST admission step (ttft)
        self.tries = {}        # rid -> failed attempts consumed
        self.emitted = {}      # rid -> ints recovered by failed attempts
        self.outputs = {}      # rid -> [tok0 (dev|int), ints...] this attempt
        self.cur_evict = {}    # rid -> quarantine step (sink-written)
        self.evict_events = []  # [rid, step] in tap order (sink-appended)
        self.evt_cursor = 0    # events before it are host-processed
        self.evictions = {}    # terminal accounting maps (rid -> step)
        self.timeouts = {}
        self.shed = {}
        self.drained = {}
        self.drain_t = None    # step the drain began (None = not draining)

    @property
    def in_flight(self) -> int:
        return sum(1 for v in self.state_of.values() if v == "inflight")

    @property
    def done(self) -> int:
        return sum(1 for v in self.state_of.values() if v == "done")

    _INT_MAPS = ("eligible", "fin", "admit_t", "tries", "cur_evict",
                 "evictions", "timeouts", "shed", "drained")

    def to_json(self) -> dict:
        out_rows = {}
        for rid, row in self.outputs.items():
            row[0] = _tok_int(row[0])         # force the deferred read once
            out_rows[str(rid)] = [int(x) for x in row]
        d = {"t": self.t, "chunks": self.chunks,
             "busy_steps": self.busy_steps,
             "slot_rid": [int(s) for s in self.slot_rid],
             "state_of": {str(k): v for k, v in self.state_of.items()},
             "emitted": {str(k): [int(x) for x in v]
                         for k, v in self.emitted.items()},
             "outputs": out_rows,
             "evict_events": [[int(a), int(b)] for a, b in
                              self.evict_events],
             "evt_cursor": int(self.evt_cursor),
             "drain_t": self.drain_t}
        for name in self._INT_MAPS:
            d[name] = {str(k): int(v)
                       for k, v in getattr(self, name).items()}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "_Ledger":
        L = cls(0, len(d["slot_rid"]), [])
        L.t = int(d["t"])
        L.chunks = int(d["chunks"])
        L.busy_steps = int(d["busy_steps"])
        L.slot_rid = [int(s) for s in d["slot_rid"]]
        L.state_of = {int(k): str(v) for k, v in d["state_of"].items()}
        L.emitted = {int(k): [int(x) for x in v]
                     for k, v in d["emitted"].items()}
        L.outputs = {int(k): [int(x) for x in v]
                     for k, v in d["outputs"].items()}
        L.evict_events = [[int(a), int(b)] for a, b in d["evict_events"]]
        L.evt_cursor = int(d["evt_cursor"])
        L.drain_t = None if d["drain_t"] is None else int(d["drain_t"])
        for name in cls._INT_MAPS:
            setattr(L, name, {int(k): int(v) for k, v in d[name].items()})
        return L


class SlotServer:
    """Continuous-batching decode over ``n_slots`` lanes."""

    def __init__(self, cfg: ArchConfig, mesh: Mesh, slots: SlotConfig,
                 rules: Rules = DEFAULT_RULES, recorder=None):
        if cfg.family in ("vlm", "audio"):
            raise NotImplementedError(
                f"slot serving admits token-only prompts; the {cfg.family!r} "
                "family needs per-request modality inputs (follow-up)")
        self.cfg, self.mesh, self.slots, self.rules = cfg, mesh, slots, rules
        self.recorder = recorder      # repro.obs.Recorder | None
        self.watch = CompileWatch(recorder)   # retrace sentinel
        self._chunk_fn = None         # cached jitted chunk program
        self._admit_fn = None         # cached jitted slot writer
        self._prefill_jits = {}       # prompt_len -> jitted batch-1 prefill
        self._tap_sink = None         # per-run host consumer of tap rows
        self._zero_poison = None      # cached all-false (K, S) fault mask
        if recorder is not None:
            for kind, n in self.pool_bytes().items():
                recorder.count(f"slot_pool_bytes.{kind}", n)

    def pool_bytes(self) -> dict:
        """Bytes the slot pool's cache holds, by kind: the K/V rings
        (``kv``) and the recurrent SSD and conv state (``ssm_state``); the
        per-slot positions are neither."""
        out = {"kv": 0, "ssm_state": 0}
        specs = M.cache_specs(self.cfg, self.slots.n_slots,
                              self.slots.ctx_len)
        for path, s in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, Spec)):
            top = path[0].key
            if top != "positions":
                kind = "ssm_state" if top.startswith("ssm") else "kv"
                out[kind] += int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        return out

    # ---- shardings ---------------------------------------------------------
    def param_shardings(self):
        return tree_shardings(M.param_specs(self.cfg), self.mesh, self.rules)

    def state_shardings(self):
        S = self.slots.n_slots
        cache_sh = tree_shardings(
            M.cache_specs(self.cfg, S, self.slots.ctx_len),
            self.mesh, self.rules)
        lane = NamedSharding(self.mesh, P(self.rules.data_axes[-1]
                                          if S > 1 else None))
        repl = NamedSharding(self.mesh, P())
        return {"cache": cache_sh, "toks": lane, "pos": lane,
                "active": lane, "remaining": lane, "keys": repl}

    # ---- state -------------------------------------------------------------
    def _state_template(self) -> dict:
        """All slots empty: inactive lanes decode-and-discard until a
        request is admitted (their writes are idempotent)."""
        S = self.slots.n_slots
        return {
            "cache": M.init_cache(self.cfg, S, self.slots.ctx_len),
            "toks": jnp.zeros((S,), jnp.int32),
            "pos": jnp.zeros((S,), jnp.int32),
            "active": jnp.zeros((S,), bool),
            "remaining": jnp.zeros((S,), jnp.int32),
            # (S, 2) per-slot sampling keys; placeholders until admission
            # re-seeds each slot with its request's fold_in key
            "keys": jnp.tile(jax.random.PRNGKey(self.slots.seed)[None],
                             (S, 1)),
        }

    def init_state(self) -> dict:
        # pin the canonical shardings up front: every producer of a state
        # tree (init / admit / chunk) must agree, or the jits re-specialise
        # on their first post-admission call
        return jax.device_put(self._state_template(), self.state_shardings())

    def abstract_state(self) -> dict:
        """ShapeDtypeStruct mirror of the decode state, for
        ``checkpoint.restore`` (crash-resume) without allocating."""
        return jax.eval_shape(self._state_template)

    # ---- tap ---------------------------------------------------------------
    def _emit_tap(self, idx, toks, active, quarantined):
        """Host side of the ordered io_callback (bound once so the chunk
        program stays stable; the per-run consumer swaps in via
        ``_tap_sink``)."""
        sink = self._tap_sink
        if sink is not None:
            with span(self.recorder, "tap", "server"):
                sink(int(idx), np.asarray(toks), np.asarray(active),
                     np.asarray(quarantined))

    # ---- compiled programs -------------------------------------------------
    def chunk_fn(self):
        """Jitted ``chunk(params, state, idx0, poison) -> state``: K
        decode steps with per-step tap emission.  Compiled once;
        ``idx0`` is a traced scalar so chunk position never retraces.
        ``poison`` is a (K, n_slots) bool fault-injection mask: flagged
        cells force that lane's logits to NaN BEFORE the finite check, so
        the ordinary quarantine path fires deterministically.  An
        all-false mask is bitwise identity — clean serves pay nothing."""
        if self._chunk_fn is not None:
            return self._chunk_fn
        from jax.experimental import io_callback

        cfg, ctx = self.cfg, self.slots.ctx_len
        temp, K = self.slots.temperature, self.slots.steps_per_launch
        emit = self._emit_tap

        def decode(params, cache, toks, pos):
            return M.decode_step(cfg, params, cache, toks, pos, ctx)

        decode = sharded_trace(decode, self.mesh, self.rules)

        def chunk(params, state, idx0, poison):
            def round_fn(st, xs):
                idx, poison_row = xs["idx"], xs["poison"]
                logits, cache = decode(params, st["cache"], st["toks"],
                                       st["pos"])
                logits = jnp.where(poison_row[:, None], jnp.nan, logits)
                act = st["active"]
                # quarantine: an active lane whose logits go non-finite is
                # evicted in-mask — no token this step, budget zeroed so the
                # lane freezes (idempotent writes) until re-admission; the
                # rest of the pool is untouched
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                quar = act & ~finite
                act = act & finite
                keys = st["keys"]
                if temp > 0:
                    # per-slot streams: each lane splits its own key, so a
                    # request's samples depend only on (seed, rid, step)
                    pair = jax.vmap(jax.random.split)(keys)      # (S, 2, 2)
                    keys, subs = pair[:, 0], pair[:, 1]
                    nxt = jax.vmap(lambda k, lg: jax.random.categorical(
                        k, lg / temp))(subs, logits).astype(jnp.int32)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                step = act.astype(jnp.int32)
                toks = jnp.where(act, nxt, st["toks"])
                rem = (st["remaining"] - step) * (~quar).astype(jnp.int32)
                # ordered: per-request consumers see tokens in decode order
                io_callback(emit, None, idx, toks, act, quar, ordered=True)
                return {"cache": cache, "toks": toks,
                        "pos": st["pos"] + step,
                        "active": act & (rem > 0), "remaining": rem,
                        "keys": keys}, None

            state, _ = jax.lax.scan(
                round_fn, state,
                {"idx": idx0 + jnp.arange(K, dtype=jnp.int32),
                 "poison": poison})
            return state

        repl = NamedSharding(self.mesh, P())
        self._chunk_fn = self.watch.wrap("chunk", jax.jit(
            chunk,
            in_shardings=(self.param_shardings(), self.state_shardings(),
                          repl, repl),
            out_shardings=self.state_shardings(),
            donate_argnums=(1,)))
        return self._chunk_fn

    def admit_fn(self):
        """Jitted ``admit(state, pcache, slot, tok0, pos0, rem0, key)``:
        write a prefilled request into slot ``slot`` (a TRACED index — one
        compile covers every admission into any slot).  ``key`` is the
        request's own sampling key (``fold_in(PRNGKey(seed), rid)``) — it
        resets the slot's stream so sampling never leaks across the
        requests that share a lane over time."""
        if self._admit_fn is not None:
            return self._admit_fn
        # each cache leaf's batch axis, read off its logical axes
        batch_axis = jax.tree_util.tree_map(
            lambda s: s.axes.index("batch"),
            M.cache_specs(self.cfg, 1, self.slots.ctx_len),
            is_leaf=lambda x: isinstance(x, Spec))

        def admit(state, pcache, slot, tok0, pos0, rem0, key):
            def wr(c, p, ax):
                # the batch-1 row lands at ``slot`` on the batch axis
                start = (0,) * ax + (slot,) + (0,) * (c.ndim - ax - 1)
                return jax.lax.dynamic_update_slice(c, p.astype(c.dtype),
                                                    start)

            return {
                "cache": jax.tree_util.tree_map(wr, state["cache"], pcache,
                                                batch_axis),
                "toks": state["toks"].at[slot].set(tok0),
                "pos": state["pos"].at[slot].set(pos0),
                "active": state["active"].at[slot].set(rem0 > 0),
                "remaining": state["remaining"].at[slot].set(rem0),
                "keys": state["keys"].at[slot].set(key),
            }

        self._admit_fn = self.watch.wrap("admit", jax.jit(
            admit, out_shardings=self.state_shardings(),
            donate_argnums=(0,)))
        return self._admit_fn

    def prefill_fn(self, prompt_len: int):
        """Jitted batch-1 prefill → (first token (1,), ctx-length cache);
        cached per prompt length."""
        fn = self._prefill_jits.get(prompt_len)
        if fn is None:
            cfg, ctx = self.cfg, self.slots.ctx_len

            def pf(params, tokens):
                logits, cache = M.prefill(cfg, params, {"tokens": tokens},
                                          ctx_len=ctx)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

            fn = self.watch.wrap(f"prefill[{prompt_len}]", jax.jit(pf))
            self._prefill_jits[prompt_len] = fn
        return fn

    def compile_counts(self) -> dict:
        """Traced-signature counts of the cached jits (the no-retrace
        gate: rotating requests through freed slots must keep these at 1
        per program).  Backed by the :class:`repro.obs.CompileWatch`
        retrace sentinel — with a recorder attached, every compile also
        lands as an instant in the trace."""
        return self.watch.counts()

    # ---- driver ------------------------------------------------------------
    def serve(self, params, prompts: np.ndarray, max_new: int, *,
              admission: Union[str, AdmissionPolicy] = "pure",
              arrivals: Optional[np.ndarray] = None,
              deadline: Optional[int] = None,
              on_token: Optional[Callable] = None,
              retry: Optional[RetryPolicy] = None,
              overload: Optional[OverloadPolicy] = None,
              drain_after: Optional[int] = None,
              faults=None, snapshot=None,
              resume_from: Optional[str] = None) -> ServeResult:
        """Serve every prompt to its ``max_new``-token budget.

        prompts: (n_requests, prompt_len) int32; ``arrivals``: optional
        (n_requests,) arrival steps on the decode-step clock (see
        :func:`~repro.distributed.admission.draw_arrivals`); ``admission``:
        a policy name/compact spec or a prepared :class:`AdmissionPolicy`;
        ``deadline``: optional queue-wait budget in decode steps — a
        request still queued when ``now − eligible > deadline`` is timed
        out at the admission sweep (chunk-boundary granularity) and never
        occupies a slot; ``on_token(rid, token, step)`` fires per streamed
        token from the tap thread (token already a host int).

        Resilience kwargs (each ``None`` ⇒ exact PR-7/8 behaviour):

        * ``retry`` (:class:`RetryPolicy`) — evictions/timeouts consume
          attempts and re-queue with deterministic backoff instead of
          being terminal on first failure; the emitted prefix replays
          through prefill at re-admission.
        * ``overload`` (:class:`OverloadPolicy`) — bounded admission
          queue; eligible waiters beyond ``queue_cap`` are shed.
        * ``drain_after=k`` — graceful drain: at the first sweep with
          ``t >= k`` every queued request is cancelled (``drained``) and
          only in-flight lanes run to completion.
        * ``faults`` (``repro.faults.ServeFaults``-shaped) — poison
          chosen (rid, decode-step) cells to NaN inside the chunk and
          schedule :class:`ServePreempted` driver kills.
        * ``snapshot`` (:class:`~repro.checkpoint.AsyncSnapshotter`) —
          offer decode state + host ledger at every due chunk boundary;
          ``resume_from=dir`` restores such a snapshot and continues
          (``prompts``/``max_new``/knobs must match the original call).

        The loop is steered entirely by host bookkeeping: completions are
        deterministic (``admit_step + remaining``), so no device value is
        ever read to decide admission — only the final token matrix is
        assembled from the tap stream.  Quarantine evictions are the one
        DEVICE-initiated event: the host learns of them from the tap.
        Without retries the slot stays allocated until the original
        completion step (the frozen lane idle-decodes harmlessly); with
        retries the host frees it at the next sweep and re-queues the
        request.  Any of ``retry``/``faults``/``snapshot``/``resume_from``
        switches the loop to SYNC dispatch (an ``effects_barrier`` per
        chunk) so the ledger is consistent at every sweep; clean serves
        keep the fully asynchronous legacy path.
        """
        S, K = self.slots.n_slots, self.slots.steps_per_launch
        n_req, plen = prompts.shape
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if plen + max_new > self.slots.ctx_len:
            raise ValueError(
                f"prompt_len + max_new = {plen + max_new} exceeds "
                f"ctx_len = {self.slots.ctx_len}")
        if isinstance(admission, AdmissionPolicy):
            policy = admission
        else:
            name, b = parse_admission(admission)
            policy = AdmissionPolicy(name, n_req, b=b,
                                     seed=self.slots.seed)
        arr = (np.zeros(n_req, np.int64) if arrivals is None
               else np.asarray(arrivals, np.int64))
        if arr.shape != (n_req,):
            raise ValueError(f"arrivals must be ({n_req},); got {arr.shape}")
        if deadline is not None and deadline < 0:
            raise ValueError(f"deadline must be >= 0 (got {deadline})")
        if drain_after is not None and drain_after < 0:
            raise ValueError(
                f"drain_after must be >= 0 (got {drain_after})")

        poisons: dict = {}            # decode step -> set of poisoned rids
        preempts: tuple = ()
        if faults is not None:
            for rid_c, st_c in getattr(faults, "poisons", ()):
                poisons.setdefault(int(st_c), set()).add(int(rid_c))
            preempts = tuple(sorted(
                int(p) for p in getattr(faults, "preempt_steps", ())))
        # device-initiated events must be host-visible at the NEXT sweep
        # for retries/snapshots to be deterministic — barrier per chunk;
        # clean serves keep the async run-ahead dispatch
        sync = (retry is not None or snapshot is not None
                or resume_from is not None or bool(poisons)
                or bool(preempts))

        chunk = self.chunk_fn()
        admit = self.admit_fn()
        pf = self.prefill_fn(plen)
        prompts_dev = jnp.asarray(prompts, jnp.int32)
        base_key = jax.random.PRNGKey(self.slots.seed)
        if self._zero_poison is None:
            self._zero_poison = jax.device_put(
                np.zeros((K, S), bool), NamedSharding(self.mesh, P()))

        trace = AdmissionTrace(n_req, wait_b=policy.wait_b)
        resumed_from = None
        if resume_from is not None:
            from ..checkpoint import checkpointer as _ckpt

            meta = _ckpt.load_meta(resume_from)
            if "serve_ledger" not in meta:
                raise ValueError(
                    f"{resume_from} is not a serve snapshot (no ledger)")
            L = _Ledger.from_json(meta["serve_ledger"])
            if len(L.slot_rid) != S or len(L.state_of) != n_req:
                raise ValueError(
                    "snapshot geometry mismatch: ledger has "
                    f"{len(L.slot_rid)} slots / {len(L.state_of)} requests, "
                    f"server has {S} / {n_req}")
            policy.load_state(meta["admission_policy"])
            trace.load_state(meta["admission_trace"])
            state = _ckpt.restore(resume_from, self.abstract_state(),
                                  shardings=self.state_shardings())
            resumed_from = L.t
        else:
            L = _Ledger(n_req, S, arr)
            state = self.init_state()
        rec = self.recorder
        step_maps: dict = {}          # chunk start -> [(rid, fin)] snapshot
        req_ns: dict = {}             # rid -> admission wall-clock ns (obs)
        tap_stats = {"rows": 0}
        mismatches: list = []

        def sink(idx, toks, act, quar):
            tap_stats["rows"] += 1
            m = step_maps.get(idx - idx % K)
            if m is None:
                mismatches.append(f"step {idx}: no chunk snapshot")
                return
            for s, (rid, fin_s) in enumerate(m):
                if bool(quar[s]):
                    if rid < 0:
                        mismatches.append(
                            f"step {idx} slot {s}: quarantine on an empty "
                            "lane")
                        continue
                    if rid not in L.cur_evict:
                        L.cur_evict[rid] = int(idx)
                        L.evict_events.append([rid, int(idx)])
                        if rec is not None:
                            rec.instant("evict", lane="faults", rid=rid,
                                        step=int(idx))
                            rec.count("evictions")
                ev = L.cur_evict.get(rid) if rid >= 0 else None
                predicted = (rid >= 0 and idx < fin_s
                             and (ev is None or idx < ev))
                if bool(act[s]) != predicted:
                    mismatches.append(
                        f"step {idx} slot {s}: device active={bool(act[s])} "
                        f"!= host-predicted {predicted}")
                    continue
                if predicted:
                    tok = int(toks[s])
                    L.outputs[rid].append(tok)
                    if on_token is not None:
                        on_token(rid, tok, int(idx))

        def ledger_meta():
            return {"serve_ledger": L.to_json(),
                    "admission_policy": policy.state_dict(),
                    "admission_trace": trace.state_dict()}

        def drain_events():
            """Fold sink-recorded quarantine evictions into the ledger."""
            while L.evt_cursor < len(L.evict_events):
                rid, step = L.evict_events[L.evt_cursor]
                L.evt_cursor += 1
                if retry is None:
                    # legacy: the lane stays booked until its scheduled
                    # completion; the eviction is terminal metadata
                    if rid not in L.evictions:
                        L.evictions[rid] = step
                        trace.evicted(rid, step)
                    continue
                # retry: the attempt failed — free the frozen lane now
                for s in range(S):
                    if L.slot_rid[s] == rid:
                        L.slot_rid[s] = -1
                req_ns.pop(rid, None)
                row = L.outputs.pop(rid, None)
                if row is not None:
                    L.emitted[rid] = (L.emitted.get(rid, [])
                                      + [_tok_int(x) for x in row])
                L.cur_evict.pop(rid, None)
                tries = L.tries[rid] = L.tries.get(rid, 0) + 1
                trace.retried(rid, tries)
                if (tries < retry.max_attempts
                        and len(L.emitted.get(rid, [])) < max_new):
                    L.state_of[rid] = "queued"
                    L.eligible[rid] = step + retry.backoff_steps(tries)
                    policy.requeue(rid)
                    if rec is not None:
                        rec.instant("retry", lane="server", rid=rid,
                                    step=step, attempt=tries)
                        rec.count("retries")
                else:
                    L.state_of[rid] = "done"
                    L.evictions[rid] = step
                    trace.evicted(rid, step)
                    policy.cancel(rid)

        t = L.t
        start_t0 = L.t                # resumed: pre-crash preempts spent
        chunks_run = 0                # this PROCESS (tap accounting)
        last_offered = None
        drain_ns = None
        attempts_bound = retry.max_attempts if retry is not None else 1
        backoff_total = (sum(retry.backoff_steps(f)
                             for f in range(1, attempts_bound))
                         if retry is not None else 0)
        horizon = 2 * (int(arr.max(initial=0))
                       + n_req * (max_new * attempts_bound + backoff_total)
                       + K) + 4 * K
        self._tap_sink = sink
        try:
            while L.done < n_req:
                if t > horizon:
                    raise RuntimeError(
                        f"slot loop passed its horizon ({horizon} steps) "
                        f"with {n_req - L.done} requests unfinished — "
                        "admission bookkeeping is stuck")
                with span(rec, "admission_sweep", "server", t=t):
                    drain_events()
                    # -- scheduled preemption of this process ---------------
                    if preempts:
                        due_p = next(
                            (p for p in preempts if start_t0 < p <= t), None)
                        if due_p is not None:
                            if snapshot is not None:
                                if last_offered != t:
                                    snapshot.offer(t, state,
                                                   meta=ledger_meta())
                                snapshot.drain()
                            raise ServePreempted(t, due_p)
                    # -- completions (deterministic, no readback) -------------
                    freed = sorted(
                        (s for s in range(S)
                         if L.slot_rid[s] >= 0 and L.fin[L.slot_rid[s]] <= t),
                        key=lambda s: (L.fin[L.slot_rid[s]], s))
                    for s in freed:
                        rid, L.slot_rid[s] = L.slot_rid[s], -1
                        L.state_of[rid] = "done"
                        trace.completed(rid, s, L.fin[rid], L.in_flight + 1)
                        policy.notify_completion(rid)
                        if rec is not None and rid in req_ns:
                            # per-request lifetime on the slot's own lane
                            rec.span_at("request", f"slot{s}", req_ns.pop(rid),
                                        rec.now_ns(), rid=rid,
                                        steps=L.fin[rid] - L.admit_t[rid] + 1)
                            rec.count("completions")
                    # -- graceful drain (stop admitting, finish in-flight) ----
                    if (drain_after is not None and t >= drain_after
                            and L.drain_t is None):
                        L.drain_t = t
                        drain_ns = rec.now_ns() if rec is not None else None
                        for r in sorted(L.state_of):
                            if L.state_of[r] == "queued":
                                L.state_of[r] = "done"
                                L.drained[r] = t
                                trace.drained(r, t)
                                policy.cancel(r)
                        if rec is not None:
                            rec.instant("drain_start", lane="server", step=t,
                                        cancelled=len(L.drained),
                                        in_flight=L.in_flight)
                            rec.count("drained", len(L.drained))
                    # -- deadline timeouts (queue-wait budget) ----------------
                    if deadline is not None:
                        for r in range(n_req):
                            if L.state_of[r] != "queued":
                                continue
                            el = L.eligible[r]
                            if el <= t and t - el > deadline:
                                if retry is not None:
                                    tries = L.tries[r] = L.tries.get(r, 0) + 1
                                    trace.retried(r, tries)
                                    if tries < retry.max_attempts:
                                        L.eligible[r] = (
                                            t + retry.backoff_steps(tries))
                                        if rec is not None:
                                            rec.instant("retry", lane="server",
                                                        rid=r, step=t,
                                                        attempt=tries)
                                            rec.count("retries")
                                        continue
                                L.timeouts[r] = t
                                L.state_of[r] = "done"
                                policy.cancel(r)
                                trace.timed_out(r, t)
                                if rec is not None:
                                    rec.instant("timeout", lane="server",
                                                rid=r, step=t,
                                                wait=t - int(el))
                                    rec.count("timeouts")
                    # -- admissions into free slots ---------------------------
                    arrived = {r for r, st_r in L.state_of.items()
                               if st_r == "queued" and L.eligible[r] <= t}
                    free = [s for s in range(S) if L.slot_rid[s] < 0]
                    while free:
                        rid = policy.pick(arrived, L.in_flight)
                        if rid is None:
                            break
                        s = free[0]
                        tries = L.tries.get(rid, 0)
                        pre = L.emitted.get(rid, [])
                        e = len(pre)
                        if e:
                            # replay the recovered prefix: re-prefill
                            # prompt + tokens-emitted-so-far
                            pf_e = self.prefill_fn(plen + e)
                            ptoks = jnp.asarray(
                                np.concatenate(
                                    [prompts[rid],
                                     np.asarray(pre, np.int64)])[None],
                                jnp.int32)
                        else:
                            pf_e, ptoks = pf, prompts_dev[rid:rid + 1]
                        key = jax.random.fold_in(base_key, rid)
                        if tries:
                            key = jax.random.fold_in(key, tries)
                        rem0 = max_new - 1 - e
                        with span(rec, "prefill", "server", rid=rid,
                                  plen=plen + e):
                            tok0, pcache = pf_e(params, ptoks)
                        with span(rec, "admit", "server", rid=rid, slot=s):
                            state = admit(state, pcache, s, tok0[0],
                                          jnp.int32(plen + e),
                                          jnp.int32(rem0), key)
                        L.outputs[rid] = [tok0]
                        L.admit_t.setdefault(rid, t)
                        L.fin[rid] = t + rem0
                        trace.admitted(rid, t)
                        arrived.discard(rid)
                        if rec is not None:
                            rec.hist("ttft_steps", t - int(arr[rid]))
                            req_ns[rid] = rec.now_ns()
                        if rem0 == 0:     # budget already emitted: completes
                            L.state_of[rid] = "done"   # at admission
                            trace.completed(rid, s, t, L.in_flight + 1)
                            policy.notify_completion(rid)
                            if rec is not None and rid in req_ns:
                                rec.span_at("request", f"slot{s}",
                                            req_ns.pop(rid), rec.now_ns(),
                                            rid=rid, steps=1)
                                rec.count("completions")
                        else:
                            L.slot_rid[s] = rid
                            L.state_of[rid] = "inflight"
                            free.pop(0)
                    # -- overload shedding (bounded admission queue) ----------
                    if overload is not None:
                        waiting = sorted(
                            (r for r, st_r in L.state_of.items()
                             if st_r == "queued" and L.eligible[r] <= t),
                            key=lambda r: (L.eligible[r], r))
                        excess = len(waiting) - overload.queue_cap
                        if excess > 0:
                            victims = (waiting[-excess:]
                                       if overload.shed == "reject-new"
                                       else waiting[:excess])
                            for r in victims:
                                L.state_of[r] = "done"
                                L.shed[r] = t
                                trace.shed(r, t)
                                policy.cancel(r)
                                if rec is not None:
                                    rec.instant("shed", lane="server", rid=r,
                                                step=t, policy=overload.shed)
                                    rec.count("shed")
                if rec is not None:
                    rec.gauge("in_flight", L.in_flight, lane="server")
                    rec.gauge("occupancy", L.in_flight / S, lane="server")
                if L.done >= n_req:
                    break
                if L.in_flight == 0:
                    # idle pool, pending arrivals/backoffs: fast-forward
                    # the clock to the next chunk boundary at/after the
                    # earliest eligibility — no launch for empty air
                    nxt = min(L.eligible[r] for r, st_r in L.state_of.items()
                              if st_r == "queued")
                    t = max(t + K, -(-int(nxt) // K) * K)
                    L.t = t
                    continue
                # -- one chunk launch --------------------------------------
                step_maps[t] = [(rid, L.fin.get(rid, -1))
                                for rid in L.slot_rid]
                for s in range(S):
                    rid = L.slot_rid[s]
                    if rid >= 0:
                        L.busy_steps += max(0, min(t + K, L.fin[rid]) - t)
                pz = self._zero_poison
                if poisons:
                    mask = np.zeros((K, S), bool)
                    hit = False
                    for j in range(K):
                        cells = poisons.get(t + j)
                        if not cells:
                            continue
                        for s in range(S):
                            if L.slot_rid[s] in cells:
                                mask[j, s] = True
                                hit = True
                    if hit:
                        pz = mask
                with span(rec, "launch", "server", t=t,
                          in_flight=L.in_flight):
                    state = chunk(params, state, jnp.int32(t), pz)
                chunks_run += 1
                L.chunks += 1
                t += K
                L.t = t
                if sync:
                    with span(rec, "chunk_barrier", "server", t=t):
                        jax.effects_barrier()
                if snapshot is not None and snapshot.due(t, 1 << 62):
                    drain_events()   # ledger must reflect delivered taps
                    snapshot.offer(t, state, meta=ledger_meta())
                    last_offered = t
            with span(rec, "barrier", "server"):
                state = jax.block_until_ready(state)
                jax.effects_barrier()
            drain_events()
        finally:
            self._tap_sink = None

        if mismatches:
            raise RuntimeError(
                "device masks diverged from host bookkeeping:\n  "
                + "\n  ".join(mismatches[:10]))
        if tap_stats["rows"] != chunks_run * K:
            raise RuntimeError(
                f"serve tap delivered {tap_stats['rows']}/{chunks_run * K} "
                "rows — an io_callback was dropped or the run was "
                "interrupted mid-chunk")

        toks = np.full((n_req, max_new), -1, np.int32)
        for rid in range(n_req):
            parts = [int(x) for x in L.emitted.get(rid, [])]
            row = L.outputs.get(rid)
            if row is not None:
                parts += [_tok_int(x) for x in row]
            failed = (rid in L.evictions or rid in L.timeouts
                      or rid in L.shed or rid in L.drained)
            if failed:
                if len(parts) > max_new:
                    raise RuntimeError(
                        f"request {rid} streamed {len(parts)} tokens past "
                        f"its {max_new} budget despite degradation")
                toks[rid, :len(parts)] = parts   # −1 from the failure on
            else:
                if len(parts) != max_new:
                    raise RuntimeError(
                        f"request {rid} streamed {len(parts)}/{max_new} "
                        "tokens")
                toks[rid] = parts
        ttft = np.array([L.admit_t[r] - arr[r] if r in L.admit_t else -1
                         for r in range(n_req)], np.int64)
        occ = (L.busy_steps / (L.chunks * K * S)) if L.chunks else 0.0
        if rec is not None:
            self.watch.observe()
            rec.count("requests", n_req)
            rec.count("serve_chunks", chunks_run)
            rec.count("serve_decode_steps", chunks_run * K)
            rec.count("serve_tap_rows", tap_stats["rows"])
            rec.gauge("occupancy_mean", float(occ), lane="server")
            if L.drain_t is not None and drain_ns is not None:
                rec.span_at("drain", "server", drain_ns, rec.now_ns(),
                            t=L.drain_t, cancelled=len(L.drained))
                rec.gauge("drain_final_occupancy", L.in_flight / S,
                          lane="server")
        return ServeResult(tokens=toks, schedule=trace.schedule(),
                           ttft_steps=ttft, occupancy=float(occ),
                           decode_steps=L.chunks * K, chunks=L.chunks,
                           tap_rows=tap_stats["rows"],
                           evictions=dict(L.evictions),
                           timeouts=dict(L.timeouts),
                           shed=dict(L.shed), drained=dict(L.drained),
                           attempts=trace.attempts,
                           resumed_from=resumed_from)
