"""AsGrad at pod scale: buffered-asynchronous training (DESIGN.md §3/§4).

Mapping of the paper onto a synchronous SPMD pod:

* the ``n`` workers are the data-parallel groups of the mesh (each group owns
  a heterogeneous data shard),
* the assignment rule (pure / random / shuffled / fedbuff) becomes a per-round
  0/1 *participation mask* over the groups, produced by the same
  ``repro.core`` schedulers that drive the exact simulator,
* staleness is the round delay: the gradient applied at round q was computed
  at round q−1's parameters, held in ONE delayed aggregated-gradient buffer
  (exactly Alg 3/5 semantics where every in-flight job shares the round
  boundary point α = ⌊t/b⌋·b) — O(1) extra memory instead of O(τ_C)
  parameter snapshots,
* the fused delayed-update (server step, eq. 2) is the Pallas
  ``async_update`` kernel's target on TPU; here it is the optimizer apply.

Rows computed: group g owns rows [g·B/n, (g+1)·B/n) of the round's batch.
Called with a ``groups`` row (the plan's ids of the round's participants,
padded with groups whose mask is 0), the step gathers those groups' rows
and runs forward and backward on them alone; the rows' weights are the
full batch's, gathered alike.  Without it, or where gathering would change
the loss or its sharding (:meth:`AsyncTrainer.step_rows`), every row is
computed and the mask zeroes the rest.

``delay_rounds = 0`` recovers synchronous SGD (the paper's baseline).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..faults.guards import GuardConfig
from ..models import model as M
from ..models.specs import Spec, abstract_tree, axes_tree
from ..optim import (OptConfig, adam_init, make_optimizer, make_delayed_apply,
                     global_norm, resolve_update_impl)
from ..optim.pool import (build_layout, init_pools, pool_tree, unpool_tree,
                          pooled_delayed_apply, pooled_update)
from .sharding import (Rules, DEFAULT_RULES, tree_pspecs, tree_shardings,
                       zero_pspec, logical_pspec, pool_axes, pool_shard_count,
                       pooled_pspec)


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    delay_rounds: int = 1          # 0 = synchronous baseline
    delay_adaptive: bool = False   # scale lr by 1/(delay+1) ([32]-style)
    aux_coeff: float = 0.01        # MoE load-balance coefficient
    microbatches: int = 1          # gradient accumulation (memory lever)
    #: None → take ``OptConfig.update_impl``; set to override per-trainer.
    #: ``"pallas"``/``"pallas_interpret"`` route the delayed-buffer apply
    #: through the fused kernels (one HBM pass per tile, gbuf swap included).
    update_impl: Optional[str] = None
    #: device-side guard rails (``repro.faults.GuardConfig``): non-finite
    #: rounds skip the apply mask-style (no host readback) and a per-worker
    #: health vector backs the effective stepsize off after bad receipts.
    #: None compiles the exact unguarded step (no extra state, no checks).
    guards: Optional[GuardConfig] = None


class AsyncTrainer:
    """Composable trainer: (arch config × scheduler) → pjit train_step."""

    #: class-level default so partially-constructed trainers (tests build
    #: bare instances for state_specs) read the tree layout
    pooled = False

    def __init__(self, cfg: ArchConfig, mesh: Mesh,
                 opt: OptConfig = OptConfig(),
                 async_cfg: AsyncConfig = AsyncConfig(),
                 rules: Rules = DEFAULT_RULES):
        self.cfg = cfg
        self.mesh = mesh
        if async_cfg.update_impl is not None:
            opt = dataclasses.replace(opt, update_impl=async_cfg.update_impl)
        self.opt = opt
        self.async_cfg = async_cfg
        self.rules = rules
        #: data-parallel shards of the mesh; ``n_groups`` starts there and
        #: callers may set more (virtual) groups
        self.data_shards = int(np.prod([mesh.shape[a]
                                        for a in rules.data_axes
                                        if a in mesh.axis_names])) or 1
        self.n_groups = self.data_shards
        self.update_impl = resolve_update_impl(opt.update_impl)
        #: pooled impls flatten the whole state into per-dtype pool buffers
        #: ONCE here (layout is static per arch × mesh); the update is then
        #: one kernel per dtype pool under shard_map, not one per leaf
        self.pooled = self.update_impl.startswith("pallas_pooled")
        if self.pooled:
            self._pool_interpret = self.update_impl.endswith("_interpret")
            self.pool_axes = pool_axes(mesh, rules)
            self.pool_layout = build_layout(
                abstract_tree(M.param_specs(cfg)),
                pool_shard_count(mesh, rules))
        else:
            self._init_opt, self._update = make_optimizer(opt)
            self._delayed_apply = make_delayed_apply(opt)

    # ------------------------------------------------------------------ specs
    def _pooled_state_specs(self):
        """Pooled state as Specs: per dtype group one (n_shards, rows, 128)
        pool each for p (param dtype), m/v (f32) and — when delayed —
        gbuf."""
        lay = self.pool_layout

        def pspec_(dk, dtype):
            return Spec(lay.pool_shape(dk), (None, None, None), "zeros",
                        dtype)

        pools = {}
        for dk in lay.groups:
            grp = {"p": pspec_(dk, dk), "m": pspec_(dk, "float32"),
                   "v": pspec_(dk, "float32")}
            if self.async_cfg.delay_rounds > 0:
                grp["gbuf"] = pspec_(dk, dk)
            pools[dk] = grp
        specs = {
            "pools": pools,
            "opt": {"count": Spec((), (), "zeros", "int32")},
            "step": Spec((), (), "zeros", "int32"),
        }
        if self.async_cfg.guards is not None:
            specs["guard"] = self._guard_specs()
        return specs

    def _guard_specs(self):
        return {"health": Spec((self.n_groups,), (None,), "zeros", "float32")}

    def state_specs(self):
        """State tree as Specs (drives both init and shardings)."""
        if self.pooled:
            return self._pooled_state_specs()
        pspecs = M.param_specs(self.cfg)

        def f32_like(s: Spec):
            return Spec(s.shape, s.axes, "zeros", "float32")

        def grad_like(s: Spec):
            return Spec(s.shape, s.axes, "zeros", s.dtype)

        specs = {
            "params": pspecs,
            "opt": {
                "m": jax.tree_util.tree_map(f32_like, pspecs,
                                            is_leaf=lambda x: isinstance(x, Spec)),
                "v": jax.tree_util.tree_map(f32_like, pspecs,
                                            is_leaf=lambda x: isinstance(x, Spec)),
                "count": Spec((), (), "zeros", "int32"),
            },
            "step": Spec((), (), "zeros", "int32"),
        }
        if self.async_cfg.delay_rounds > 0:
            specs["gbuf"] = jax.tree_util.tree_map(
                grad_like, pspecs, is_leaf=lambda x: isinstance(x, Spec))
        if self.async_cfg.guards is not None:
            specs["guard"] = self._guard_specs()
        return specs

    def state_shardings(self, fsdp_params: bool = True):
        """Params/gbuf are 2D-sharded (model × data, FSDP-style) by default:
        at 314B even bf16 params exceed HBM if only tensor-parallel.  XLA
        inserts the per-layer all-gathers; their cost shows up in §Roofline
        and is a §Perf lever.

        Pooled impls: every pool buffer carries the pooled pspec (rows over
        the data axes — each device owns its ZeRO shard of every leaf)."""
        specs = self.state_specs()
        if self.pooled:
            psh = NamedSharding(self.mesh, pooled_pspec(self.mesh, self.rules))
            scal = NamedSharding(self.mesh, P())
            out = {
                "pools": jax.tree_util.tree_map(
                    lambda s: psh, specs["pools"],
                    is_leaf=lambda x: isinstance(x, Spec)),
                "opt": {"count": scal},
                "step": scal,
            }
            if "guard" in specs:
                out["guard"] = {"health": scal}
            return out
        out = {
            "params": tree_shardings(specs["params"], self.mesh, self.rules,
                                     zero=fsdp_params),
            "opt": {
                "m": tree_shardings(specs["opt"]["m"], self.mesh, self.rules, zero=True),
                "v": tree_shardings(specs["opt"]["v"], self.mesh, self.rules, zero=True),
                "count": NamedSharding(self.mesh, P()),
            },
            "step": NamedSharding(self.mesh, P()),
        }
        if "gbuf" in specs:
            out["gbuf"] = tree_shardings(specs["gbuf"], self.mesh, self.rules,
                                         zero=fsdp_params)
        if "guard" in specs:
            out["guard"] = {"health": NamedSharding(self.mesh, P())}
        return out

    def abstract_state(self):
        return abstract_tree(self.state_specs())

    def init_state(self, key):
        params = M.init_params(self.cfg, key)
        if self.pooled:
            state = {
                "pools": init_pools(self.pool_layout, params,
                                    delayed=self.async_cfg.delay_rounds > 0),
                "opt": {"count": jnp.zeros((), jnp.int32)},
                "step": jnp.zeros((), jnp.int32),
            }
        else:
            state = {
                "params": params,
                "opt": adam_init(params),
                "step": jnp.zeros((), jnp.int32),
            }
            if self.async_cfg.delay_rounds > 0:
                state["gbuf"] = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params)
        if self.async_cfg.guards is not None:
            # every worker starts at full health (scale 1 = unguarded γ)
            state["guard"] = {
                "health": jnp.ones((self.n_groups,), jnp.float32)}
        return state

    def params_of(self, state):
        """Params tree view of a trainer state, whatever the layout
        (identity on tree states, unpool on pooled states) — for
        checkpoint/eval consumers that expect the tree."""
        if self.pooled:
            return unpool_tree(
                self.pool_layout,
                {dk: b["p"] for dk, b in state["pools"].items()})
        return state["params"]

    # ------------------------------------------------------------- train step
    def _grad_shardings(self):
        pspecs = M.param_specs(self.cfg)
        return tree_shardings(pspecs, self.mesh, self.rules, zero=True)

    def _example_weights(self, mask, batch_size: int):
        """mask (n_groups,) → per-example weights (B,) over the whole
        batch: group g owns the contiguous slice [g·B/n, (g+1)·B/n).  A
        step that computes only some groups' rows gathers these weights
        with the same row indices as the rows themselves."""
        per = batch_size // self.n_groups
        return jnp.repeat(mask, per, total_repeat_length=batch_size)

    def step_rows(self, batch_size: int, width: int) -> int:
        """Rows the step computes for a batch of ``batch_size`` rows when
        at most ``width`` groups take part in a round: those groups' rows,
        or the whole batch.  The whole batch where every group may take
        part, where the config has experts (the MoE aux loss covers every
        row, weighted or not) and where the gathered rows would not split
        evenly over the mesh's data shards."""
        rows = width * (batch_size // self.n_groups)
        if width >= self.n_groups or self.cfg.n_experts or \
                rows % self.data_shards:
            return batch_size
        return rows

    def train_step_fn(self):
        """The pjit train step.

        ``step(state, batch, mask, delay_scale=None, grad_density=None,
        fault_gain=None, groups=None)``:
        ``delay_scale`` is the optional per-round stepsize scale
        (γ_q = γ·delay_scale_q) fed from the realised schedule's delay
        metadata (:func:`repro.core.round_delay_scales`); omitted, the
        static ``delay_adaptive`` 1/(1+delay_rounds) rule applies.
        ``grad_density`` is the optional per-round keep-density in (0, 1]
        (the ``repro.scenarios`` sparsified-gradients staleness remedy):
        each gradient leaf keeps only its largest-magnitude ``density``
        fraction (per-leaf quantile threshold — the density is traced, so
        k is dynamic and ``top_k`` is unavailable); 1.0 is an exact no-op.
        ``groups`` is the optional ``(G,)`` int32 row of the plan's
        participating group ids: where :meth:`step_rows` allows it, the
        forward and backward run on those groups' rows of ``batch`` only
        (module docstring); the masks, metrics and update are unchanged.
        Sparsification happens BEFORE the ZeRO reshard / pooling, i.e. on
        the gradient the server update consumes.  With
        ``delay_rounds > 0`` the whole server update (eq. 2) — consume the
        stale ``gbuf``, step params/moments, buffer the fresh grads — is one
        :func:`repro.optim.make_delayed_apply` call, which the pallas
        ``update_impl``s execute as one fused HBM pass per tile.

        Pooled impls keep the state in per-dtype pool buffers: params are
        viewed back into the tree for the forward/backward pass (the
        constraint to the per-leaf compute shardings is where XLA inserts
        the FSDP-style gathers), the fresh grads are pooled once, and the
        whole server update runs as one kernel per dtype pool under
        shard_map over the mesh's data axes."""
        cfg, acfg = self.cfg, self.async_cfg
        if self.pooled:
            param_sh = tree_shardings(M.param_specs(cfg), self.mesh,
                                      self.rules, zero=True)
            pool_sh = NamedSharding(self.mesh,
                                    pooled_pspec(self.mesh, self.rules))

        def step(state, batch, mask, delay_scale=None, grad_density=None,
                 fault_gain=None, groups=None):
            if self.pooled:
                params = unpool_tree(
                    self.pool_layout,
                    {dk: b["p"] for dk, b in state["pools"].items()},
                    shardings=param_sh)
            else:
                params = state["params"]
            bsz = batch["tokens"].shape[0]
            w = self._example_weights(mask.astype(jnp.float32), bsz)
            if groups is not None and \
                    self.step_rows(bsz, groups.shape[0]) < bsz:
                per = bsz // self.n_groups
                rows = (groups[:, None] * per
                        + jnp.arange(per, dtype=groups.dtype)).reshape(-1)
                batch = jax.tree_util.tree_map(lambda x: x[rows], batch)
                w = w[rows]
                bsz = rows.shape[0]
            if fault_gain is not None:
                # fault channel: multiplicative gain on the round's RECEIVED
                # contribution (huge = inflated corrupted receipt, NaN =
                # poisoned).  Folding the gain into the example weights
                # would cancel in the CE's weight normalisation, so the
                # participation-weighted mean gain scales the post-
                # normalisation loss/grads instead (below).  Gate on the
                # mask so a non-participant's gain (even NaN) cannot leak.
                part = mask.astype(jnp.float32)
                gain = jnp.where(part > 0,
                                 jnp.asarray(fault_gain, jnp.float32), 1.0)
                fault_c = jnp.where(
                    jnp.sum(part) > 0,
                    jnp.sum(part * gain) / jnp.maximum(jnp.sum(part), 1e-6),
                    1.0)
            else:
                fault_c = None

            def lfn(p, b, wslice):
                return M.loss_fn(cfg, p, b, example_weights=wslice,
                                 aux_coeff=acfg.aux_coeff)

            k = acfg.microbatches
            if k > 1 and bsz % k == 0:
                # gradient accumulation: scan over k microbatches — peak
                # activation memory drops ~k×, grads accumulated in f32
                def split(x):
                    return x.reshape((k, bsz // k) + x.shape[1:])

                mb = jax.tree_util.tree_map(split, batch)
                wb = split(w)

                def acc_step(carry, inp):
                    g_acc, l_acc, a_acc = carry
                    b_i, w_i = inp
                    (l, parts_i), g = jax.value_and_grad(
                        lfn, has_aux=True)(params, b_i, w_i)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, x: a + x.astype(jnp.float32) / k, g_acc, g)
                    return (g_acc, l_acc + l / k, a_acc + parts_i["aux"] / k), None

                gsh = self._grad_shardings()
                g0 = jax.tree_util.tree_map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), s),
                    params, gsh)
                (g32, loss, aux), _ = jax.lax.scan(
                    acc_step, (g0, 0.0, 0.0), (mb, wb))
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), g32, params)
                parts = {"ce": loss, "aux": aux}
            else:
                (loss, parts), grads = jax.value_and_grad(
                    lfn, has_aux=True)(params, batch, w)
            if fault_c is not None:
                # the corrupted/poisoned receipt: everything the server
                # "receives" this round is scaled — grads (what the update
                # consumes) and the reported loss components alike, so the
                # breaker and the guard see exactly what the step applies
                loss = loss * fault_c
                parts = {k: v * fault_c for k, v in parts.items()}
                grads = jax.tree_util.tree_map(
                    lambda g: g * fault_c.astype(g.dtype), grads)
            if grad_density is not None:
                # magnitude top-k per leaf at traced density: threshold at
                # the (1 − density)-quantile of |g| and zero everything
                # below it.  density = 1 ⇒ threshold = min|g| ⇒ keep-all
                # (g·1.0 is bitwise identity), so a neutral channel row
                # changes nothing.
                dens = jnp.clip(jnp.asarray(grad_density, jnp.float32),
                                0.0, 1.0)

                def sparsify(g):
                    a = jnp.abs(g.astype(jnp.float32)).reshape(-1)
                    thr = jnp.quantile(a, 1.0 - dens)
                    keep = jnp.abs(g.astype(jnp.float32)) >= thr
                    return g * keep.astype(g.dtype)

                grads = jax.tree_util.tree_map(sparsify, grads)
            if acfg.guards is not None:
                # guard rails, all mask-style (no host readback): a round
                # whose loss or raw grad norm is non-finite is SKIPPED via
                # the old-vs-new state select below, which keeps every
                # leaf — params, moments AND the delay buffer — at its
                # previous value, so nothing non-finite survives the round
                # (zeroing the grads here too would just spend an extra
                # pass on values the select is about to discard).  The
                # norm check must run on the FRESH grads, pre-apply: the
                # delayed path's own gnorm is the stale buffer's, and a
                # poisoned receipt has to be caught before it is buffered.
                # Health: participants of a bad round (non-finite, or a
                # finite norm spike past spike_norm) back off; clean
                # participants recover toward 1.
                gd = acfg.guards
                raw_norm = global_norm(grads)
                finite = jnp.isfinite(loss) & jnp.isfinite(raw_norm)
                bad = ~finite
                if gd.spike_norm is not None:
                    bad = bad | (raw_norm > gd.spike_norm)
                part = mask.astype(jnp.float32)
                h = state["guard"]["health"]
                gscale = jnp.sum(h * part) / jnp.maximum(part.sum(), 1.0)
                h_next = jnp.clip(
                    jnp.where(part > 0,
                              jnp.where(bad, h * gd.backoff,
                                        jnp.minimum(h * gd.recover, 1.0)),
                              h),
                    gd.min_scale, 1.0)
                skipped = 1.0 - finite.astype(jnp.float32)
            else:
                finite = None
                gscale = jnp.float32(1.0)
                skipped = jnp.float32(0.0)
            if delay_scale is not None:
                lr_scale = jnp.asarray(delay_scale, jnp.float32)
            elif acfg.delay_adaptive and acfg.delay_rounds > 0:
                lr_scale = 1.0 / (1.0 + acfg.delay_rounds)
            else:
                lr_scale = 1.0

            # skip the very first round (empty buffer) via a smooth gate
            gate = jnp.where(
                (state["step"] == 0) & (acfg.delay_rounds > 0), 0.0, 1.0)
            if acfg.guards is not None:
                # participation-weighted mean health scales this round's γ
                gate = gate * gscale

            def _apply_update(_):
                # ZeRO: reshard grads to the optimizer-state sharding before
                # the update (reduce-scatter) — clip/Adam f32 temps shrink by
                # the data-axis factor, which is what makes 314B fit.  The
                # pooled path reshards straight into pool layout instead: one
                # concat pass, constrained so each device materialises only
                # its rows
                if self.pooled:
                    grad_pools = pool_tree(self.pool_layout, grads,
                                           sharding=pool_sh)
                    apply = pooled_delayed_apply if acfg.delay_rounds > 0 \
                        else pooled_update
                    new_pools, new_count, gnorm = apply(
                        grad_pools, state["pools"], state["opt"]["count"],
                        self.opt, lr_scale=lr_scale * gate, mesh=self.mesh,
                        axes=self.pool_axes, interpret=self._pool_interpret)
                    return {
                        "pools": new_pools,
                        "opt": {"count": new_count},
                        "step": state["step"] + 1,
                    }, gnorm
                g = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, grads,
                    self._grad_shardings())
                if acfg.delay_rounds > 0:
                    # one fused apply: consume the stale buffer, write the
                    # fresh grads back (reference impl composes the same
                    # semantics)
                    new_params, new_gbuf, new_opt, gnorm = \
                        self._delayed_apply(
                            g, state["gbuf"], state["opt"], params,
                            self.opt, lr_scale=lr_scale * gate)
                    return {
                        "params": new_params,
                        "opt": new_opt,
                        "step": state["step"] + 1,
                        "gbuf": new_gbuf,
                    }, gnorm
                new_params, new_opt, gnorm = self._update(
                    g, state["opt"], params, self.opt,
                    lr_scale=lr_scale * gate)
                return {
                    "params": new_params,
                    "opt": new_opt,
                    "step": state["step"] + 1,
                }, gnorm

            if acfg.guards is None:
                new_state, gnorm = _apply_update(None)
            else:
                # skipped round: every leaf keeps its previous value — the
                # cond's false branch passes the old state straight through,
                # so under the round scan a clean round pays one branch
                # dispatch (not an old-vs-new select pass over every leaf)
                # and a poisoned round skips the apply entirely.  Under the
                # grid lane's vmap the cond lowers back to a select — both
                # branches run, exactly the old cost.  The step counter
                # always advances, and the health vector is how the skip is
                # charged; a skipped round reports grad_norm 0 (no gradient
                # was applied).
                def _skip(_):
                    old = {k: v for k, v in state.items() if k != "guard"}
                    old["step"] = state["step"] + 1
                    return old, jnp.float32(0.0)

                new_state, gnorm = jax.lax.cond(
                    finite, _apply_update, _skip, None)
                new_state["guard"] = {"health": h_next}
            metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"],
                       "grad_norm": gnorm,
                       "participation": jnp.mean(mask.astype(jnp.float32)),
                       "skipped": skipped, "gscale": gscale}
            return new_state, metrics

        from .sharding import sharded_trace
        return sharded_trace(step, self.mesh, self.rules)

    def jit_train_step(self, batch_shape, donate: bool = True,
                       with_delay_scale: bool = False,
                       with_grad_density: bool = False,
                       with_fault_gain: bool = False,
                       with_groups: bool = False):
        """pjit-compiled train step for a (batch, seq) shape.

        The compiled signature is exactly positional: ``step(state, batch,
        mask)`` plus one replicated traced extra per enabled channel, in
        the fixed order ``delay_scale`` (per-round stepsize scale), then
        ``grad_density`` (per-round gradient keep-density), then
        ``fault_gain`` (per-worker loss-weight gains), then ``groups``
        (the round's participating group ids) — each present only
        when its ``with_*`` flag is on, the remaining channels pinned to
        None inside (so e.g. density-without-scale leaves the trainer's
        static stepsize rule in charge)."""
        bspecs = M.batch_specs(self.cfg, *batch_shape)
        batch_sh = tree_shardings(bspecs, self.mesh, self.rules)
        state_sh = self.state_shardings()
        repl = NamedSharding(self.mesh, P())
        step = self.train_step_fn()
        names = [n for n, on in (("delay_scale", with_delay_scale),
                                 ("grad_density", with_grad_density),
                                 ("fault_gain", with_fault_gain),
                                 ("groups", with_groups)) if on]

        def fn_(state, batch, mask, *extras):
            return step(state, batch, mask, **dict(zip(names, extras)))

        in_sh = (state_sh, batch_sh, repl) + (repl,) * len(names)
        fn = jax.jit(
            fn_,
            in_shardings=in_sh,
            out_shardings=(state_sh, None),
            donate_argnums=(0,) if donate else (),
        )
        return fn

    # ------------------------------------------------------------- input specs
    def batch_struct(self, batch: int, seq: int):
        specs = M.batch_specs(self.cfg, batch, seq)
        sh = tree_shardings(specs, self.mesh, self.rules)
        ab = abstract_tree(specs)
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            ab, sh)
