"""Logical-axis sharding rules with divisibility fallback.

Every tensor in the system (params, optimizer state, activations, caches,
batches) carries logical axis names (see ``models.specs.Spec``).  Rules map
logical names to mesh axes; a candidate that does not divide the dimension
is skipped rather than erroring (e.g. grok-1's 8 KV heads on a 16-way model
axis fall through to the next candidate).  At most one tensor dim gets each
mesh axis; priority order decides who wins — and is itself a perf lever
(§Perf iterates on it).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Rules:
    """model_priority: logical names that want the tensor-parallel axis, in
    decreasing priority.  batch_names: names sharded over the data axes."""

    model_priority: tuple = (
        "experts", "heads", "kv_heads", "ctx", "d_inner", "ssm_heads",
        "ff", "vocab", "embed",
    )
    batch_names: tuple = ("batch", "capacity")
    data_axes: tuple = ("pod", "data")      # outer-to-inner data parallelism
    model_axis: str = "model"
    # ZeRO/FSDP: additionally shard params + optimizer state over the data
    # axes on the first divisible *tensor* dim that is still replicated.
    # Deliberately NOT the "layers" dim: slicing a layers-sharded stack at a
    # dynamic index makes GSPMD hoist a whole-stack all-gather out of the
    # scan (f32-converted on top, on backends that upcast bf16 dots) —
    # sharding a tensor dim instead yields small per-layer gathers inside
    # the loop, which is the standard 2D FSDP×TP schedule.
    zero_names: tuple = ("embed", "ff", "heads", "kv_heads", "d_inner",
                         "vocab", "experts", "ssm_heads", "ctx")


DEFAULT_RULES = Rules()

# ---------------------------------------------------------------------------
# activation sharding constraints (scan carries lose their sharding without
# explicit with_sharding_constraint — 40 GB of replicated logits otherwise)
# ---------------------------------------------------------------------------
import contextvars

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_activation_sharding", default=None)


class activation_sharding:
    """Context manager enabling with_sharding_constraint inside model code.

    Model code calls :func:`shard_activation` with logical axes; outside this
    context (plain CPU tests) it is a no-op.
    """

    def __init__(self, mesh, rules=None):
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES

    def __enter__(self):
        self._tok = _ACT_CTX.set((self.mesh, self.rules))
        return self

    def __exit__(self, *exc):
        _ACT_CTX.reset(self._tok)
        return False


def shard_activation(x, axes):
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_pspec(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


SEQ_PARALLEL_RULES = Rules(
    model_priority=DEFAULT_RULES.model_priority + ("seq",))


def auto_rules(cfg, model_axis_size: int = 16) -> Rules:
    """Beyond-paper optimisation (§Perf): pick the sharding rules per arch.

    Architectures whose attention heads cannot shard across the model axis
    (qwen2's 14 heads, seamless' 16 MHA heads at kv=16, ...) replicate their
    attention compute model_axis-fold under the default rules; sequence
    parallelism removes that (measured 13× compute / 12.9× HBM on
    qwen2-0.5b × prefill_32k).  For archs with shardable heads (grok,
    qwen3, ...) seq-parallel k/v gathers cost more than the all-reduces they
    replace (measured +23% collectives on grok-1), so they keep the default.
    """
    heads_ok = cfg.n_heads and cfg.n_heads % model_axis_size == 0
    ssm_ok = cfg.ssm_state and cfg.ssm_heads % model_axis_size == 0
    if heads_ok or (cfg.family == "ssm" and ssm_ok):
        return DEFAULT_RULES
    return SEQ_PARALLEL_RULES


def data_shard_count() -> int:
    """Number of data-parallel shards in the active activation context
    (1 outside any context) — used by group-local MoE dispatch."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return 1
    mesh, rules = ctx
    n = 1
    for a in rules.data_axes:
        if a in mesh.axis_names:
            n *= _mesh_size(mesh, a)
    return n


def sharded_trace(fn, mesh, rules=None):
    """Wrap a step function so activation constraints apply while tracing."""
    def wrapped(*a, **k):
        with activation_sharding(mesh, rules):
            return fn(*a, **k)
    return wrapped


def _mesh_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def logical_pspec(axes, shape, mesh: Mesh, rules: Rules = DEFAULT_RULES) -> P:
    """Build a PartitionSpec for one tensor from its logical axes."""
    if axes is None:
        return P()
    assignment: list = [None] * len(axes)
    used: set = set()

    # 1) batch dims over the data axes (pod × data if both divide); each
    #    mesh axis is consumed at most once even if several dims are
    #    batch-named
    for i, ax in enumerate(axes):
        if ax in rules.batch_names:
            present = [a for a in rules.data_axes
                       if a in mesh.axis_names and a not in used]
            if not present:
                continue
            prod = math.prod(_mesh_size(mesh, a) for a in present)
            if shape[i] % prod == 0:
                assignment[i] = tuple(present) if len(present) > 1 else present[0]
                used.update(present)
            else:
                for a in reversed(present):       # try inner axis alone
                    if shape[i] % _mesh_size(mesh, a) == 0:
                        assignment[i] = a
                        used.add(a)
                        break

    # 2) one dim gets the model axis, by priority, if divisible
    msz = _mesh_size(mesh, rules.model_axis)
    if rules.model_axis in mesh.axis_names and msz > 1:
        for name in rules.model_priority:
            if rules.model_axis in used:
                break
            for i, ax in enumerate(axes):
                if ax == name and assignment[i] is None and shape[i] % msz == 0 \
                        and shape[i] >= msz:
                    assignment[i] = rules.model_axis
                    used.add(rules.model_axis)
                    break
    return P(*assignment)


def zero_pspec(axes, shape, mesh: Mesh, base: P,
               rules: Rules = DEFAULT_RULES) -> P:
    """Optimizer-state sharding: param spec + data-axis sharding on the first
    still-replicated dim named in ``zero_names`` (ZeRO-1 style)."""
    present = [a for a in rules.data_axes if a in mesh.axis_names]
    if not present:
        return base
    spec = list(base) + [None] * (len(shape) - len(base))
    used = {a for s in spec if s is not None
            for a in (s if isinstance(s, tuple) else (s,))}
    free = [a for a in present if a not in used]
    if not free:
        return base
    prod = math.prod(_mesh_size(mesh, a) for a in free)
    for name in rules.zero_names:
        for i, ax in enumerate(axes or ()):
            if ax == name and spec[i] is None and shape[i] % prod == 0 \
                    and shape[i] >= prod:
                spec[i] = tuple(free) if len(free) > 1 else free[0]
                return P(*spec)
    return base


def pool_axes(mesh: Mesh, rules: Rules = DEFAULT_RULES) -> tuple:
    """The mesh data axes a pooled state buffer shards over (the ZeRO
    domain), in rules order."""
    return tuple(a for a in rules.data_axes if a in mesh.axis_names)


def pool_shard_count(mesh: Mesh, rules: Rules = DEFAULT_RULES) -> int:
    """Leading dim of the pooled ``(n_shards, rows, 128)`` buffers: one
    shard per ZeRO shard (1 on data-parallel-free meshes)."""
    return int(np.prod([mesh.shape[a] for a in pool_axes(mesh, rules)],
                       dtype=int)) or 1


def pooled_pspec(mesh: Mesh, rules: Rules = DEFAULT_RULES) -> P:
    """PartitionSpec of a pooled ``(n_shards, rows, 128)`` state buffer:
    shards over the data axes (each device owns its ZeRO shard of EVERY
    leaf), rows and lanes unsharded.  Replicated over the model axis —
    pooling trades the per-leaf 2D model×data sharding for O(n_dtypes)
    kernel launches; see the README for when to pick which."""
    axes = pool_axes(mesh, rules)
    if not axes:
        return P(None, None, None)
    return P(axes if len(axes) > 1 else axes[0], None, None)


def tree_pspecs(spec_tree, mesh: Mesh, rules: Rules = DEFAULT_RULES,
                zero: bool = False):
    """Map a Spec tree → PartitionSpec tree."""
    from ..models.specs import Spec

    def one(s: Spec):
        base = logical_pspec(s.axes, s.shape, mesh, rules)
        if zero:
            base = zero_pspec(s.axes, s.shape, mesh, base, rules)
        return base

    return jax.tree_util.tree_map(one, spec_tree,
                                  is_leaf=lambda x: isinstance(x, Spec))


def tree_shardings(spec_tree, mesh: Mesh, rules: Rules = DEFAULT_RULES,
                   zero: bool = False):
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        tree_pspecs(spec_tree, mesh, rules, zero),
        is_leaf=lambda x: isinstance(x, P),
    )


def bytes_per_device(spec_tree, mesh: Mesh, rules: Rules = DEFAULT_RULES,
                     zero: bool = False) -> int:
    """Analytic per-device bytes of a Spec tree under the rules (used by the
    dry-run report alongside XLA's memory_analysis)."""
    from ..models.specs import Spec
    import jax.numpy as jnp

    total = 0
    for s in jax.tree_util.tree_leaves(spec_tree,
                                       is_leaf=lambda x: isinstance(x, Spec)):
        p = logical_pspec(s.axes, s.shape, mesh, rules)
        if zero:
            p = zero_pspec(s.axes, s.shape, mesh, p, rules)
        shards = 1
        for e in p:
            for a in (e if isinstance(e, tuple) else (e,)) if e else ():
                shards *= _mesh_size(mesh, a)
        total += int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize // shards
    return total
