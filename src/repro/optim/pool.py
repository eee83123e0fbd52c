"""Pooled optimizer state: the whole server update as ONE kernel per dtype.

The AsGrad server update (eq. 2) is a pure elementwise pass over the full
parameter/moment/buffer state, yet the per-leaf fused path launches one
``pallas_call`` per parameter leaf — dozens-to-hundreds of tiny kernels per
step for a transformer, each paying launch + HBM-stream setup cost.  This
module flattens the params/m/v/gbuf trees ONCE (at trainer init) into
per-dtype contiguous pool buffers so the entire delayed update — clip,
Adam/SGD(+momentum) step, bias corrections, weight decay, delay_scale and
the gbuf ← fresh-grads swap — executes as one ``pallas_call`` per dtype
pool, O(n_dtypes) launches instead of O(n_leaves).

Layout.  A pool is a ``(n_shards, rows, 128)`` buffer, read as ``n_shards``
flat shards of ``cols = rows · 128`` columns: leaf ``l`` (padded to
``n_shards · width_l`` elements and chunked row-major) owns the column band
``[col_l, col_l + width_l)`` of every shard, so shard ``r`` holds shard
``r`` of EVERY leaf.  Sharding the pool ``P(data_axes, None, None)``
therefore gives each ZeRO shard a contiguous, self-contained slice of the
whole state: the fused update runs under ``shard_map`` over the mesh's data
axes with zero XLA-inserted gathers, and leaves that were too small or
indivisible to ZeRO-shard individually are sharded anyway.  Widths round up
to whole 128-lane rows and ``cols`` to whole kernel tiles
(``BLOCK_ROWS × 128``), so the kernels view each local shard as
``(rows, 128)`` tiles without a copy, and the lane-minor shape costs no
layout padding on a TPU (a ``(1, cols)`` bf16 buffer would be stored twice
over).

Padding invariant.  :func:`pool_tree` zero-fills pad columns and every
kernel preserves zeros there (moments start at 0, weight decay multiplies a
0 parameter), so :func:`pooled_global_norm` is an exact global norm as a
single fused reduction per pool — no per-leaf Python-sum of reductions, no
masking.

This module is mesh-agnostic: callers pass the data-axis names explicitly
(``repro.distributed.sharding.pooled_pspec`` is the NamedSharding helper).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.async_update import BLOCK_ROWS, LANE
from .optimizers import OptConfig, clip_scale_from_norm

F32 = jnp.float32


def _dtype_key(dt) -> str:
    return str(jnp.dtype(dt))


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's view into its dtype pool."""

    index: int          # position in the tree's flatten order
    path: str           # keystr (debugging / error messages)
    shape: tuple
    dtype: str          # dtype key of the POOL group (the param dtype)
    col: int            # first column of every flat shard (a 128 multiple)
    width: int          # columns owned: ceil(size / n_shards), 128-rounded
    size: int


@dataclasses.dataclass(frozen=True)
class PoolLayout:
    """tree ↔ per-dtype ``(n_shards, rows, 128)`` pool buffers, built once.

    ``groups`` maps a dtype key ("bfloat16", "float32", ...) to the slots of
    every leaf with that dtype, in tree-flatten order; ``cols`` is each
    group's total column count per shard (``rows · 128``, a whole number of
    kernel tiles).  The same layout serves params, grads and
    the f32 moments (moments pool under the PARAM's group so the kernel
    reads aligned bands, see ``pool_tree(dtype=...)``)."""

    n_shards: int
    groups: dict        # dtype key → tuple[LeafSlot, ...]
    cols: dict          # dtype key → total columns
    treedef: Any
    n_leaves: int

    @property
    def n_pools(self) -> int:
        return len(self.groups)

    def pool_shape(self, dk: str) -> tuple:
        return (self.n_shards, self.cols[dk] // LANE, LANE)


def build_layout(tree, n_shards: int = 1) -> PoolLayout:
    """Build the pooled layout for ``tree`` (arrays, ShapeDtypeStructs, or
    anything with ``.shape``/``.dtype``), chunked for ``n_shards`` ZeRO
    shards."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
    groups: dict = {}
    cols: dict = {}
    for index, (path, leaf) in enumerate(leaves_p):
        dk = _dtype_key(leaf.dtype)
        size = int(np.prod(leaf.shape)) if len(leaf.shape) else 1
        width = _round_up(-(-size // n_shards), LANE)
        slot = LeafSlot(index=index, path=jax.tree_util.keystr(path),
                        shape=tuple(leaf.shape), dtype=dk,
                        col=cols.get(dk, 0), width=width, size=size)
        groups.setdefault(dk, []).append(slot)
        cols[dk] = slot.col + width
    cols = {dk: _round_up(c, BLOCK_ROWS * LANE) for dk, c in cols.items()}
    return PoolLayout(n_shards=n_shards,
                      groups={k: tuple(v) for k, v in groups.items()},
                      cols=cols, treedef=treedef, n_leaves=len(leaves_p))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _constrain(x, sharding):
    return x if sharding is None else jax.lax.with_sharding_constraint(
        x, sharding)


def pool_tree(layout: PoolLayout, tree, dtype=None, sharding=None) -> dict:
    """tree → {dtype key: (n_shards, rows, 128) pool}.

    ``dtype`` overrides the pool element type (f32 moments pooling under
    their param's group); ``sharding`` (a NamedSharding) is applied to every
    pool.  Pad columns are zero-filled."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    if len(leaves) != layout.n_leaves:
        raise ValueError(
            f"tree has {len(leaves)} leaves, layout expects {layout.n_leaves}")
    n = layout.n_shards
    pools = {}
    for dk, slots in layout.groups.items():
        blocks = []
        for s in slots:
            flat = jnp.ravel(leaves[s.index])
            if dtype is not None:
                flat = flat.astype(dtype)
            pad = n * s.width - s.size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            blocks.append(flat.reshape(n, s.width // LANE, LANE))
        tail = layout.cols[dk] - (slots[-1].col + slots[-1].width)
        if tail:
            blocks.append(jnp.zeros((n, tail // LANE, LANE),
                                    blocks[0].dtype))
        pools[dk] = _constrain(jnp.concatenate(blocks, axis=1)
                               if len(blocks) > 1 else blocks[0], sharding)
    return pools


def unpool_tree(layout: PoolLayout, pools: dict, shardings=None):
    """{dtype key: pool} → tree.  ``shardings`` (an optional matching tree of
    NamedShardings) re-constrains each leaf to its compute sharding — the
    hook XLA turns into the per-leaf FSDP-style gathers."""
    leaves: list = [None] * layout.n_leaves
    for dk, slots in layout.groups.items():
        pool = pools[dk]
        for s in slots:
            rows = pool[:, s.col // LANE:(s.col + s.width) // LANE]
            flat = rows.reshape(-1)
            leaves[s.index] = flat[:s.size].reshape(s.shape)
    tree = jax.tree_util.tree_unflatten(layout.treedef, leaves)
    if shardings is not None:
        tree = jax.tree_util.tree_map(_constrain, tree, shardings)
    return tree


def pool_zeros(layout: PoolLayout, dtype=None, sharding=None) -> dict:
    """Zero pools (moments / delayed buffer init)."""
    return {dk: _constrain(
        jnp.zeros(layout.pool_shape(dk),
                  jnp.dtype(dtype) if dtype is not None else jnp.dtype(dk)),
        sharding) for dk in layout.groups}


def init_pools(layout: PoolLayout, params, delayed: bool = True,
               sharding=None) -> dict:
    """Fresh pooled optimizer state from a params tree: per dtype group
    ``{"p", "m", "v"}`` (+ a zero ``"gbuf"`` when ``delayed``) — the state
    schema every pooled consumer (trainer, benches, tests) shares."""
    p_pools = pool_tree(layout, params, sharding=sharding)
    m_pools = pool_zeros(layout, "float32", sharding=sharding)
    v_pools = pool_zeros(layout, "float32", sharding=sharding)
    b_pools = pool_zeros(layout, sharding=sharding) if delayed else None
    pools = {}
    for dk in layout.groups:
        grp = {"p": p_pools[dk], "m": m_pools[dk], "v": v_pools[dk]}
        if b_pools is not None:
            grp["gbuf"] = b_pools[dk]
        pools[dk] = grp
    return pools


def pooled_global_norm(pools: dict) -> jax.Array:
    """Global L2 norm over pool buffers: one fused reduction per pool
    (exact, because pad columns hold zeros)."""
    return jnp.sqrt(sum(jnp.sum(p.astype(F32) ** 2) for p in pools.values()))


# ---------------------------------------------------------------------------
# the fused pooled apply
# ---------------------------------------------------------------------------
def _maybe_shard_map(fn, mesh, axes, n_pool_args, n_scalar_args, n_out):
    """Wrap ``fn(pools..., scalars...)`` in shard_map over ``axes`` so each
    device updates only its local ZeRO rows (no XLA-inserted gathers).
    ``mesh=None`` or no data axes → plain call."""
    if mesh is None or not axes:
        return fn
    from jax.sharding import PartitionSpec as P

    spec = P(axes if len(axes) > 1 else axes[0], None, None)
    in_specs = (spec,) * n_pool_args + (P(),) * n_scalar_args
    out_specs = (spec,) * n_out if n_out > 1 else spec
    # check_vma=False: pallas_call carries no replication rule
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _block_rows(n_elems: int, interpret: bool) -> int:
    """Tile height for a pooled kernel call.

    Compiled mode keeps the kernels' default VMEM-sized pipeline tiles
    (the layout pads every pool to a whole number of them).  Interpret
    mode emulates the grid SEQUENTIALLY with whole-array functional updates
    — cost O(grid_points · pool_size), quadratic for one big pool split
    into many tiles — so there the whole pool is ONE tile (grid=1, linear,
    and exactly what the launch-count story promises)."""
    if not interpret:
        return BLOCK_ROWS
    return max(1, -(-n_elems // LANE))


def _adam_group_fns(cfg: OptConfig, interpret: bool, delayed: bool):
    from ..kernels.async_update import (fused_adam_delayed_pallas,
                                        fused_adam_pallas)

    if delayed:
        def fn(p, m, v, gb, g, clip, count, scale):
            return fused_adam_delayed_pallas(
                p, m, v, gb, g, lr=cfg.lr * scale, beta1=cfg.beta1,
                beta2=cfg.beta2, eps=cfg.eps, count=count, clip_scale=clip,
                weight_decay=cfg.weight_decay, interpret=interpret,
                block_rows=_block_rows(p.size, interpret))
        return fn, 5, 4

    def fn(p, m, v, g, clip, count, scale):
        return fused_adam_pallas(
            p, m, v, g, lr=cfg.lr * scale, beta1=cfg.beta1, beta2=cfg.beta2,
            eps=cfg.eps, count=count, clip_scale=clip,
            weight_decay=cfg.weight_decay, interpret=interpret,
            block_rows=_block_rows(p.size, interpret))
    return fn, 4, 3


def _sgd_group_fns(cfg: OptConfig, interpret: bool, delayed: bool):
    from ..kernels.async_update import (async_update_pallas, sgd_step_pallas,
                                        sgd_momentum_delayed_pallas,
                                        sgd_momentum_step_pallas)

    if cfg.momentum:
        if delayed:
            def fn(p, m, gb, g, clip, count, scale):
                return sgd_momentum_delayed_pallas(
                    p, m, gb, g, lr=cfg.lr, momentum=cfg.momentum,
                    clip_scale=clip, delay_scale=scale, interpret=interpret,
                    block_rows=_block_rows(p.size, interpret))
            return fn, 4, 3

        def fn(p, m, g, clip, count, scale):
            return sgd_momentum_step_pallas(
                p, m, g, lr=cfg.lr, momentum=cfg.momentum, clip_scale=clip,
                delay_scale=scale, interpret=interpret,
                block_rows=_block_rows(p.size, interpret))
        return fn, 3, 2

    if delayed:
        def fn(p, gb, g, clip, count, scale):
            return async_update_pallas(
                p, gb, g, lr=cfg.lr, clip_scale=clip, delay_scale=scale,
                interpret=interpret,
                block_rows=_block_rows(p.size, interpret))
        return fn, 3, 2

    def fn(p, g, clip, count, scale):
        return sgd_step_pallas(
            p, g, lr=cfg.lr, clip_scale=clip, delay_scale=scale,
            interpret=interpret, block_rows=_block_rows(p.size, interpret))
    return fn, 2, 1


def _apply_groups(grad_pools, pools, count, cfg: OptConfig, lr_scale, *,
                  delayed: bool, mesh, axes, interpret):
    """Shared body of :func:`pooled_update` / :func:`pooled_delayed_apply`."""
    if interpret is None:   # auto: compiled on TPU, interpreter elsewhere
        interpret = jax.default_backend() != "tpu"
    source = ({dk: pools[dk]["gbuf"] for dk in pools} if delayed
              else grad_pools)
    gnorm = pooled_global_norm(source)
    clip = clip_scale_from_norm(gnorm, cfg.clip_norm)
    new_count = count + 1
    scale = jnp.asarray(lr_scale, F32)

    if cfg.name == "adam":
        fn, n_in, n_out = _adam_group_fns(cfg, interpret, delayed)
    elif cfg.name == "sgd":
        fn, n_in, n_out = _sgd_group_fns(cfg, interpret, delayed)
    else:
        raise ValueError(cfg.name)
    fn = _maybe_shard_map(fn, mesh, axes, n_in, 3, n_out)

    new_pools = {}
    for dk, bufs in pools.items():
        g = grad_pools[dk]
        if cfg.name == "adam":
            args = (bufs["p"], bufs["m"], bufs["v"]) \
                + ((bufs["gbuf"],) if delayed else ()) + (g,)
            out = fn(*args, clip, new_count, scale)
            new = {"p": out[0], "m": out[1], "v": out[2]}
            if delayed:
                new["gbuf"] = out[3]
        elif cfg.momentum:
            args = (bufs["p"], bufs["m"]) \
                + ((bufs["gbuf"],) if delayed else ()) + (g,)
            out = fn(*args, clip, new_count, scale)
            new = {"p": out[0], "m": out[1], "v": bufs["v"]}
            if delayed:
                new["gbuf"] = out[2]
        else:
            args = (bufs["p"],) + ((bufs["gbuf"],) if delayed else ()) + (g,)
            out = fn(*args, clip, new_count, scale)
            out = out if isinstance(out, tuple) else (out,)
            new = {"p": out[0], "m": bufs["m"], "v": bufs["v"]}
            if delayed:
                new["gbuf"] = out[1]
        new_pools[dk] = new
    return new_pools, new_count, gnorm


def pooled_update(grad_pools, pools, count, cfg: OptConfig, lr_scale=1.0, *,
                  mesh=None, axes=(), interpret=None):
    """Synchronous pooled server update (``delay_rounds == 0``):

        pools' ← step(pools; clip·grad_pools),  one kernel per dtype pool.

    ``pools`` is ``{dtype: {"p", "m", "v"}}``; returns
    ``(new_pools, new_count, gnorm)`` with ``gnorm`` the pre-clip norm of
    the applied gradient — the pooled analogue of the
    ``make_optimizer`` update contract.  ``interpret=None`` auto-selects:
    compiled Mosaic kernels on TPU, the Pallas interpreter elsewhere."""
    return _apply_groups(grad_pools, pools, count, cfg, lr_scale,
                         delayed=False, mesh=mesh, axes=tuple(axes),
                         interpret=interpret)


def pooled_delayed_apply(grad_pools, pools, count, cfg: OptConfig,
                         lr_scale=1.0, *, mesh=None, axes=(),
                         interpret=None):
    """The delayed server update (eq. 2) over pooled state, one
    ``pallas_call`` per dtype pool:

        p', m', v' ← step(p, m, v; clip·gbuf)   (apply the STALE gradient)
        gbuf'      ← grad_pools                 (buffer the fresh one)

    ``pools`` is ``{dtype: {"p", "m", "v", "gbuf"}}``.  With ``mesh`` and
    ``axes`` (the mesh's data-axis names) the kernels run under
    ``shard_map``: each device updates only its local ZeRO rows.  Returns
    ``(new_pools, new_count, gnorm)``; ``gnorm`` is the pre-clip norm of
    the APPLIED (stale) gradient.  ``interpret=None`` auto-selects:
    compiled Mosaic kernels on TPU, the Pallas interpreter elsewhere."""
    return _apply_groups(grad_pools, pools, count, cfg, lr_scale,
                         delayed=True, mesh=mesh, axes=tuple(axes),
                         interpret=interpret)
