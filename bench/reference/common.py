"""Shared pieces of the plain references: the seed laws of the weights,
the training tokens and the worker schedule, the delayed Adam step, and
the two matmul precisions.

Nothing here imports the program under test.  The laws restate, from the
seed alone, what the program is asked to compute on: the same seed gives
the same weights, tokens and participation masks, and the references then
do the arithmetic in float32 at ``highest`` matmul precision.
"""
from __future__ import annotations

import heapq
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: float8 formats of the control: e4m3 forward, e5m2 for gradients
FP8 = (jnp.float8_e4m3fn, 448.0)
FP8_GRAD = (jnp.float8_e5m2, 57344.0)


# ---------------------------------------------------------------- weights
def _fan_in(shape) -> int:
    if len(shape) == 1:
        return shape[0]
    if len(shape) == 2:
        return shape[0]
    return shape[0] * shape[1]


def _materialize(shape, init, dtype, key):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "mamba_A":
        a = jax.random.uniform(key, shape, F32, 1.0, 16.0)
        return jnp.log(a).astype(dtype)
    if init == "mamba_dt":
        dt0 = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                         math.log(1e-1)))
        return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)
    std = 1.0 / math.sqrt(max(_fan_in(shape), 1)) if init == "fan_in" \
        else 0.02
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def init_weights(decl: dict, key) -> dict:
    """Materialise a nested dict of ``(shape, init, dtype)`` declarations.
    Each leaf's key is the seed key folded with the CRC32 of its path, so
    a leaf's values depend only on (seed, path).  Values are rounded to
    their storage dtype and returned as float32."""
    def leaf(path, d):
        shape, init, dtype = d
        name = jax.tree_util.keystr(path)
        sub = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        return _materialize(shape, init, jnp.dtype(dtype), sub).astype(F32)

    return jax.tree_util.tree_map_with_path(
        leaf, decl, is_leaf=lambda x: isinstance(x, tuple))


# ----------------------------------------------------------------- tokens
def zipf_cdf(vocab: int, a: float = 1.2) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    pmf = ranks ** (-a)
    return np.cumsum(pmf / pmf.sum()).astype(np.float32)


def group_perms(vocab: int, n_groups: int, seed: int,
                heterogeneity: float = 1.0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_groups):
        p = np.arange(vocab)
        swap = int(heterogeneity * vocab)
        if swap > 1:
            idx = rng.choice(vocab, size=min(swap, vocab), replace=False)
            p[idx] = rng.permutation(p[idx])
        out.append(p)
    return np.stack(out).astype(np.int32)


def train_tokens(seed: int, rnd: int, cdf, perms, global_batch: int,
                 seq_len: int):
    """Round ``rnd``'s (global_batch, seq_len) tokens: Zipf ranks by inverse
    CDF, pushed through each worker group's vocabulary permutation; group
    g owns rows [g·B/n, (g+1)·B/n)."""
    n = perms.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), np.uint32(rnd))
    u = jax.random.uniform(jax.random.fold_in(key, 0),
                           (global_batch, seq_len))
    cdf = jnp.asarray(cdf)
    ranks = jnp.clip(jnp.searchsorted(cdf, u), 0,
                     cdf.shape[0] - 1).astype(jnp.int32)
    gidx = np.repeat(np.arange(n), global_batch // n)
    return jnp.asarray(perms)[gidx[:, None], ranks]


# --------------------------------------------------------------- schedule
def heterogeneous_speeds(n: int, slow: float, base: float = 1.0):
    return base * (1.0 + (slow - 1.0) * np.arange(n) / max(n - 1, 1))


def round_masks(scheduler: str, n: int, rounds: int, seed: int,
                slow: float) -> np.ndarray:
    """(rounds, n) participation masks of Algorithm 1 for the ``shuffled``
    (Alg. 6) and ``pure_waiting:b=B`` (Alg. 3) assignment rules under
    Poisson compute times: an event simulation of the cluster, where a
    worker whose gradient lands in round q adds 1 to mask[q, worker]."""
    name, _, opts = scheduler.partition(":")
    b = int(dict(kv.split("=") for kv in opts.split(",") if kv)
            .get("b", 1))
    if name not in ("shuffled", "pure_waiting"):
        raise ValueError(f"no schedule law for {scheduler!r}")
    srng = np.random.default_rng(seed)
    perm, r = srng.permutation(n), 0
    trng = np.random.default_rng(seed)
    speeds = heterogeneous_speeds(n, slow)
    queues = [[] for _ in range(n)]       # (job_id, assign_time)
    free_at = np.zeros(n)
    heap, jid = [], 0

    def assign(ws, at):
        nonlocal jid
        starts = []
        for w in ws:
            queues[w].append((jid, at))
            jid += 1
            if free_at[w] >= 0:
                j, a = queues[w].pop(0)
                starts.append((w, j, max(free_at[w], a)))
                free_at[w] = -1.0
        if starts:
            d = np.maximum(trng.poisson(speeds[[w for w, _, _ in starts]])
                           .astype(np.float64), 1.0)
            for (w, j, s), dd in zip(starts, d):
                heapq.heappush(heap, (s + float(dd), j, w))

    def advance():
        nonlocal perm, r
        w = int(perm[r])
        r += 1
        if r == n:
            r = 0
            perm = srng.permutation(n)
        return w

    assign(range(n), 0.0)
    masks = np.zeros((rounds, n), np.float32)
    finished = []
    for t in range(rounds * b):
        finish, _, w = heapq.heappop(heap)
        masks[t // b, w] += 1.0
        free_at[w] = finish
        if queues[w]:
            j, a = queues[w].pop(0)
            start = max(free_at[w], a)
            free_at[w] = -1.0
            dd = max(float(trng.poisson(speeds[w])), 1.0)
            heapq.heappush(heap, (start + dd, j, w))
        finished.append(w)
        if (t + 1) % b == 0:
            assign(finished if name == "pure_waiting" else [advance()],
                   finish)
            finished = []
    return masks


# ----------------------------------------------------------------- matmul
def _round_scaled(x, fmt):
    dtype, top = fmt
    x = x.astype(F32)
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(F32) / s


@jax.custom_vjp
def quantize_fp8(x):
    """Per-tensor scaled float8 rounding of a matmul operand, back in f32:
    e4m3 going forward; the gradient that flows back through it is
    rounded the same way in e5m2 (the usual float8 training recipe)."""
    return _round_scaled(x, FP8)


quantize_fp8.defvjp(lambda x: (_round_scaled(x, FP8), None),
                    lambda _, g: (_round_scaled(g, FP8_GRAD),))


def make_einsum(precision: str):
    """``einsum`` of the reference (``"f32"``: float32 operands at highest
    precision) or of its control (``"fp8"``: both operands rounded to
    per-tensor scaled float8 first, products summed in float32)."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a.astype(F32),
                                             b.astype(F32), precision=hi)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, quantize_fp8(a),
                                             quantize_fp8(b), precision=hi)
    raise ValueError(precision)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def weighted_xent_sums(logits, labels, w_rows):
    """(sum of row-weighted next-token NLL, sum of weights) over a block."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    return jnp.sum(nll * w_rows[:, None]), jnp.sum(
        jnp.broadcast_to(w_rows[:, None], nll.shape))


# ------------------------------------------------------------------- Adam
def delayed_adam(params, opt, grads, *, lr, beta1=0.9, beta2=0.95,
                 eps=1e-8, clip_norm=1.0):
    """One server step of delayed Adam: apply the gradient buffered in the
    previous round (clipped by its global norm), buffer ``grads``.  The
    first step has an empty buffer and moves nothing.  Returns (params,
    opt, norm of the applied gradient)."""
    gbuf = opt["gbuf"]
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(
        gbuf)))
    clip = jnp.minimum(1.0, clip_norm / (norm + 1e-12)) if clip_norm \
        else jnp.float32(1.0)
    count = opt["count"] + 1
    c = count.astype(F32)
    bc1, bc2 = 1.0 - beta1 ** c, 1.0 - beta2 ** c
    gate = jnp.where(opt["step"] == 0, 0.0, 1.0)

    def upd(p, g, m, v):
        g = clip * g
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        return p - lr * gate * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = jax.tree_util.tree_map(upd, params, gbuf, opt["m"], opt["v"])
    is_t = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree_util.tree_map(lambda t: t[i], out,
                                            is_leaf=is_t)
    return pick(0), {"m": pick(1), "v": pick(2), "gbuf": grads,
                     "count": count, "step": opt["step"] + 1}, norm


def adam_init(params):
    z = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params),
            "gbuf": jax.tree_util.tree_map(z, params),
            "count": jnp.zeros((), jnp.int32),
            "step": jnp.zeros((), jnp.int32)}
