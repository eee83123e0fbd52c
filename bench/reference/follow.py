"""Drive the references through what a cell's timed path produced.

* :func:`train_steps` follows the first steps of a training run: the
  same seed's weights, tokens and participation masks, the loss and its
  gradient in float32 over only the rows that take part, and the delayed
  Adam update.  It reports each step's loss, the norm of the gradient each
  update applies, and per weight tensor the norm of the change after the
  steps and of the first gradient.
* :func:`serve_gaps` runs the full forward over each sampled request's
  prompt and served tokens, and reads how far each served token's logit
  lies below the best one.

``precision="fp8"`` gives the control: the same arithmetic with every
matmul operand rounded to per-tensor scaled float8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import for_family
from .common import (F32, adam_init, delayed_adam, group_perms, init_weights,
                     make_einsum, round_masks, train_tokens, zipf_cdf)


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(F32))))) for p, x in flat}


def schedule_masks(mix: dict, seed: int, rounds: int) -> np.ndarray:
    pattern, _, opts = mix["timing"].partition(":")
    if pattern != "poisson":
        raise ValueError(f"no timing law for {mix['timing']!r}")
    slow = float(dict(kv.split("=") for kv in opts.split(",") if kv)
                 .get("slow", 5.0))
    return round_masks(mix["scheduler"], mix["n_workers"], rounds, seed,
                       slow)


def train_steps(cfg: dict, mix: dict, seed: int, steps: int, *,
                first: int = 0, precision: str = "f32",
                fault: str | None = None) -> dict:
    """Follow ``steps`` rounds of delayed Adam from the seed's weights, on
    the data and masks of plan rounds ``first, first + 1, ...``.

    ``fault="half"`` plants a fault in the reference itself: each step
    drops the second half of the participating rows and takes the mean
    over the rest."""
    fam = for_family(cfg["family"])
    ein = make_einsum(precision)
    B, S = mix["global_batch"], mix["seq_len"]
    n = mix["n_workers"]
    vocab = cfg["vocab_size"]
    masks = schedule_masks(mix, seed, first + steps)[first:]
    cdf = zipf_cdf(vocab)
    perms = group_perms(vocab, n, seed, mix.get("heterogeneity", 1.0))
    init = jax.jit(lambda k: init_weights(fam.declare(cfg), k))
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        params = init(key)
        opt = adam_init(params)

        @jax.jit
        def grad_block(p, toks, w):
            return jax.value_and_grad(
                lambda q: fam.loss_sums(cfg, ein, q, toks, w)[0])(p)

        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                      donate_argnums=0)
        scale = jax.jit(lambda g, d: jax.tree_util.tree_map(
            lambda x: x / d, g), donate_argnums=0)
        step_fn = jax.jit(lambda p, o, g: delayed_adam(
            p, o, g, lr=mix["stepsize"], clip_norm=mix["clip_norm"]),
            donate_argnums=(0, 1, 2))
        losses, gnorms, g0 = [], [], None
        for r in range(steps):
            toks = train_tokens(seed, first + r, cdf, perms, B, S)
            w = np.repeat(masks[r], B // n).astype(np.float32)
            rows = np.flatnonzero(w > 0)
            if fault == "half":
                rows = rows[: max(len(rows) // 2, 1)]
            denom = float(w[rows].sum()) * (S - 1) + 1e-6
            tot, grads = 0.0, None
            block = mix.get("reference_rows", 2)
            for i in range(0, len(rows), block):
                sel = rows[i:i + block]
                t, g = grad_block(params, toks[sel], jnp.asarray(w[sel]))
                tot += float(t)
                grads = g if grads is None else add(grads, g)
                del g
            grads = scale(grads, jnp.float32(denom))
            losses.append(tot / denom)
            if g0 is None:
                g0 = _leaf_norms(grads)
            params, opt, gn = step_fn(params, opt, grads)
            del grads
            gnorms.append(float(gn))
        del opt
        change = _leaf_norms(jax.tree_util.tree_map(jnp.subtract, params,
                                                    init(key)))
    return {"loss": np.asarray(losses), "gnorm": np.asarray(gnorms),
            "change": change, "grad0": g0, "masks": masks}


def serve_gaps(cfg: dict, seed: int, prompts: np.ndarray,
               served: np.ndarray, *, precision: str = "f32") -> np.ndarray:
    """(requests, new tokens) gaps: the best float32 logit minus the float32
    logit of the served token at each served position.  With ``precision``
    lower than f32, the token read is the one the lower precision puts
    first, at the same positions of the same prompts and tokens."""
    fam = for_family(cfg["family"])
    ref_ein, low_ein = make_einsum("f32"), make_einsum(precision)
    plen = prompts.shape[1]
    new = served.shape[1]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_weights(fam.declare(cfg), k))(
            jax.random.PRNGKey(seed))

        @jax.jit
        def gaps(p, seq, toks):
            ref = fam.logits(cfg, ref_ein, p, seq, plen - 1, plen - 1 + new)
            if precision == "f32":
                pick = toks
            else:
                low = fam.logits(cfg, low_ein, p, seq, plen - 1,
                                 plen - 1 + new)
                pick = jnp.argmax(low, axis=-1)
            got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            return jnp.max(ref, axis=-1) - got

        out = []
        for i in range(prompts.shape[0]):
            seq = np.concatenate([prompts[i], served[i, :-1]])[None]
            out.append(np.asarray(gaps(params, jnp.asarray(seq, jnp.int32),
                                       jnp.asarray(served[i:i + 1]))[0]))
    return np.stack(out)
