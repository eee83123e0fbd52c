"""Training cells: the async trainer driven through the compiled executor.

Set-up builds one object the way ``TrainerBackend`` does (trainer, the
realised schedule lowered to a ``RunPlan``, one ``PlanExecutor``) and its
state from the seed in one jitted call.  It then drives that object
through the first launch of ``rounds_per_launch`` rounds with the
window's own call (``PlanExecutor.run_scan``, the same compiled chunk),
keeps what those steps produced for the check, and times one more launch
to size the window.  The window is one ``run_scan`` call of whole launches
with no host sync inside it, ending in ``block_until_ready``.

After the window the program's state is freed and the plain reference
follows the same first launch from the seed (weights, tokens, masks), and
the two are compared: the loss of the first two steps and the norm of the
gradients that the updates of rounds 1 and 2 apply (all four computed at
the initial weights), and per weight tensor the norm of the change over
the launch.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import time
from functools import partial

import numpy as np

from bench import harness

WINDOW = "bench_window"


def _spec(run, rounds):
    from repro.api import ExperimentSpec, TrainJob

    c, mix = run.cell.config, run.cell.mix
    job = TrainJob(arch=c["registry"],
                   reduced=bool(c.get("registry_reduced", False)),
                   arch_overrides=arch_overrides(c),
                   remat=mix["remat"],
                   global_batch=mix["global_batch"], seq_len=mix["seq_len"],
                   heterogeneity=mix["heterogeneity"],
                   delay_rounds=mix["delay_rounds"], opt=mix["opt"],
                   clip_norm=mix["clip_norm"],
                   update_impl=mix["update_impl"])
    spec = ExperimentSpec(scheduler=mix["scheduler"], timing=mix["timing"],
                          T=rounds, n_workers=mix["n_workers"],
                          stepsize=mix["stepsize"], seed=run.seed,
                          rounds_per_launch=mix["rounds_per_launch"],
                          metrics=mix["metrics"], objective=job)
    return spec, job


def arch_overrides(c: dict) -> tuple:
    """What the program is told to change from its registry entry so that
    it runs the configuration as the file states it."""
    return (("norm_eps", c["rms_norm_eps"]),)


def check_widths(arch, c: dict) -> None:
    """The registry's configuration must be the one the file states."""
    fam = c["family"]
    if fam == "dense":
        want = dict(d_model=c["hidden_size"], d_ff=c["intermediate_size"],
                    n_layers=c["num_hidden_layers"],
                    n_heads=c["num_attention_heads"],
                    n_kv_heads=c["num_key_value_heads"],
                    d_head=c["head_dim"], vocab=c["vocab_size"],
                    rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
                    tie_embeddings=c["tie_word_embeddings"])
    else:
        s = c["ssm_cfg"]
        want = dict(d_model=c["d_model"], n_layers=c["n_layer"],
                    vocab=c["vocab_size"], ssm_state=s["d_state"],
                    ssm_head_dim=s["headdim"], ssm_expand=s["expand"],
                    ssm_conv=s["d_conv"], ssm_chunk=s["chunk_size"],
                    norm_eps=c["rms_norm_eps"],
                    tie_embeddings=c["tie_embeddings"])
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise SystemExit(f"bench: the program's {arch.name} is {got}, the "
                         f"configuration file states {want}")


def _leaf_change_norms(tr, state, p0):
    import jax
    import jax.numpy as jnp

    def norms(st, p):
        d = jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            tr.params_of(st), p)
        return d

    out = jax.jit(norms)(state, p0)
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_leaves_with_path(out)}


#: losses compared: the first two steps, both run at the initial weights
#: (round 0's update applies an empty buffer).  The third runs after one
#: Adam step, which moves nearly every weight by the step size whatever the
#: size of its gradient, so the bfloat16 program and the float32 reference
#: part ways there by chance (PERF.md)
LOSS_STEPS = 2
#: applied gradients compared: those of rounds 1 and 2, both computed at
#: the initial weights (round 0's update applies an empty buffer)
GNORM_STEPS = slice(1, 3)


def compare(prog: dict, ref: dict, limits: dict):
    """The numbers compared, and every reading (compared or not)."""
    lr, lp = ref["loss"][:LOSS_STEPS], prog["loss"][:LOSS_STEPS]
    gr, gp = ref["gnorm"][GNORM_STEPS], prog["gnorm"][GNORM_STEPS]
    g0 = ref["grad0"]
    med_g = float(np.median(list(g0.values())))
    counted = [k for k, v in g0.items() if v >= 1e-3 * med_g]
    med_c = float(np.median([ref["change"][k] for k in counted]))
    gaps = {k: abs(prog["change"][k] - ref["change"][k])
            / max(ref["change"][k], med_c) for k in counted}
    worst = max(gaps, key=gaps.get)
    readings = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "gnorm_gap": float(np.max(np.abs(gp - gr) / np.abs(gr))),
        "change_gap": float(gaps[worst]),
        "change_gap_median": float(np.median(list(gaps.values()))),
    }
    checks = {}
    for name in limits:
        harness.check(checks, name, readings[name], limits)
    extra = {"worst_leaf": worst,
             "left_out": sorted(set(g0) - set(counted)),
             "leaf_gaps": gaps}
    return checks, readings, extra


def _raw(r: dict) -> dict:
    return {k: (np.asarray(v).tolist() if k in ("loss", "gnorm") else v)
            for k, v in r.items() if k != "masks"}


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.api import TrainerBackend
    from repro.models import init_params
    from repro.runtime import PlanExecutor, compile_plan

    from bench import trace as T
    from bench.counts import for_family
    from bench.reference import follow

    c, mix = run.cell.config, run.cell.mix
    K = mix["rounds_per_launch"]
    B, S, n = mix["global_batch"], mix["seq_len"], mix["n_workers"]
    max_launches = max(1, math.ceil(run.seconds * mix["max_launches_per_s"]))
    rounds = K * max_launches
    spec, job = _spec(run, rounds)
    be = TrainerBackend()
    tr, arch, n_groups = be._make_trainer(spec, job, spec.stepsize.gamma,
                                          adaptive=False)
    check_widths(arch, c)
    world = be.world_for(spec, n_groups)
    plan = compile_plan(world.schedule, job, rounds=rounds,
                        n_groups=n_groups, seed=run.seed,
                        availability=world.availability,
                        zipf_as=world.zipf_as,
                        grad_density=world.grad_density,
                        fault_gain=world.fault_gain)
    ex = PlanExecutor(tr, plan)
    key = jax.random.PRNGKey(run.seed)
    state = jax.jit(tr.init_state, out_shardings=tr.state_shardings())(key)

    # first launch: the window's call and feed, from a fresh state
    first = rounds - K
    call = partial(ex.run_scan, rounds_per_launch=K, metrics=mix["metrics"])
    res = call(state, start_round=first)
    prog = {"loss": np.asarray(res.metrics["loss"], np.float64),
            "gnorm": np.asarray(res.metrics["grad_norm"], np.float64)}
    p0 = jax.jit(partial(init_params, arch))(key)
    prog["change"] = _leaf_change_norms(tr, res.state, p0)
    del p0
    # one warm launch sizes the window
    t = time.perf_counter()
    res = call(res.state, start_round=first)
    launch_s = time.perf_counter() - t
    n_launch = min(max(1, round(run.seconds / launch_s)), max_launches)
    if run.trace:
        n_launch = min(n_launch, mix["trace_launches"])
    lo = rounds - K * n_launch
    compiles = dict(ex.compile_counts())

    log_dir = run.root / ".bench_trace" / run.cell.name
    if run.trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(log_dir))
    t_w0 = time.time()
    w0 = time.perf_counter()
    with TraceAnnotation(WINDOW):
        with TraceAnnotation("run_scan"):
            res = call(res.state, start_round=lo)
    window_s = time.perf_counter() - w0
    red = None
    if run.trace:
        jax.profiler.stop_trace()
        red = T.reduce(T.find_xplane(str(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
    setup_s = t_w0 - run.t0
    if ex.compile_counts() != compiles:
        raise RuntimeError(f"compiled inside the window: {compiles} -> "
                           f"{ex.compile_counts()}")
    wmasks = plan.masks[lo:rounds]
    trained = int(np.count_nonzero(wmasks)) * (B // n) * S
    computed = wmasks.shape[0] * B * S
    losses = np.asarray(res.metrics["loss"])
    device = harness.device_info(red)
    del state, res, call, ex, tr
    gc.collect()

    fam = for_family(c["family"])
    record = {"peak": run.peak, "rounds": int(wmasks.shape[0]),
              "trained_tokens": trained, "computed_tokens": computed,
              "flops_per_trained_token": fam.train_flops_per_token(c, S),
              "update_bytes": fam.update_bytes(c)}

    t_ref = time.perf_counter()
    ref = follow.train_steps(c, mix, run.seed, K, first=first)
    if not np.array_equal(ref["masks"], plan.masks[first:rounds]):
        raise RuntimeError("the reference's participation masks differ "
                           "from the program's plan")
    checks, readings, extra = compare(prog, ref, run.cell.limits)
    for k, v in readings.items():
        print(f"reading {k} {v!r}", file=sys.stderr)
    print(f"reading worst_leaf {extra['worst_leaf']} left_out "
          f"{extra['left_out']} reference_s "
          f"{time.perf_counter() - t_ref:.3f}", file=sys.stderr)
    if run.control:
        raw = {"prog": _raw(prog), "ref": _raw(ref)}
        for tag, kw in (("control", {"precision": "fp8"}),
                        ("fault_half", {"fault": "half"})):
            other = follow.train_steps(c, mix, run.seed, K, first=first,
                                       **kw)
            raw[tag] = _raw(other)
            _, got, ext = compare(other, ref, {})
            for k, v in got.items():
                print(f"{tag} {k} {v!r}", file=sys.stderr)
            print(f"{tag} worst_leaf {ext['worst_leaf']}", file=sys.stderr)
        print("raw " + json.dumps(raw), file=sys.stderr)
    return {"end_to_end": {"train_tokens_per_s": trained / window_s,
                           "setup_s": setup_s},
            "record": record, "trace": red,
            "attempted": int(wmasks.shape[0]),
            "failed": int(np.count_nonzero(~np.isfinite(losses))),
            "checks": checks,
            "correct": harness.all_within(checks) and bool(checks),
            "device": device}
