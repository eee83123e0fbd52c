"""Serving cells: continuous batching through ``SlotServer.serve``.

Set-up makes the weights from the seed in one jitted call, builds one
``SlotServer``, and warms it with two short ``serve`` calls of the cell's
own shapes: the first compiles the chunk, admission and prefill programs,
the second (the cell's arrival pattern, timed) measures the decode-step
rate that sizes the window's call.

The window lies inside one ``serve`` call: requests of ``prompt_len``
tokens drawn uniformly from the vocabulary arrive on the decode-step
clock, request ``i`` at step ``floor(i * arrival_gap_steps)`` (a gap of
``max_new / n_slots`` keeps the pool full); the window
opens at the first token of decode step ``ramp_steps`` (the pool is full
by then) and lasts ``--seconds``; admissions stop with ``drain_after``
once the window is surely past.  Every token reaches the harness through
``on_token``, which stamps it on the host clock; the tokens of one decode
step share the stamp of its first, so the window closes at the first step
that starts ``--seconds`` or more after it opened.

After the call the weights are freed, and the plain reference runs the
full forward over a sample of finished requests (prompt and served
tokens), drawn from the seed: the widest gap by which a served token's
logit lies below the reference's best is compared.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import threading
import time
from collections import defaultdict
from functools import partial

import numpy as np

from bench import harness

WINDOW = "bench_window"


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.api import ServeJob
    from repro.distributed import DEFAULT_RULES, SlotConfig, SlotServer
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params

    from bench import trace as T
    from bench.counts import for_family
    from bench.drivers.train import arch_overrides, check_widths
    from bench.reference import follow

    c, mix, seed = run.cell.config, run.cell.mix, run.seed
    S, K = mix["n_slots"], mix["steps_per_launch"]
    plen, new, gap = mix["prompt_len"], mix["max_new"], \
        mix["arrival_gap_steps"]
    ramp = mix["ramp_steps"]
    arch = ServeJob(arch=c["registry"],
                    reduced=bool(c.get("registry_reduced", False)),
                    arch_overrides=arch_overrides(c)).make_arch()
    check_widths(arch, c)
    server = SlotServer(arch, make_host_mesh(),
                        SlotConfig(n_slots=S, ctx_len=plen + new,
                                   temperature=mix["temperature"],
                                   seed=seed, steps_per_launch=K),
                        rules=DEFAULT_RULES)
    params = jax.jit(partial(init_params, arch),
                     out_shardings=server.param_shardings())(
        jax.random.PRNGKey(seed))
    vocab = c["vocab_size"]

    def arrivals(n):
        return np.floor(gap * np.arange(n)).astype(np.int64)

    serve = partial(server.serve, params, admission=mix["admission"])

    # warm-up: compile, then time the cell's own arrival pattern
    warm = np.random.default_rng([seed, 1]).integers(
        0, vocab, (mix["warmup_requests"], plen)).astype(np.int32)
    serve(warm[:2], mix["warmup_new"])
    t = time.perf_counter()
    wres = serve(warm, mix["warmup_new"], arrivals=arrivals(len(warm)))
    rate = wres.decode_steps / (time.perf_counter() - t)
    compiles = dict(server.compile_counts())

    steps = ramp + math.ceil(mix["window_margin"] * run.seconds * rate)
    steps = -(-steps // K) * K
    n_req = int(steps // gap) + 1
    prompts = np.random.default_rng([seed, 2]).integers(
        0, vocab, (n_req, plen)).astype(np.int32)

    rids, tsteps, times = [], [], []
    opened = threading.Event()
    clock = {"step": -1, "t": 0.0}

    def on_token(rid, tok, step):
        if step != clock["step"]:
            clock["step"], clock["t"] = step, time.perf_counter()
            if step >= ramp and not opened.is_set():
                opened.set()
        rids.append(rid)
        tsteps.append(step)
        times.append(clock["t"])

    traced = {}
    log_dir = run.root / ".bench_trace" / run.cell.name

    def tracer():
        if not opened.wait(timeout=600):
            return
        jax.profiler.start_trace(str(log_dir))
        with TraceAnnotation(WINDOW):
            traced["lo"] = time.perf_counter()
            time.sleep(mix["trace_seconds"])
            traced["hi"] = time.perf_counter()
        jax.profiler.stop_trace()

    th = None
    if run.trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        th = threading.Thread(target=tracer, daemon=True)
        th.start()
    wall_minus_perf = time.time() - time.perf_counter()
    with TraceAnnotation("serve"):
        res = serve(prompts, new, arrivals=arrivals(n_req),
                    on_token=on_token, drain_after=steps)
    red = None
    if th is not None:
        opened.set()
        th.join()
        red = T.reduce(T.find_xplane(str(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
    if server.compile_counts() != compiles:
        raise RuntimeError(f"compiled inside the window: {compiles} -> "
                           f"{server.compile_counts()}")
    device = harness.device_info(red)
    del serve, params
    gc.collect()

    rids, tsteps, times = (np.asarray(rids), np.asarray(tsteps),
                           np.asarray(times))
    first = np.flatnonzero(tsteps >= ramp)
    if not len(first):
        raise RuntimeError("the serve call ended before the ramp did")
    # the window holds whole decode steps: it closes at the first step that
    # starts at or after ``--seconds`` (a step's tokens share one stamp)
    w_lo = times[first[0]]
    later = times[times >= w_lo + run.seconds]
    if not len(later):
        short = w_lo + run.seconds - times[-1]
        raise RuntimeError(f"the serve call ended {short:.3f} s before the "
                           "window closed")
    w_hi = float(later.min())
    inw = (times >= w_lo) & (times < w_hi)
    # per-request order: k-th streamed token of a request, and its gap
    order = np.lexsort((tsteps, rids))
    k = np.zeros(len(rids), np.int64)
    gaps = np.full(len(rids), np.nan)
    r_sorted = rids[order]
    same = np.r_[False, r_sorted[1:] == r_sorted[:-1]]
    run_idx = np.arange(len(order))
    start = np.maximum.accumulate(np.where(~same, run_idx, 0))
    k[order] = run_idx - start
    t_sorted = times[order]
    gaps[order[1:]] = np.where(same[1:], t_sorted[1:] - t_sorted[:-1],
                               np.nan)
    itl = gaps[inw & ~np.isnan(gaps)]
    setup_s = w_lo + wall_minus_perf - run.t0

    fam = for_family(c["family"])
    record = {"peak": run.peak}
    if run.trace:
        tin = (times >= traced["lo"]) & (times < traced["hi"])
        pos = plen + k[tin]
        flops = float(sum(fam.decode_flops(c, int(p)) for p in pos))
        admitted = int(np.count_nonzero(k[tin] == 0))
        flops += admitted * fam.prefill_flops(c, plen)
        step_bytes = defaultdict(float)
        for s_, p in zip(tsteps[tin], pos):
            step_bytes[int(s_)] += (p + 1) * fam.kv_bytes_per_position(c)
        n_steps = len(step_bytes)
        record.update({
            "trace_flops": flops, "trace_steps": n_steps,
            "decode_bytes_per_step": (
                fam.decode_weight_bytes(c, S)
                + sum(step_bytes.values()) / max(n_steps, 1))})

    done = [r for r in range(n_req) if (res.tokens[r] >= 0).all()]
    in_window = set(rids[inw].tolist())
    pool = [r for r in done if r in in_window]
    rng = np.random.default_rng([seed, 3])
    sample = np.sort(rng.choice(pool, min(mix["sample_requests"],
                                          len(pool)), replace=False))
    t_ref = time.perf_counter()
    ref_gaps = follow.serve_gaps(c, seed, prompts[sample],
                                 res.tokens[sample])
    if run.control:
        low = follow.serve_gaps(c, seed, prompts[sample],
                                res.tokens[sample], precision="fp8")
        print(f"control logit_gap {float(np.max(low))!r} positions_moved "
              f"{int(np.count_nonzero(low > 0))} of {low.size}",
              file=sys.stderr)
    checks = {}
    readings = {"logit_gap": float(np.max(ref_gaps))}
    for name in run.cell.limits:
        harness.check(checks, name, readings[name], run.cell.limits)
    for name, v in readings.items():
        print(f"reading {name} {v!r}", file=sys.stderr)
    print(f"reading sample {sample.tolist()} served_tokens "
          f"{ref_gaps.size} reference_s {time.perf_counter() - t_ref:.3f} "
          f"decode_steps_per_s_warm {rate:.3f}", file=sys.stderr)
    stamps = np.unique(times[inw])
    print(f"reading window_s {w_hi - w_lo:.4f} steps {len(stamps)} "
          f"longest_step_s {np.max(np.diff(stamps)):.4f}", file=sys.stderr)
    failed = len(res.evictions) + len(res.timeouts) + len(res.shed)
    served = int(inw.sum()) / (w_hi - w_lo)
    return {"end_to_end": {"serve_tokens_per_s": served,
                           "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3,
                           "setup_s": setup_s},
            "record": record, "trace": red,
            "attempted": len(in_window), "failed": failed,
            "checks": checks,
            "correct": harness.all_within(checks) and bool(checks)
            and failed == 0 and len(sample) > 0,
            "device": device}
