"""Smoke run of the main path on a TPU: trainer and slot server at width.

Drives the async trainer and the continuous-batching server through
``repro.api`` at qwen2-0.5b's published width and depth (24 layers,
d_model 896, 14/2 heads, d_ff 4864, vocab 151936), with random weights
drawn from a seed, and checks what comes out:

* train, reference update: a few async rounds of the shuffled scheduler
  over 4 worker groups with a one-round delayed buffer; losses finite and
  falling.  The batch is the first of ``TRAIN_SHAPES`` whose compiled step
  fits the device with headroom (``memory_analysis()``).
* train, ``update_impl="pallas_pooled"``: the same spec and seed through the
  compiled Mosaic update kernels (``tpu_custom_call`` in the step's HLO);
  per-round losses match the reference phase.
* serve: 8 requests through the 4-slot server; every request completes and
  the greedy tokens are compared with a plain loop of the model's prefill
  and decode step.

``--chips 4`` runs only the pooled train phase on a (data=2, model=2) mesh
of four chips, and the same spec on one of them as the comparison.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips of one host

It needs a TPU and never falls back to the CPU.  Times it prints are
informational, from one run: it is not a benchmark.  The last line of its
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
SEED = 0
ROUNDS = 8
N_WORKERS = 4
LR = 1e-3
#: (global_batch, seq_len) candidates, largest first
TRAIN_SHAPES = ((8, 1024), (8, 512), (4, 512), (4, 256))
REDUCED_SHAPES = ((8, 64),)
#: a compiled step may take this share of the device's memory
HEADROOM = 0.8
#: trainer-curve tolerance of tests/test_optim_fused.py
LOSS_RTOL = 5e-3
#: bf16 tolerance of tests/test_kernels.py
LOGIT_TOL = 3e-2
#: serve traffic: 8 requests through 4 slots
N_SLOTS, N_REQUESTS, PROMPT_LEN, GEN = 4, 8, 64, 32


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _device():
    import jax
    return jax.devices()[0]


def _peak_bytes():
    """Process peak of device memory so far (None where not reported)."""
    stats = _device().memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _bytes_limit() -> float:
    stats = _device().memory_stats()
    return float("inf") if not stats else float(stats["bytes_limit"])


def _program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def train_spec(update_impl: str, shape, *, reduced: bool = False):
    from repro.api import ExperimentSpec, TrainJob

    global_batch, seq_len = shape
    return ExperimentSpec(
        scheduler="shuffled", timing="poisson:slow=6", T=ROUNDS,
        n_workers=N_WORKERS, stepsize=LR, seed=SEED,
        rounds_per_launch=1,
        objective=TrainJob(arch=ARCH, reduced=reduced, remat="full",
                           global_batch=global_batch, seq_len=seq_len,
                           delay_rounds=1, update_impl=update_impl))


def size_train(update_impl: str, *, reduced: bool = False, mesh=None):
    """The first candidate shape whose compiled step fits with headroom."""
    from repro.api import TrainerBackend

    limit = _bytes_limit()
    for shape in (REDUCED_SHAPES if reduced else TRAIN_SHAPES):
        t0 = time.perf_counter()
        compiled = TrainerBackend(mesh=mesh).compile_step(
            train_spec(update_impl, shape, reduced=reduced))
        need = _program_bytes(compiled)
        log("size", impl=update_impl, shape=f"{shape[0]}x{shape[1]}",
            compile_s=round(time.perf_counter() - t0, 3),
            program_bytes=need, bytes_limit=limit)
        if need <= HEADROOM * limit:
            return shape
    raise RuntimeError(f"no train shape fits {HEADROOM} of {limit} bytes")


def train_phase(update_impl: str, shape, *, reduced: bool = False,
                mesh=None, label: str = "train") -> dict:
    """Compile the step, run ROUNDS async rounds, check the losses.  On a
    TPU the Pallas update must run compiled (interpreted elsewhere)."""
    import jax
    from repro.api import TrainerBackend
    from repro.models import n_params

    spec = train_spec(update_impl, shape, reduced=reduced)
    done = []

    def on_step(i, state, metrics):
        jax.block_until_ready(state)
        done.append(time.perf_counter())

    backend = TrainerBackend(mesh=mesh, on_step=on_step)
    t0 = time.perf_counter()
    compiled = backend.compile_step(spec)
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    custom_calls = hlo.count("tpu_custom_call")
    collectives = {op: hlo.count(f" {op}(") for op in
                   ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")}

    t0 = time.perf_counter()
    res = backend.run(spec)
    losses = np.asarray(res.losses, np.float64)
    impl = res.extra["update_impl"]
    cfg = spec.objective.make_arch()
    log(label, impl=impl, arch=cfg.name, params=n_params(cfg),
        layers=cfg.n_layers, d_model=cfg.d_model,
        heads=f"{cfg.n_heads}/{cfg.n_kv_heads}", d_ff=cfg.d_ff,
        vocab=cfg.vocab, batch=f"{shape[0]}x{shape[1]}",
        mesh=dict(res.x["step"].sharding.mesh.shape),
        step_compile_s=round(compile_s, 3),
        step_program_bytes=_program_bytes(compiled),
        tpu_custom_calls=custom_calls, collectives=collectives)
    log(label, first_round_s=round(done[0] - t0, 3),
        warm_round_s_informational=round(
            float(np.median(np.diff(done[1:]))), 4),
        peak_bytes_in_use=_peak_bytes(),
        losses=[round(x, 5) for x in losses.tolist()])

    if losses.shape != (ROUNDS,) or not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: losses not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and impl.endswith("_interpret"):
        raise AssertionError(f"{label}: resolved to the interpreter: {impl}")
    if on_tpu and update_impl != "reference" and not custom_calls:
        raise AssertionError(f"{label}: no tpu_custom_call in the step HLO")
    out = {"losses": losses, "impl": impl, "collectives": collectives}
    if mesh is not None:
        out["pool_bytes"] = _pool_bytes(res.x)
    return out


def _pool_bytes(state) -> tuple:
    """(bytes on each device, bytes of the whole pools) of a pooled state."""
    import jax

    per_device: Counter = Counter()
    total = 0
    for leaf in jax.tree_util.tree_leaves(state["pools"]):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    return dict(per_device), total


def check_losses_agree(label: str, want, got) -> None:
    gap = float(np.max(np.abs(got - want) / np.abs(want)))
    log(label, max_rel_loss_gap=gap, rtol=LOSS_RTOL)
    if not np.allclose(got, want, rtol=LOSS_RTOL, atol=0.0):
        raise AssertionError(f"{label}: losses differ: {want} vs {got}")


def plain_loop_tokens(cfg, params, prompts, T: int, ctx: int) -> np.ndarray:
    """Greedy tokens from ``M.prefill`` and ``M.decode_step`` alone: each
    prompt prefilled alone (batch 1, as the slot server admits it), the
    rows stacked into one cache, then T − 1 steps over every row at once."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    @jax.jit
    def pf(params, tokens):
        logits, cache = M.prefill(cfg, params, {"tokens": tokens},
                                  ctx_len=ctx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    step = jax.jit(lambda params, c, t, p: M.decode_step(cfg, params, c, t,
                                                         p, ctx))
    rows = [pf(params, jnp.asarray(p[None])) for p in prompts]
    tok = jnp.concatenate([t for t, _ in rows])
    cache = jax.tree_util.tree_map_with_path(
        lambda path, *a: jnp.concatenate(
            a, axis=0 if path[0].key == "positions" else 1),
        *[c for _, c in rows])
    pos = jnp.full(tok.shape, prompts.shape[1], jnp.int32)
    out = [np.asarray(tok)]
    for _ in range(T - 1):
        logits, cache = step(params, cache, tok, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
        pos = pos + 1
    return np.stack(out, axis=1)


def serve_phase(*, reduced: bool = False, mesh=None) -> dict:
    """8 requests through the 4-slot server, against the plain loop."""
    import jax
    from repro.api import ExperimentSpec, ServeBackend, ServeJob
    from repro.models import init_params

    slot_spec = ExperimentSpec(objective=ServeJob(
        arch=ARCH, reduced=reduced, prompt_len=PROMPT_LEN, batch=N_REQUESTS,
        n_slots=N_SLOTS, n_requests=N_REQUESTS, steps_per_launch=8),
        T=GEN, seed=SEED)
    runs = {}
    for name in ("slot", "slot_warm"):
        res = ServeBackend(mesh=mesh).run(slot_spec)
        runs[name] = res
        log("serve", run=name, seconds=round(res.seconds, 3),
            decode_s=round(res.extra["decode_seconds"], 3),
            tokens=list(res.x.shape), peak_bytes_in_use=_peak_bytes())
    slot, warm = runs["slot"], runs["slot_warm"]
    log("serve", n_slots=N_SLOTS, n_requests=N_REQUESTS,
        prompt_len=PROMPT_LEN, gen=GEN,
        cold_minus_warm_s=round(slot.seconds - warm.seconds, 3),
        warm_decode_step_s_informational=round(
            warm.extra["decode_seconds"] / warm.extra["decode_steps"], 5),
        decode_steps=warm.extra["decode_steps"],
        occupancy=round(float(slot.extra["occupancy"]), 4),
        evictions=slot.extra["evictions"], timeouts=slot.extra["timeouts"])

    toks = np.asarray(slot.x)
    if toks.shape != (N_REQUESTS, GEN) or (toks < 0).any():
        raise AssertionError(f"serve: incomplete requests: {toks}")
    if slot.extra["evictions"] or slot.extra["timeouts"]:
        raise AssertionError("serve: evictions or timeouts on a clean run")
    if not np.array_equal(toks, warm.x):
        raise AssertionError("serve: two identical serves disagree")
    cfg = slot_spec.objective.make_arch()
    prompts = slot.extra["prompts"]
    ref = plain_loop_tokens(cfg, init_params(cfg, jax.random.PRNGKey(SEED)),
                            prompts, GEN, PROMPT_LEN + GEN)
    same = [bool(np.array_equal(a, b)) for a, b in zip(toks, ref)]
    log("serve", requests_bitwise_equal_plain_loop=f"{sum(same)}/{len(same)}")
    for r in np.flatnonzero(~np.asarray(same)):
        _check_near_tie(slot_spec, prompts[r], ref[r], toks[r], r)
    return {"tokens": toks, "plain_loop": ref, "matches": sum(same)}


def _check_near_tie(spec, prompt, ref_row, got_row, r: int) -> None:
    """Where the slot and plain-loop tokens part, the two tokens' logits
    (from a full forward over the common prefix) agree within bf16."""
    import jax
    import jax.numpy as jnp
    from repro.models import forward_logits, init_params

    t = int(np.flatnonzero(ref_row != got_row)[0])
    cfg = spec.objective.make_arch()
    params = init_params(cfg, jax.random.PRNGKey(spec.seed))
    prefix = np.concatenate([prompt, ref_row[:t]]).astype(np.int32)
    logits, _ = forward_logits(cfg, params,
                               {"tokens": jnp.asarray(prefix)[None]})
    last = np.asarray(logits[0, -1], np.float32)
    a, b = float(last[ref_row[t]]), float(last[got_row[t]])
    log("serve", request=r, first_differing_step=t,
        plain_loop_token=int(ref_row[t]), slot_token=int(got_row[t]),
        logit_plain_loop=a, logit_slot=b, logit_max=float(last.max()))
    if not np.isclose(a, b, rtol=LOGIT_TOL, atol=LOGIT_TOL):
        raise AssertionError(
            f"serve: request {r} step {t}: logits {a} vs {b} differ "
            f"beyond bf16 tolerance")


def one_chip(*, reduced: bool = False):
    shape = size_train("pallas_pooled", reduced=reduced)
    ref = train_phase("reference", shape, reduced=reduced,
                      label="train_reference")
    pooled = train_phase("pallas_pooled", shape, reduced=reduced,
                         label="train_pooled")
    check_losses_agree("train_pooled", ref["losses"], pooled["losses"])
    serve_phase(reduced=reduced)


def four_chips(*, reduced: bool = False):
    import jax
    from jax.sharding import Mesh
    from repro.launch import make_host_mesh

    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shape = size_train("pallas_pooled", reduced=reduced, mesh=one)
    single = train_phase("pallas_pooled", shape, reduced=reduced, mesh=one,
                         label="train_pooled_1dev")
    sharded = train_phase("pallas_pooled", shape, reduced=reduced,
                          mesh=make_host_mesh(data=2),
                          label="train_pooled_2x2")
    check_losses_agree("train_pooled_2x2", single["losses"],
                       sharded["losses"])
    per_device, total = sharded["pool_bytes"]
    shares = {d: b / total for d, b in sorted(per_device.items())}
    log("train_pooled_2x2", pool_bytes_total=total,
        pool_bytes_per_device=dict(sorted(per_device.items())),
        pool_share_per_device={d: round(s, 4) for d, s in shares.items()})
    if len(shares) != 4 or any(abs(s - 0.5) > 0.05 for s in shares.values()):
        raise AssertionError(f"pools not split over the data axis: {shares}")
    if not any(sharded["collectives"].values()):
        raise AssertionError("no collectives in the sharded step's HLO")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded pooled train phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} found",
              file=sys.stderr)
        return 1
    from repro.launch import enable_compile_cache

    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    log("done", seconds=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
