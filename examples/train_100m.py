"""End-to-end driver: train a ~100M-parameter qwen2-family model with the
AsGrad async trainer on heterogeneous data for a few hundred steps.

One ``ExperimentSpec`` + ``TrainJob`` through ``repro.api``'s trainer
backend — the same spec vocabulary as the theory-tier simulator.

Presets:
  --preset smoke   tiny model, 20 steps   (runs anywhere, CI-sized)
  --preset 100m    ~100M params, 300 steps (the deliverable run; sized for a
                   real accelerator — on this CPU container use smoke)

  PYTHONPATH=src python examples/train_100m.py --preset smoke \
      --scheduler shuffled --pattern poisson
"""
import argparse
import dataclasses

from repro.api import ExperimentSpec, TrainJob, TrainerBackend
from repro.launch import enable_compile_cache
from repro import checkpoint


def build_job(preset: str):
    if preset == "smoke":
        job = TrainJob(arch="qwen2-0.5b", reduced=True, remat="none",
                       global_batch=8, seq_len=64)
        steps, n_groups = 20, 4
    else:  # ~100M active params
        job = TrainJob(
            arch="qwen2-0.5b", reduced=False, remat=None,
            arch_overrides=(("n_layers", 12), ("d_model", 768),
                            ("n_heads", 12), ("n_kv_heads", 4),
                            ("d_head", 64), ("d_ff", 2048),
                            ("vocab", 32768), ("tie_embeddings", True)),
            global_batch=32, seq_len=512)
        steps, n_groups = 300, 8
    return job, steps, n_groups


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m"])
    ap.add_argument("--scheduler", default="shuffled",
                    choices=["pure", "random", "shuffled", "fedbuff"])
    ap.add_argument("--pattern", default="poisson")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sync", action="store_true",
                    help="synchronous baseline (delay_rounds=0)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    job, steps, n_groups = build_job(args.preset)
    if args.sync:
        job = dataclasses.replace(job, delay_rounds=0)
    spec = ExperimentSpec(
        scheduler=f"{args.scheduler}:b={max(n_groups // 2, 1)}"
        if args.scheduler == "fedbuff" else args.scheduler,
        timing=f"{args.pattern}:slow=6",
        objective=job, T=steps, n_workers=n_groups,
        stepsize=args.lr, seed=0)

    cfg = job.make_arch()
    from repro.models import n_params
    print(f"arch={cfg.name}-derived  params={n_params(cfg)/1e6:.1f}M  "
          f"steps={steps}  batch={job.global_batch}x{job.seq_len}  "
          f"groups={n_groups}")

    def on_step(i, state, m):
        if i % max(steps // 10, 1) == 0 or i == steps - 1:
            print(f"step {i:4d}  loss={m['loss']:.4f}  "
                  f"|g|={m['grad_norm']:.3f}  part={m['participation']:.2f}")

    res = TrainerBackend(on_step=on_step).run(spec)
    print(f"done in {res.seconds:.1f}s  final loss={res.losses[-1]:.4f}  "
          f"tau_max={res.trace['tau_max']}")
    if args.ckpt:
        checkpoint.save(args.ckpt, res.x, step=steps, meta={"arch": cfg.name})
        print("checkpoint saved to", args.ckpt)


if __name__ == "__main__":
    main()
