"""Batched serving example: ``batch`` prompts through ``repro.api``'s serve
backend, which serves every job through the continuous-batching
``SlotServer`` (here ``batch`` requests in ``batch`` slots: each prompt is
prefilled alone, then all of them decode together over the ring-buffer KV
cache in one compiled loop).

  PYTHONPATH=src python examples/serve_batched.py [--arch qwen2-0.5b]
"""
import argparse

from repro.api import ExperimentSpec, ServeJob, ServeBackend
from repro.launch import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    spec = ExperimentSpec(
        objective=ServeJob(arch=args.arch, batch=args.batch,
                           prompt_len=args.prompt_len, temperature=0.8),
        T=args.gen, seed=0)
    res = ServeBackend().run(spec)
    gen = res.x
    print(f"decoded {gen.shape} in {res.extra['decode_seconds']:.2f}s "
          f"({res.extra['tok_per_s']:.1f} tok/s, total {res.seconds:.2f}s "
          f"incl. prefill)")
    print("sample:", gen[0][:12])


if __name__ == "__main__":
    main()
