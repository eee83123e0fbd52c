"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, its configuration from
``bench/configs/<config>.json``, its traffic mix from
``bench/mixes/<traffic>.json`` and its correctness limits from
``bench/limits/<cell>.json``; runs the driver of the mix's kind
(``bench/drivers/<kind>.py``); and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<name>.py``), ``device``
and, traced, ``breakdown``; the numbers compared for ``correct`` come
last, under ``checks``, and are also the last lines of standard error.

It refuses to run without an accelerator: with no TPU, or fewer chips
than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference in float8) "
                    "and the faults planted in the reference, against the "
                    "same reference: the upper readings the limits are set "
                    "from.  Printed on standard error only.")
    return ap.parse_args(argv)


def main(argv=None, *, require_chip: bool = True, overrides=None) -> int:
    """Run one cell.  ``require_chip=False`` and ``overrides`` (a dict that
    replaces parts of the cell: ``config``, ``mix``, ``limits``) are for
    the CPU tests, which drive a whole run at a tiny size."""
    args = parse(argv)
    cell = harness.load_cell(ROOT, args.workload, overrides)
    harness.enable_compile_cache(ROOT)
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    try:
        peak = harness.peaks(ROOT, devs[0].device_kind)
    except KeyError as e:
        if require_chip:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        peak = None     # CPU tests: no device metric without a peak
    driver = harness.driver(cell.mix["kind"])
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), peak=peak, t0=PROCESS_T0,
                      root=ROOT, control=bool(args.control))
    out = driver.run(run)
    line = harness.result_line(run, out)
    harness.print_checks(out["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
