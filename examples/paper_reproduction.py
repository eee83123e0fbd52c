"""Full Section-5 reproduction driver: runs the fig1/fig2/fig3/table1
benchmarks at paper-scale grids and writes experiments/figs/*.csv.

  PYTHONPATH=src python examples/paper_reproduction.py [--quick]
"""
import argparse
import sys

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    from benchmarks import fig1_fullgrad, fig2_stochastic, fig3_grid, \
        table1_rates
    for mod in (fig1_fullgrad, fig2_stochastic, fig3_grid, table1_rates):
        print(f"== {mod.__name__}")
        for row in mod.run(quick=args.quick):
            print(row)


if __name__ == "__main__":
    main()
