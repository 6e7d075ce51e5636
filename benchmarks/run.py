"""Benchmark harness: one section per paper table + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV rows.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="shorter convergence runs")
    ap.add_argument("--only", default=None,
                    choices=[None, "kernels", "convergence", "roofline"])
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    if args.only in (None, "kernels"):
        from benchmarks import kernel_bench
        kernel_bench.main()
    if args.only in (None, "convergence"):
        from benchmarks import convergence_bench
        if args.fast:
            convergence_bench.table1_resnet(steps=30)
            convergence_bench.table3_transformer(steps=40)
            convergence_bench.table4_ncf(steps=50)
            convergence_bench.fig5_stats(steps=20)
        else:
            convergence_bench.main()
    if args.only in (None, "roofline"):
        from benchmarks import roofline_bench
        roofline_bench.main()


if __name__ == "__main__":
    main()
