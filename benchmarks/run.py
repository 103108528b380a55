# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#   comm_footprint  -> paper Fig. 6 + Table 2 communication columns
#   kernelbench     -> Pallas kernel oracle checks + CPU ref timings
#   trainbench      -> scan training engine / K-party vmapped throughput
#   roofline        -> VFL-stage FLOPs/bytes via compiled cost_analysis
#   accuracy        -> paper Fig. 5 (quick subset) + Table 2 metric columns
#
# ``--full`` runs the complete 48-scenario accuracy sweep (hours on 1 CPU).
import argparse
import sys

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--max-epochs", type=int, default=40)
    ap.add_argument("--skip-accuracy", action="store_true")
    args = ap.parse_args()

    from benchmarks import (accuracy, comm_footprint, kernelbench, roofline,
                            trainbench)

    print("name,us_per_call,derived")
    for row in comm_footprint.rows():
        tag = f"comm/{row['dataset']}/{row['aligned']}"
        print(f"{tag},0,apcvfl={row['apcvfl_MB']:.2f}MB|"
              f"vfedtrans={row['vfedtrans_MB']:.2f}MB|"
              f"splitnn={row['splitnn_MB']:.2f}MB|"
              f"xVFT={row['saving_vs_vfedtrans']:.1f}|"
              f"xSplitNN={row['saving_vs_splitnn']:.1f}")
    sys.stdout.flush()

    kernelbench.run(csv=False)
    sys.stdout.flush()

    trainbench.run(rows=2048, epochs=10)
    sys.stdout.flush()

    roofline.run(csv=False, out_json="BENCH_roofline.json")
    sys.stdout.flush()

    if not args.skip_accuracy:
        accuracy.run(quick=not args.full, max_epochs=args.max_epochs)


if __name__ == '__main__':
    main()
