"""Calibrate alpha_ef (paper Sec. 4.4): sweep the relaxation parameter and
print the quality-coverage frontier so a deployment can pick its operating
point.

  PYTHONPATH=src python examples/calibration_sweep.py
"""
from benchmarks.common import bench_dataset, frontier_bandit
from repro.launch.compile_cache import enable_compile_cache


def main():
    ds = bench_dataset(256, 8)
    print("alpha_ef   coverage   overlap@5   flops_saving")
    for p in frontier_bandit(ds, k=5,
                             alphas=(0.05, 0.1, 0.2, 0.4, 0.8, 1.6)):
        print(f"{p['alpha_ef']:8.2f} {100*p['coverage']:9.1f}% "
              f"{p['overlap']:10.3f} {p['flops_saving']:11.1f}x")
    print("\npick the smallest alpha whose overlap meets your SLO; "
          "larger alpha = more conservative (more compute, higher fidelity).")


if __name__ == "__main__":
    enable_compile_cache()
    main()
