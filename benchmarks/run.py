"""Benchmark harness — one entry per paper experiment/table + the roofline
table for the assigned architectures (deliverable d).

``python -m benchmarks.run``          full set
``python -m benchmarks.run --fast``   reduced sizes (CI)
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_atoms, bench_dispatch,
                            bench_emulation_portability,
                            bench_emulation_same_host, bench_fleet,
                            bench_profiling_consistency,
                            bench_profiling_overhead, bench_roofline,
                            bench_scenarios, bench_service)
    suite = [
        ("atoms", bench_atoms.main),
        ("dispatch", bench_dispatch.main),
        ("profiling_overhead", bench_profiling_overhead.main),
        ("profiling_consistency", bench_profiling_consistency.main),
        ("emulation_same_host", bench_emulation_same_host.main),
        ("emulation_portability", bench_emulation_portability.main),
        ("roofline", bench_roofline.main),
        ("scenarios", bench_scenarios.main),
        ("fleet", bench_fleet.main),
        # substring --only matching: keep these names free of "fleet" so
        # `--only fleet` doesn't drag the soak/chaos legs along
        ("soak", bench_fleet.soak),
        ("chaos", bench_fleet.chaos),
        ("dag", bench_fleet.dag),
        ("service", bench_service.main),
    ]
    for name, fn in suite:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            fn(fast=args.fast)
            print(f"## {name}: done in {time.time()-t0:.1f}s\n", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"## {name}: FAILED {type(e).__name__}: {e}", flush=True)
            raise


if __name__ == '__main__':
    main()
