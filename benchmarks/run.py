# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""
    PYTHONPATH=src python -m benchmarks.run [--only construction,search,...]
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: construction,search,quant,streaming,"
                         "serving,degrees,t1t2,k_sweep,scale,kernels")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_construction, bench_degrees, bench_k_sweep,
                            bench_kernels, bench_quant, bench_scale,
                            bench_search, bench_serving, bench_streaming,
                            bench_t1t2)

    suites = {
        "construction": bench_construction.run,   # paper Fig 3
        "search": bench_search.run,               # paper Fig 2
        "quant": bench_quant.run,                 # int8/pq memory-recall-qps
        "streaming": bench_streaming.run,         # dynamic insert/delete churn
        "serving": bench_serving.run,             # admission-batched frontend
        "degrees": bench_degrees.run,             # paper Fig 4/5 + Table A
        "t1t2": bench_t1t2.run,                   # paper Fig 6/7
        "k_sweep": bench_k_sweep.run,             # paper Fig 8
        "scale": bench_scale.run,                 # paper §5.5
        "kernels": bench_kernels.run,             # pallas vs oracle micro
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# == {name} ==", flush=True)
        fn()
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
