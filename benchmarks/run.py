# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure:
  Fig 3a/3b/3c  bench_matrix_powers   (strategies, n-scaling, k-scaling)
  Fig 3d        bench_sums_powers
  Fig 3e        bench_ols
  Fig 3f        bench_scaling          (mesh-width collective scaling)
  Fig 3g/3h     bench_general_form     (hybrid study, BGD)
  Table 3       bench_memory           (memory vs speedup)
  Table 4       bench_batch_updates    (Zipf batches)
Pass suite names to run a subset, e.g. ``-m benchmarks.run ols``.
"""

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_batch_updates, bench_general_form,
                   bench_matrix_powers, bench_memory, bench_ols,
                   bench_scaling, bench_sums_powers)
    suites = {
        "matrix_powers": bench_matrix_powers.main,
        "sums_powers": bench_sums_powers.main,
        "ols": bench_ols.main,
        "general_form": bench_general_form.main,
        "memory": bench_memory.main,
        "batch_updates": bench_batch_updates.main,
        "scaling": bench_scaling.main,
    }
    want = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    for name in want:
        fn = suites.get(name)
        if fn is None:
            print(f"# unknown suite {name}; have {sorted(suites)}")
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        fn()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == '__main__':
    main()
