"""Benchmark harness — one module per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines:
  accuracy.py     → Table 1 (centralized) + Tables 2/3 (FedPC/FedAvg/Phong)
  noniid.py       → Table 4 (Dirichlet non-IID)
  convergence.py  → Fig. 4 (cost evolution)
  comm.py         → Fig. 6 / Eq. (8) (bytes per epoch + headline reductions)
  kernels_bench.py→ FedPC round-op kernels vs jnp reference
  roofline.py     → §Roofline rows from the dry-run JSON
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (ablation, accuracy, comm, convergence,
                            kernels_bench, noniid, roofline)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    modules = [
        ("comm", comm),
        ("convergence", convergence),
        ("accuracy", accuracy),
        ("noniid", noniid),
        ("ablation", ablation),
        ("kernels", kernels_bench),
        ("roofline", roofline),
    ]
    failures = 0
    t0 = time.time()
    for name, mod in modules:
        print(f"# --- {name} ---")
        try:
            mod.run()
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0.0,{traceback.format_exc(limit=3)!r}")
    print(f"# done in {time.time() - t0:.1f}s, {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
