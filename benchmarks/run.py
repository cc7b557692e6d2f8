"""Benchmark harness: one module per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV per the harness contract.  Two
suites additionally write machine-readable perf records at the repo root,
tracked across PRs:

* ``predictor`` -> ``BENCH_predictor.json`` (feature-extraction us,
  single / batch host-scorer us, Pallas us, train seconds, speedups);
* ``sim`` -> ``BENCH_sim.json`` (one-shot sweep vs per-event reference
  wall clock on a table9-sized grid, trace-equivalence verdict);
* ``serve`` -> ``BENCH_serve.json`` (seed vs fused real-decode tokens/s,
  TTFT, per-token dispatch overhead, end-to-end queue-to-completion P50);
* ``policies`` -> ``BENCH_policies.json`` (short/long P50+P99 for every
  registered scheduling policy under Poisson rho=0.74 and 100-req burst);
* ``batching`` -> ``BENCH_batching.json`` (lane-scaling tok/s through the
  micro-batched engine, the s(c) slowdown calibration, and the
  policy x lane-count x KV-budget DES grid);
* ``faults`` -> ``BENCH_faults.json`` (fault-injection degradation
  curves: SJF-vs-FCFS short-P50 and goodput across crash-MTBF x repair
  grids, overload shedding P99 bound, serving-layer chaos drain);
* ``sidecar`` -> ``BENCH_sidecar.json`` (loopback HTTP/SSE: streaming
  TTFT overhead vs in-process, client-observed SJF-vs-FCFS short P50);
* ``paging`` -> ``BENCH_paging.json`` (block-paged admission vs
  worst-case KVBudget accounting at an identical byte budget: aggregate
  tok/s + short P50, prefix-reuse warm-prefill speedup, and the
  page-size x budget x share-ratio DES grid);
* ``speculative`` -> ``BENCH_speculative.json`` (draft-verify lanes at
  c=4 vs the fused lane path — aggregate tok/s speedup with bitwise
  token equality, adversarial-draft contrast, and the acceptance-aware
  admission policy x draft-K x acceptance-distribution DES grid);
* ``observability`` -> ``BENCH_observability.json`` (flight-recorder /
  metrics overhead on the loopback wire drain, ranking-monitor fidelity
  recovery + inversion-alert, DES-vs-live trace parity).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run predictor  # one suite
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSONS = {
    "predictor": os.path.join(_ROOT, "BENCH_predictor.json"),
    "sim": os.path.join(_ROOT, "BENCH_sim.json"),
    "serve": os.path.join(_ROOT, "BENCH_serve.json"),
    "policies": os.path.join(_ROOT, "BENCH_policies.json"),
    "batching": os.path.join(_ROOT, "BENCH_batching.json"),
    "faults": os.path.join(_ROOT, "BENCH_faults.json"),
    "sidecar": os.path.join(_ROOT, "BENCH_sidecar.json"),
    "paging": os.path.join(_ROOT, "BENCH_paging.json"),
    "speculative": os.path.join(_ROOT, "BENCH_speculative.json"),
    "observability": os.path.join(_ROOT, "BENCH_observability.json"),
}


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import (batching_bench, faults_bench, fig3_rho_sweep,
                            observability_bench, paging_bench,
                            policies_bench, predictor_latency,
                            serve_bench, sidecar_bench, sim_bench,
                            speculative_bench, table1_service_stats,
                            table2_dataset_stats, table4_ablation,
                            table5_ranking, table6_cross, table7_baselines,
                            table8_burst, table9_tau)

    suites = {
        "table1": table1_service_stats.run,
        "table2": table2_dataset_stats.run,
        "table4": table4_ablation.run,
        "table5": table5_ranking.run,
        "table6": table6_cross.run,
        "table7": table7_baselines.run,
        "table8": table8_burst.run,
        "table9": table9_tau.run,
        "fig3": fig3_rho_sweep.run,
        "predictor": predictor_latency.run,
        "sim": sim_bench.run,
        "serve": serve_bench.run,
        "policies": policies_bench.run,
        "batching": batching_bench.run,
        "faults": faults_bench.run,
        "sidecar": sidecar_bench.run,
        "paging": paging_bench.run,
        "speculative": speculative_bench.run,
        "observability": observability_bench.run,
    }
    wanted = sys.argv[1:] or list(suites)
    t0 = time.time()
    for name in wanted:
        fn = suites.get(name)
        if fn is None:
            sys.exit(f"unknown suite {name!r}; available: {', '.join(suites)}")
        print(f"# --- {name} ---")
        result = fn()
        path = BENCH_JSONS.get(name)
        if path and isinstance(result, dict):
            with open(path, "w") as f:
                json.dump({k: round(v, 4) if isinstance(v, float) else v
                           for k, v in result.items()}, f, indent=2)
                f.write("\n")
            print(f"# wrote {path}")
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
