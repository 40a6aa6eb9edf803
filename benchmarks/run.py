"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout); progress goes to
stderr-ish bracketed lines.  First run trains the small bench model
(~2 min on CPU) and caches it under artifacts/bench/.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table3     # one section
"""
import sys
import time


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (fig2_template, fig5_speculation, kernel_bench,
                            mask_bench, precompute_cost, serving_bench,
                            table2_invasiveness, table2b_ner,
                            table3_throughput, table4_lookahead)
    sections = {
        "precompute": precompute_cost.run,
        "table2": table2_invasiveness.run,
        "table2b": table2b_ner.run,
        "table3": table3_throughput.run,
        "table4": table4_lookahead.run,
        "fig2": fig2_template.run,
        "fig5": fig5_speculation.run,
        "kernels": kernel_bench.run,
        "mask": mask_bench.run,
        "serving": serving_bench.run,
    }
    want = sys.argv[1:] or list(sections)
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name in want:
        fn = sections[name]
        print(f"# === {name} ===", flush=True)
        fn()
    print(f"# done in {time.perf_counter()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
