"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = 0.0 for pure
derived/ratio rows).  Full raw sweeps land in benchmarks/results/*.json.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run exp1 exp5  # named subsets
"""

import sys
import time


SECTIONS = {
    "exp1": ("qps_recall", "Exp-1 QPS vs recall (Fig. 3)"),
    "exp2": ("construction", "Exp-2 construction cost (Fig. 4)"),
    "exp34": ("effect_params", "Exp-3/4 effect of δ and t (Figs. 5-6)"),
    "exp5": ("error_analysis", "Exp-5 relative distance error (Fig. 7)"),
    "exp67": ("local_optimum", "Exp-6/7 local-optimum & δ' (Fig. 8)"),
    "exp8": ("scalability", "Exp-8 scalability (Fig. 9)"),
    "exp9": ("ablation", "Exp-9 ablation (Fig. 10)"),
    "retrieval": ("retrieval", "δ-EMQG behind recsys retrieval_cand"),
    "kernels": ("kernels_bench", "Pallas kernel microbench"),
}


def main() -> None:
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    names = sys.argv[1:] or list(SECTIONS)
    print("name,us_per_call,derived")
    for key in names:
        mod_name, title = SECTIONS[key]
        print(f"# --- {title} ---")
        t0 = time.time()
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{key}_FAILED,0.0,{type(e).__name__}:{str(e)[:120]}")
        print(f"# {key} done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
