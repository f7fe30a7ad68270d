"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,derived`` CSV rows.  ``--fast`` shrinks episode budgets;
``--only fig7`` runs a single section.  The roofline section reads the
dry-run sweep output (results/dryrun_baseline.jsonl).
"""
import argparse
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache


SECTIONS = [
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline"),
    ("fig5", "benchmarks.fig5_control"),
    ("fig6", "benchmarks.fig6_distributed_scaling"),
    ("fig7", "benchmarks.fig7_rate_limiter"),
    ("fig9", "benchmarks.fig9_discrete"),
    ("fig10", "benchmarks.fig10_bsuite"),
    ("fig11", "benchmarks.fig11_demos"),
    ("fig12", "benchmarks.fig12_offline"),
    ("fig13", "benchmarks.fig13_replay_sharding"),
    ("fig14", "benchmarks.fig14_actor_scaling"),
]


def main() -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    p.add_argument("--fast", action="store_true")
    args = p.parse_args()

    failures = 0
    for name, module_name in SECTIONS:
        if args.only and args.only != name:
            continue
        print(f"# === {name} ({module_name}) ===", flush=True)
        t0 = time.time()
        try:
            import importlib
            mod = importlib.import_module(module_name)
            if name == "fig10":
                mod.main(fast=args.fast)
            else:
                mod.main()
            print(f"{name}/section_wall_s,{round(time.time() - t0, 1)},")
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{name}/FAILED,{type(e).__name__},{e}")
        sys.stdout.flush()
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
