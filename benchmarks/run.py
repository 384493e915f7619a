"""Benchmark orchestrator. One module per paper table/figure.

Prints `name,us_per_call,derived` CSV rows. Roofline rows (from the dry-run
artifacts, if present) are appended at the end.
"""
from __future__ import annotations

import sys
import traceback


def _emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
    sys.stdout.flush()


def main() -> None:
    from benchmarks import (fig1_env_throughput, fig2_dqn_training, fig3_multitask,
                            fig4_pool_scaling, table2_carbon)

    print("name,us_per_call,derived")
    for mod in (fig1_env_throughput, fig2_dqn_training, fig3_multitask,
                fig4_pool_scaling, table2_carbon):
        try:
            mod.main(_emit)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            _emit(f"{mod.__name__}/ERROR", 0.0, repr(e))

    # roofline summary (requires results/dryrun from launch.dryrun)
    try:
        from benchmarks import roofline

        rows = roofline.table(mesh="pod16x16")
        for r in rows:
            _emit(f"roofline/{r['arch']}/{r['shape']}", r["bound_s"] * 1e6,
                  f"dominant={r['dominant']};roofline_frac={r['roofline_fraction']:.3f}")
    except Exception as e:  # noqa: BLE001
        _emit("roofline/SKIPPED", 0.0, repr(e))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
