"""Benchmark entry point: one function per paper table/figure plus the
kernel microbenches, the serving-engine path comparison, and the
roofline table.

Prints a human-readable block per benchmark followed by machine-readable
``name,us_per_call,derived`` CSV lines, and writes two JSON artifacts —
``BENCH_kernels.json`` (kernel + figure + roofline rows) and
``BENCH_engine.json`` (serving-engine rows) — so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import ensure_fake_cpu_devices

    # before JAX starts: bench_engine's mesh rows need 2 devices; every
    # other row runs on the first
    ensure_fake_cpu_devices(2)
    enable_compile_cache()
    from benchmarks import (
        bench_engine,
        bench_kernels,
        bench_roofline,
        paper_figures,
    )

    rows = []
    rows += paper_figures.fig6_area_power()
    rows += paper_figures.fig7_speedup_energy()
    rows += paper_figures.fig8_per_layer()
    rows += paper_figures.fig9_qos_curves()
    rows += paper_figures.fig10_tradeoff()
    rows += paper_figures.fig11_sublinear()
    rows += paper_figures.table3()
    rows += bench_kernels.bench_kernels()
    rows += bench_roofline.bench_roofline()
    engine_rows = bench_engine.bench_engine()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows + engine_rows:
        print(f"{name},{us:.2f},{derived}")

    bench_engine.rows_to_json(rows, "BENCH_kernels.json")
    bench_engine.rows_to_json(engine_rows, "BENCH_engine.json")


if __name__ == "__main__":
    main()
