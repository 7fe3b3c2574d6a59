"""Benchmark harness: one module per paper table/figure + the TPU-serving
integration and the roofline analysis.  Prints ``name,us_per_call,derived``
CSV rows (us_per_call = harness wall time per run; derived = the figure's
metrics).  A module that raises gets a ``<name>.ERROR`` row, the remaining
modules still run, and the harness exits nonzero."""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import emit


def main() -> None:
    from benchmarks import (
        fig3_overhead,
        fig5_static,
        fig6_window,
        fig7_cluster,
        fig8_cdfs,
        fig9_colocation,
        fig10_overhead,
        fig11_baselines,
        roofline,
        serving_lags,
    )

    rows = []
    modules = [
        ("fig3", lambda: fig3_overhead.main()),
        ("fig3-cluster", lambda: fig3_overhead.main(cluster_mode=True)),
        ("fig5", fig5_static.main),
        ("fig6", fig6_window.main),
        ("fig7", fig7_cluster.main),
        ("fig8", fig8_cdfs.main),
        ("fig9", fig9_colocation.main),
        ("fig10", fig10_overhead.main),
        ("fig11", fig11_baselines.main),
        ("serving", serving_lags.main),
        ("roofline", roofline.main),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = []
    for name, fn in modules:
        if only and only not in name:
            continue
        try:
            rows.extend(fn())
        except Exception as e:  # noqa: BLE001 — report, continue, fail below
            traceback.print_exc(file=sys.stderr)
            rows.append((f"{name}.ERROR", 0.0, repr(e)[:120]))
            failed.append(name)
    emit(rows)
    if failed:
        sys.exit(f"benchmark modules raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
