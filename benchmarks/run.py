"""Benchmark driver — one section per paper table/figure + the roofline.

Prints human-readable sections and ``name,us_per_call,derived`` CSV lines.
"""

from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import dse_sweep, lbm_bench, table3
    from repro.compat import enable_compile_cache

    enable_compile_cache()

    sections = []
    sections += table3.run()
    sections.append("")
    sections += dse_sweep.run()
    sections.append("")
    sections += lbm_bench.run()
    sections.append("")
    # roofline table (requires dry-run artifacts; degrade gracefully)
    try:
        from benchmarks import roofline

        arts = roofline.load_artifacts()
        if arts:
            sections.append("## Roofline (from dry-run artifacts)")
            rows = [roofline.analyze(a) for a in arts]
            rows.sort(key=lambda r: r.roofline_frac)
            sections.append(roofline.render(rows))
            for r in rows:
                sections.append(
                    f"roofline/{r.arch}/{r.shape},{r.step_time()*1e6:.1f},"
                    f"frac={r.roofline_frac:.3f};bound={r.bound}"
                )
        else:
            sections.append("## Roofline: no dry-run artifacts found "
                            "(run python -m repro.launch.dryrun --all)")
    except Exception as e:  # pragma: no cover
        sections.append(f"## Roofline: unavailable ({e})")

    print("\n".join(sections))


if __name__ == "__main__":
    main()
