"""Generate the EXPERIMENTS.md §Roofline / §Dry-run markdown tables from
dry-run JSON results (lists of per-cell records).

    PYTHONPATH=src python -m benchmarks.gen_roofline_md \
        dryrun_single.json dryrun_multi.json > roofline_tables.md
"""
from __future__ import annotations

import json
import sys


def fmt_bytes(b):
    if b is None:
        return "-"
    return f"{b/2**30:.2f}"


def fmt_ms(s):
    return f"{s*1e3:.1f}"


def table(results, title):
    out = [f"### {title}", "",
           "| arch | shape | mb | GiB/dev | fits | t_comp ms | t_mem ms | "
           "t_coll ms | bottleneck | useful | roofline_frac |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in results:
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | - |"
                       f" - | SKIP | - | - |")
            continue
        if r["status"] == "error":
            out.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | - |"
                       f" - | ERROR | - | - |")
            continue
        roof = r["roofline"]
        out.append(
            f"| {r['arch']} | {r['shape']} | {r.get('microbatches', 1)} | "
            f"{fmt_bytes(r.get('bytes_per_device'))} | "
            f"{'Y' if r.get('fits_16gb') else 'N'} | "
            f"{fmt_ms(roof['t_compute_s'])} | {fmt_ms(roof['t_memory_s'])} | "
            f"{fmt_ms(roof['t_collective_s'])} | {roof['bottleneck']} | "
            f"{roof['useful_flops_ratio']:.2f} | "
            f"{roof['roofline_fraction']:.3f} |")
    out.append("")
    return "\n".join(out)


def main():
    parts = []
    for path in sys.argv[1:]:
        # a bench artifact may legitimately be absent (its bench has not
        # run on this checkout yet): skip with a visible note instead of
        # failing the whole render
        try:
            with open(path) as f:
                results = json.load(f)
        except FileNotFoundError:
            parts.append(f"### {path}\n\n_Skipped: {path} not found — "
                         f"run its benchmark to regenerate._\n")
            continue
        except json.JSONDecodeError as e:
            parts.append(f"### {path}\n\n_Skipped: {path} is not valid "
                         f"JSON ({e})._\n")
            continue
        if not (isinstance(results, list) and results
                and "mesh" in results[0]):
            # some other bench's artifact (out-of-core, prefetch,
            # autotune, ...): note what it is rather than crash on an
            # unexpected shape
            keys = (sorted(results)[:8] if isinstance(results, dict)
                    else [type(results).__name__])
            parts.append(f"### {path}\n\n_Skipped: no roofline "
                         f"tables in this artifact (top-level: "
                         f"{', '.join(map(str, keys))})._\n")
            continue
        mesh = "x".join(str(m) for m in results[0]["mesh"])
        parts.append(table(results, f"mesh {mesh} ({results[0]['chips']} "
                           f"chips) — {path}"))
    print("\n".join(parts))


if __name__ == "__main__":
    main()
