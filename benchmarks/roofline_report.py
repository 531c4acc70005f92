"""Roofline report over BENCH_engine.json: achieved-vs-bound per backend.

    PYTHONPATH=src python -m benchmarks.roofline_report [BENCH_engine.json]

Reads the ``roofline`` section :mod:`benchmarks.engine_bench` attaches to
every backend row (modeled bytes-moved / FLOPs / bound / achieved % from
:class:`repro.launch.roofline.RooflineModel`) and prints the markdown
table the README's backend matrix is refreshed from.  The achieved %
column is drawn against the published peaks of the device the benchmark
ran on; a run on a device without published peaks (the CPU) prints "not
measured" there, and the number to read is the *relative* bytes-moved
ranking (fused moves ~iters× fewer table bytes than per-hop).

The legacy mode — deriving three-term rooflines from LM dry-run JSONL
records — moved with the HLO cost model to :mod:`repro.launch.hlo_cost`;
``derive``/``rows_from``/``table`` below keep that path importable behind
a ``DeprecationWarning`` (now with guarded divisions).
"""
from __future__ import annotations

import json
import sys
import warnings


def bottleneck_note(row: dict) -> str:
    """One actionable lever per bound, FoG flavored."""
    bound = row.get("bound") or row.get("dominant")
    if bound == "memory":
        return "cut table re-reads: fused pin / int8 pack / compaction"
    if bound == "collective":
        return "reshard or overlap: fewer rotation hops across ICI"
    return "raise VPU utilization: bigger block_b / denser live lanes"


def engine_rows(path: str) -> list[dict]:
    """Backend rows of BENCH_engine.json that carry a roofline entry."""
    with open(path) as f:
        bench = json.load(f)
    latency = bench.get("backend_us", {})
    return [{"name": name, "latency_us": latency.get(name), **roof}
            for name, roof in bench.get("roofline", {}).items()]


def engine_table(rows: list[dict]) -> list[str]:
    hdr = ("| backend | latency | bytes moved | flops | bound | "
           "roofline ideal | achieved | next lever |")
    sep = "|" + "---|" * 8
    out = [hdr, sep]
    for r in sorted(rows, key=lambda r: (r.get("latency_us") or 0.0)):
        lat = r.get("latency_us")
        lat_cell = f"{lat / 1e3:.2f} ms" if lat else "—"
        out.append(
            f"| {r['name']} | {lat_cell} | "
            f"{r['bytes_moved'] / 1e6:.2f} MB | {r['flops']:.3g} | "
            + (f"{r['bound']} | {r['ideal_s'] * 1e6:.1f} us | "
               f"{r['achieved_pct']:.3f}% | " if "achieved_pct" in r else
               "unpriced | — | not measured | ")
            + f"{bottleneck_note(r)} |")
    return out


def main() -> None:
    paths = sys.argv[1:] or ["BENCH_engine.json"]
    for path in paths:
        print(f"\n## {path}")
        rows = engine_rows(path)
        if not rows:
            print("(no roofline sections; run benchmarks.engine_bench first)")
            continue
        print("\n".join(engine_table(rows)))


# --------------------------------------------------------------------------
# deprecated: LM dry-run JSONL mode (no FoG path produces these records)
# --------------------------------------------------------------------------

def _warn_legacy(name: str) -> None:
    warnings.warn(
        f"benchmarks.roofline_report.{name} consumes LM dry-run JSONL "
        "records, which no FoG path produces; the engine roofline lives in "
        "BENCH_engine.json (engine_rows/engine_table)",
        DeprecationWarning, stacklevel=3)


def derive(rec: dict) -> dict:
    """DEPRECATED three-term derivation for one dry-run JSONL record."""
    _warn_legacy("derive")
    from repro.launch.hlo_cost import HBM_BW, ICI_BW, ICI_LINKS, PEAK_FLOPS
    compute = rec["hlo_flops"] / PEAK_FLOPS
    memory = rec["hlo_bytes"] / HBM_BW
    coll = rec["collective_bytes"] / (ICI_LINKS * ICI_BW)
    terms = {"compute": compute, "memory": memory, "collective": coll}
    dom = max(terms, key=terms.get)
    step = max(terms.values())
    chips = rec.get("chips") or 1
    ideal = rec["model_flops"] / (chips * PEAK_FLOPS)
    useful = ((rec["model_flops"] / chips) / rec["hlo_flops"]
              if rec["hlo_flops"] else 0.0)
    return {**rec, "compute_s": compute, "memory_s": memory,
            "collective_s": coll, "dominant": dom,
            "useful_flops_ratio": useful,
            "roofline_fraction": ideal / step if step else 0.0}


def rows_from(path: str) -> list[dict]:
    """DEPRECATED reader for dry-run JSONL files."""
    _warn_legacy("rows_from")
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("skipped") or rec.get("error"):
                continue
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                out.append(derive(rec))
    return out


def table(rows: list[dict]) -> list[str]:
    """DEPRECATED dry-run table renderer."""
    _warn_legacy("table")
    hdr = ("| arch | shape | mesh | compute_s | memory_s | collective_s | "
           "dominant | MODEL_FLOPS | useful | roofline_frac | next lever |")
    sep = "|" + "---|" * 11
    out = [hdr, sep]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r['compute_s']:.3e} | {r['memory_s']:.3e} | "
            f"{r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['model_flops']:.3g} | {r['useful_flops_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} | {bottleneck_note(r)} |")
    return out


if __name__ == "__main__":
    main()
