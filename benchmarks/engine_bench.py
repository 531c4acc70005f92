"""FogEngine backend matrix benchmark -> CSV rows + BENCH_engine.json.

Times one full Algorithm-2 evaluation per backend on a fixed trained
forest/batch and records wall time, mean hops, and accuracy, so every
future PR has a perf trajectory for the unified hot path.  Backends:

  reference        pure-jnp scan (the oracle)
  reference-lazy   early-exit while_loop
  pallas           fused hop-update kernel (interpreted on CPU, Mosaic on TPU)
  pallas-chunked   same, batch evaluated in chunk_b slices (VMEM-bounded)
  fused            ENTIRE Algorithm-2 loop in ONE Pallas launch (all grove
                   tables VMEM-pinned, early-exit while_loop in-kernel) —
                   pinned at the historical hand-picked block_b=256 with
                   compaction off: the autotuner's baseline to beat
  fused-tuned      fused at the MEASURED autotune winner (block_b x live-
                   lane compaction swept per (precision, field size) —
                   kernels/autotune.py); the roofline_gate asserts this is
                   no slower than the hand-picked row
  fused-auto       same, chunk_b="auto": chunks ONLY when the packed tables
                   + batch footprint exceed the VMEM budget (this forest
                   fits, so it must match plain fused — the fix for the
                   fused-chunked 29.4ms-vs-8.2ms regression)
  fused-bf16 /     fused over bf16 / int8 ForestPacks (packed VMEM
  fused-int8       residency; int8 pins ~4x the field per byte)
  reference-int8   the int8 dequantize oracle

Every row also gets a ``roofline`` entry — modeled bytes-moved / FLOPs /
bound / achieved-vs-roofline % from the dtype-aware analytic
:class:`repro.launch.roofline.RooflineModel` (drawn against the published
peaks of the device the run found; a device without published peaks, the
CPU among them, gets bytes and FLOPs only) — and the measured
autotune winner is recorded under ``autotune``.

The record's ``kernel_launches`` field is the analytic per-eval Pallas
dispatch count; ``table_bytes`` is each precision's packed ForestPack
footprint (the fused kernel's VMEM load and the paper's SRAM capacity);
``energy_pj`` is each row's modeled pJ/example from the EvalReport's own
EnergyModel (the README backend matrix's pJ column).  Rows sharing a
precision must agree bit-for-bit on hops (the energy contract); int8 rows
additionally face the quantization gate — ``quant_gate`` fails the run if
int8 accuracy drops more than 1% below fp32, and CI invokes it against the
emitted JSON.

The record also carries a ``frontier`` dump: the Pareto frontier the
planning layer builds over (threshold x precision) on this forest
(``core/frontier.py``), which CI's ``energy_gate`` re-checks for
monotonicity — no frontier point may have both lower accuracy and higher
energy than a neighbor.

The ring backend is timed separately in fog_ring_bench (needs forced
multi-device XLA in a subprocess).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_engine.json"

QUANT_GATE_MAX_DROP = 0.01      # int8 may cost at most 1% accuracy vs fp32

# measured-vs-hand-picked tolerance: the tuned config must not lose more
# than this to the legacy block_b=256 default (timing noise headroom on
# shared CI runners; the tuner picked the faster config when it measured)
ROOFLINE_GATE_SLACK = 1.10


def _time_engine(engine, x, key, policy, reps=3):
    res = engine.eval(x, key, policy=policy)   # compile + warm
    res.proba.block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        res = engine.eval(x, key, policy=policy)
        res.proba.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best, res


def energy_gate(record: dict | None = None,
                path: Path | str = OUT_PATH) -> None:
    """Fail (raise) unless the dumped frontier is monotone: sorted by
    energy ascending, accuracy must strictly increase (Frontier's Pareto
    invariant — a violation means the builder regressed)."""
    from repro.core.frontier import Frontier
    if record is None:
        record = json.loads(Path(path).read_text())
    frontier = Frontier.from_dict(record["frontier"])
    frontier.check_monotone()
    print(f"CSV,engine,energy_gate=pass,points={len(frontier)},"
          f"span_nj={frontier.points[0].energy_nj:.3f}"
          f"-{frontier.points[-1].energy_nj:.3f}")


def quant_gate(record: dict | None = None,
               path: Path | str = OUT_PATH) -> None:
    """Fail (raise) if int8 accuracy trails fp32 by more than the gate."""
    if record is None:
        record = json.loads(Path(path).read_text())
    acc = record["acc"]
    fp32, int8 = acc["fused"], acc["fused-int8"]
    if int8 < fp32 - QUANT_GATE_MAX_DROP:
        raise SystemExit(
            f"quantization gate FAILED: int8 accuracy {int8:.4f} is more "
            f"than {QUANT_GATE_MAX_DROP:.0%} below fp32 {fp32:.4f}")
    print(f"CSV,engine,quant_gate=pass,acc_fp32={fp32:.4f},"
          f"acc_int8={int8:.4f}")


def roofline_gate(record: dict | None = None,
                  path: Path | str = OUT_PATH) -> None:
    """Fail (raise) unless (a) every timed backend row carries a roofline
    entry with bytes-moved — plus bound / achieved % exactly when the run's
    device has published peaks — and (b) the measured autotune winner is
    no slower than the hand-picked block_b default (within timing-noise
    slack)."""
    from repro.launch.roofline import PEAKS
    if record is None:
        record = json.loads(Path(path).read_text())
    roof = record.get("roofline")
    if not roof:
        raise SystemExit("roofline gate FAILED: no roofline section")
    for name in record["backend_us"]:
        entry = roof.get(name)
        if not entry:
            raise SystemExit(f"roofline gate FAILED: no roofline entry "
                             f"for backend row {name!r}")
        priced = record.get("device", {}).get("kind") in PEAKS
        for field in ("bytes_moved", "bound", "achieved_pct"):
            if (field in entry) != (priced or field == "bytes_moved"):
                raise SystemExit(f"roofline gate FAILED: roofline[{name!r}]"
                                 f" {'lacks' if priced else 'prices'} "
                                 f"{field!r}")
    tuned = record["backend_us"].get("fused-tuned")
    hand = record["backend_us"].get("fused")
    if tuned is None or hand is None:
        raise SystemExit("roofline gate FAILED: need both fused and "
                         "fused-tuned rows")
    if tuned > hand * ROOFLINE_GATE_SLACK:
        raise SystemExit(
            f"roofline gate FAILED: autotuned fused ({tuned} us) is slower "
            f"than the hand-picked default ({hand} us) beyond "
            f"{ROOFLINE_GATE_SLACK:.2f}x slack")
    cfg = record.get("autotune", {})
    print(f"CSV,engine,roofline_gate=pass,tuned_us={tuned},hand_us={hand},"
          f"block_b={cfg.get('block_b')},compact={cfg.get('compact')}")


def run(out_path: Path | str | None = OUT_PATH) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FogEngine, FogPolicy, ForestPack, split
    from repro.data import make_dataset
    from repro.forest import TrainConfig, train_random_forest

    ds = make_dataset("penbased")
    rf = train_random_forest(ds.x_train, ds.y_train, ds.n_classes,
                             TrainConfig(n_trees=16, max_depth=8, seed=1))
    gc = split(rf, 2)
    x = jnp.asarray(ds.x_test)
    key = jax.random.key(0)
    thresh = 0.3
    policy = FogPolicy(threshold=thresh, max_hops=gc.n_groves)

    # measured autotune pass first: sweep block_b x compaction on inputs
    # representative of this benchmark, so the fused-tuned row (block_b
    # unset -> best_config cache hit) serves the measured winner
    from repro.core.policy import NO_BUDGET
    from repro.kernels import autotune
    B = int(x.shape[0])
    pack_fp32 = ForestPack.from_groves(gc, "fp32")
    tuned = autotune.tune(
        pack_fp32, x,
        jax.random.randint(jax.random.key(1), (B,), 0, gc.n_groves),
        jnp.full((B,), thresh, jnp.float32),
        jnp.full((B,), NO_BUDGET, jnp.int32),
        max_hops=gc.n_groves,
        blocks=[512, 256, 128, 64])

    engines = {
        "reference": FogEngine(gc),
        "reference-lazy": FogEngine(gc, lazy=True),
        "pallas": FogEngine(gc, backend="pallas"),
        "pallas-chunked": FogEngine(gc, backend="pallas", chunk_b=256),
        # the historical hand-picked config: the tuner's baseline to beat
        "fused": FogEngine(gc, backend="fused", block_b=256, compact=False),
        "fused-tuned": FogEngine(gc, backend="fused"),  # autotuned knobs
        "fused-auto": FogEngine(gc, backend="fused", chunk_b="auto"),
        "fused-bf16": FogEngine(gc, backend="fused", precision="bf16"),
        "fused-int8": FogEngine(gc, backend="fused", precision="int8"),
        "reference-int8": FogEngine(gc, precision="int8"),
    }
    precisions = {name: eng.precision for name, eng in engines.items()}
    n_chunks = -(-B // 256)
    # analytic Pallas dispatches per evaluation (worst case, lazy aside);
    # fused-auto must NOT chunk this VMEM-resident forest: 1 launch
    launches = {
        "reference": 0, "reference-lazy": 0,
        "pallas": gc.n_groves, "pallas-chunked": gc.n_groves * n_chunks,
        "fused": 1, "fused-tuned": 1, "fused-auto": 1,
        "fused-bf16": 1, "fused-int8": 1, "reference-int8": 0,
    }
    table_bytes = {p: ForestPack.from_groves(gc, p).table_bytes
                   for p in ("fp32", "bf16", "int8")}
    rows, record = [], {"bench": "engine_backends", "B": B,
                        "n_groves": gc.n_groves, "thresh": thresh,
                        "backend_us": {}, "mean_hops": {}, "acc": {},
                        "energy_pj": {},
                        "kernel_launches": launches,
                        "table_bytes": table_bytes,
                        "autotune": tuned.to_dict(), "roofline": {}}
    # which rows walk every lane every iteration (fixed-trip scan) vs exit
    # early, and which run the fused compaction — the roofline model's
    # iters / compute terms
    scan_rows = {"reference", "pallas", "pallas-chunked", "reference-int8"}
    compact_rows = {"fused-tuned": tuned.compact, "fused-auto": True,
                    "fused-bf16": True, "fused-int8": True}
    from repro.launch.roofline import PEAKS, RooflineModel
    dev = jax.devices()[0]
    record["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    # a device without published peaks gets traffic only: no achieved %
    spec = PEAKS.get(dev.device_kind)
    base_hops = {}
    for name, eng in engines.items():
        dt, res = _time_engine(eng, x, key, policy)
        hops = np.asarray(res.hops)
        acc = float((np.asarray(res.label) == ds.y_test).mean())
        prec = precisions[name]
        if prec not in base_hops:
            base_hops[prec] = hops
        else:
            # backends must preserve the hop-count energy accounting
            # within each precision (int8 walks legitimately differ)
            assert (hops == base_hops[prec]).all(), \
                f"{name} diverged on hops"
        energy_pj = res.energy_report().per_example_pj
        roof = RooflineModel(eng.tables.pack(prec), x.shape[1],
                             spec).estimate(
            name,
            B,
            iters=gc.n_groves if name in scan_rows else int(hops.max()),
            hops_total=float(hops.sum()),
            compact=compact_rows.get(name, False))
        record["backend_us"][name] = round(dt * 1e6)
        record["mean_hops"][name] = float(hops.mean())
        record["acc"][name] = acc
        record["energy_pj"][name] = energy_pj
        record["roofline"][name] = roof.to_dict(measured_s=dt)
        rows.append(f"CSV,engine,backend={name},us={dt * 1e6:.0f},"
                    f"acc={acc:.4f},mean_hops={hops.mean():.2f},"
                    f"energy_pj={energy_pj:.1f},"
                    f"launches={launches[name]},"
                    f"table_bytes={table_bytes[prec]},"
                    f"roof_bound={roof.bound if spec else 'unpriced'},"
                    f"roof_mb={roof.bytes_moved / 1e6:.2f}")
    # the auto-chunk regression fix: auto must not chunk a resident pack
    assert engines["fused-auto"]._resolve_chunk(
        "fused", engines["fused-auto"].tables.pack("fp32"), B, 256, "auto",
        int(x.shape[1])) is None, "fused-auto chunked a VMEM-resident pack"
    # the planning layer's Pareto frontier over (threshold x precision) on
    # this forest — persisted so CI's energy_gate can assert monotonicity
    # and the README pJ column has a calibrated source
    from repro.core.frontier import build_frontier
    frontier = build_frontier(
        engines["reference"], np.asarray(ds.x_test), ds.y_test)
    record["frontier"] = frontier.to_dict()
    rows.extend(f"CSV,engine,frontier,{p}" for p in frontier)
    if out_path is not None:
        Path(out_path).write_text(json.dumps(record, indent=2) + "\n")
        rows.append(f"CSV,engine,wrote={out_path}")
    quant_gate(record)
    energy_gate(record)
    roofline_gate(record)
    return rows


if __name__ == "__main__":
    import sys
    if "--gate-only" in sys.argv:
        quant_gate()
    elif "--energy-gate-only" in sys.argv:
        energy_gate()
    elif "--roofline-gate-only" in sys.argv:
        roofline_gate()
    else:
        print("\n".join(run()))
