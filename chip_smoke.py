"""Chip smoke run: the forest's train -> pack -> registry -> serve path on a TPU.

    python chip_smoke.py              # one chip: train, pack+publish, serve,
                                      # check, wide field
    python chip_smoke.py --chips 4    # four chips: 4 replicas vs 1, and the
                                      # grove-sharded ring vs reference

Every phase runs in this one process (a chip belongs to one process) and
prints one line; any failed phase raises, so the run exits non-zero.  The
last line of a passing run is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script refuses to run and prints no result.  The phases
are plain functions so the tests can drive them at tiny sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# check tolerances (see check_agreement)
PROBA_TOL = 1e-6
GATE_TOL = 1e-6
MIN_LABEL_AGREEMENT = 0.995


def log(msg: str) -> None:
    print(msg, flush=True)


def accuracy(labels, y) -> float:
    import numpy as np
    return float(np.mean(np.asarray(labels) == np.asarray(y)))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_train(ds, *, n_trees=16, grove_size=2, depth=8, trainer="device",
                seed=0, tag="train"):
    """Fit a FogClassifier (cold, then warm: the difference is compile
    time) and score it through the fused backend."""
    import numpy as np
    from repro.sklearn import FogClassifier

    def fit():
        t0 = time.perf_counter()
        clf = FogClassifier(n_trees=n_trees, grove_size=grove_size,
                            max_depth=depth, trainer=trainer,
                            backend="fused", seed=seed)
        clf.fit(ds.x_train, ds.y_train, n_classes=ds.n_classes)
        np.asarray(clf.gc_.leaf)            # wait for the device
        return clf, time.perf_counter() - t0

    clf, cold = fit()
    warm = None
    if trainer == "device":
        clf, warm = fit()
    acc = clf.score(ds.x_test, ds.y_test)
    fit_s = f"fit_s={warm:.3f} compile_s={cold - warm:.3f}" if warm else (
        f"fit_s={cold:.3f}")
    log(f"[{tag}] {ds.name} trainer={trainer} trees={n_trees}x"
        f"{grove_size} depth={depth} F={ds.x_train.shape[1]} "
        f"C={ds.n_classes} {fit_s} test_acc={acc:.4f}")
    return clf


def phase_pack_publish(clf, root: Path, *, name="pen"):
    """fp32 and int8 packs of the fitted field, each published as a
    registry tenant."""
    from repro.forest.pack import ForestPack
    from repro.registry import ModelRegistry
    shutil.rmtree(root, ignore_errors=True)
    registry = ModelRegistry(root)
    packs, tenants = {}, {}
    for precision in ("fp32", "int8"):
        pack = ForestPack.from_groves(clf.gc_, precision)
        tenant = f"{name}-{precision}"
        version = registry.publish(
            tenant, pack, extra={"n_features_in": clf.n_features_in_})
        back, _ = registry.load(tenant, version)
        if back.precision != precision or back.table_bytes != pack.table_bytes:
            raise RuntimeError(f"{tenant} v{version} did not load back as "
                               f"published ({back.precision}, "
                               f"{back.table_bytes} B)")
        packs[precision], tenants[precision] = pack, tenant
    log("[pack] " + " ".join(
        f"{tenants[p]}=v{registry.live_version(tenants[p])}:"
        f"{packs[p].table_bytes}B" for p in packs) + f" registry={root}")
    return registry, packs, tenants


def serve_stream(registry, tenants, ds, *, n_requests, n_slots, devices,
                 threshold=0.3, seed=0):
    """Drive a seeded request stream (test rows, alternating fp32 / int8
    tenants) through the registry, the pipelined packed ContinuousBatcher
    and one DeviceDispatcher replica per device.  Returns the completed
    requests by rid, the row each request carried, and the dispatcher."""
    import numpy as np
    from repro.core.policy import FogPolicy
    from repro.registry import PackCache
    from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
    from repro.serve.scheduler import ContinuousBatcher, Request

    n_features = ds.x_test.shape[1]
    cache = PackCache(registry, budget_bytes=64 * 2**20)
    server = ForestReplicaServer(None, n_features, backend="fused",
                                 registry=registry, cache=cache, seed=seed)
    dispatcher = DeviceDispatcher(server.packed_factory, devices)
    batcher = ContinuousBatcher(n_slots, None, server.prefill, eos_id=-1,
                                default_policy=FogPolicy(threshold=threshold),
                                dispatcher=dispatcher, registry=registry,
                                pipeline=True)
    rows = np.random.default_rng(seed).integers(0, len(ds.x_test), n_requests)
    precisions = sorted(tenants)
    for rid, row in enumerate(rows):
        p = precisions[rid % len(precisions)]
        if not batcher.submit(Request(
                rid=rid, prompt=ds.x_test[row], max_new_tokens=1,
                model=tenants[p],
                policy=FogPolicy(threshold=threshold, precision=p))):
            raise RuntimeError(f"request {rid} was refused at submit")
    done = {r.rid: r for r in batcher.run(max_steps=100 * n_requests)}
    shed = batcher.stats.n_shed
    if len(done) != n_requests or shed or not all(r.done for r in
                                                  done.values()):
        raise RuntimeError(f"served {len(done)}/{n_requests} requests "
                           f"(shed {shed})")
    return done, rows, dispatcher


def stream_summary(done, rows, ds, tenants):
    import numpy as np
    out = {}
    for p, tenant in sorted(tenants.items()):
        rs = [r for r in done.values() if r.model == tenant]
        labels = [r.generated[0] for r in rs]
        out[p] = (len(rs), accuracy(labels, ds.y_test[rows[[r.rid for r in
                                                             rs]]]),
                  float(np.mean([r.hops[0] for r in rs])))
    return out


def phase_serve(registry, tenants, ds, *, n_requests=4000, n_slots=256,
                seed=0):
    import jax
    done, rows, _ = serve_stream(registry, tenants, ds,
                                 n_requests=n_requests, n_slots=n_slots,
                                 devices=jax.devices()[:1], seed=seed)
    s = stream_summary(done, rows, ds, tenants)
    log(f"[serve] completed={len(done)}/{n_requests} shed=0 slots={n_slots} "
        "replicas=1 " + " ".join(
            f"{p}: n={n} acc={a:.4f} mean_hops={h:.3f}"
            for p, (n, a, h) in s.items()))
    return s


def maxdiff(proba):
    import numpy as np
    top = np.sort(np.asarray(proba, np.float64), axis=-1)
    return top[..., -1] - top[..., -2]


def check_agreement(got, want, threshold: float, n_groves: int) -> dict:
    """The fused-vs-reference contract on one batch.

    threshold > 1: every grove votes, so hops must be identical (all G)
    and probabilities within PROBA_TOL.  A real gate: labels agree on at
    least MIN_LABEL_AGREEMENT of lanes, and every lane where hops or label
    differ sits on a knife edge — the margin where one side stopped is
    within GATE_TOL of the gate, or the final top-2 is a tie."""
    import numpy as np
    hops_g, hops_w = np.asarray(got.hops), np.asarray(want.hops)
    lab_g, lab_w = np.asarray(got.label), np.asarray(want.label)
    p_g, p_w = np.asarray(got.proba), np.asarray(want.proba)
    n = len(lab_w)
    if threshold > 1:
        max_err = float(np.abs(p_g - p_w).max())
        if not (hops_g == n_groves).all() or not (hops_w == n_groves).all():
            raise AssertionError("threshold > 1 but some lane stopped early")
        if max_err > PROBA_TOL:
            raise AssertionError(f"probabilities differ by {max_err:.3g}")
        return {"lanes": n, "hops_equal": int((hops_g == hops_w).sum()),
                "max_proba_err": max_err,
                "label_agree": int((lab_g == lab_w).sum())}
    differ = (hops_g != hops_w) | (lab_g != lab_w)
    agree = int((lab_g == lab_w).sum())
    edge = (np.minimum(np.abs(maxdiff(p_g) - threshold),
                       np.abs(maxdiff(p_w) - threshold)) <= GATE_TOL)
    tie = np.minimum(maxdiff(p_g), maxdiff(p_w)) <= GATE_TOL
    off_edge = int((differ & ~edge & ~tie).sum())
    if agree < MIN_LABEL_AGREEMENT * n:
        raise AssertionError(f"labels agree on {agree}/{n} lanes")
    if off_edge:
        raise AssertionError(f"{off_edge} lanes differ away from the gate")
    return {"lanes": n, "label_agree": agree,
            "hops_equal": int((hops_g == hops_w).sum()),
            "differ": int(differ.sum()), "off_edge": off_edge}


def compare_backends(pack, x, *, key, backend="fused", mesh=None,
                     tag="check", thresholds=(1.1, 0.3)):
    """``FogEngine(pack, backend=...)`` against the reference backend on the
    same batch, key and start groves, at each threshold.  (The ring draws
    its starts stratified over its shards; the reference is then run on
    those same starts.)"""
    import jax
    from repro.core.engine import FogEngine, _eval_core, sample_starts
    from repro.core.policy import NO_BUDGET, FogPolicy
    parts = []
    for thr in thresholds:
        pol = FogPolicy(threshold=thr, max_hops=pack.n_groves)
        if mesh is None:
            got = FogEngine(pack, backend=backend).eval(x, key, policy=pol)
            want = FogEngine(pack, backend="reference").eval(x, key,
                                                             policy=pol)
        else:
            got = FogEngine(pack, backend=backend, mesh=mesh).eval(
                x, key, policy=pol)
            start = sample_starts(key, x.shape[0], pack.n_groves,
                                  mesh.shape["grove"])
            want = _eval_core(pack, x, start, thr, NO_BUDGET, pack.n_groves,
                              "reference", None, False)
        jax.block_until_ready((got.proba, want.proba))
        r = check_agreement(got, want, thr, pack.n_groves)
        parts.append(f"thr={thr}: " + " ".join(f"{k}={v}"
                                                for k, v in r.items()))
    log(f"[{tag}] {backend} vs reference {pack.precision} "
        + " | ".join(parts))


def phase_check(packs, ds, *, seed=0, tag="check"):
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(ds.x_test)
    for p in ("fp32", "int8"):
        compare_backends(packs[p], x, key=jax.random.key(seed), tag=tag)


def phase_wide(ds, *, n_trees=16, grove_size=2, depth=12, seed=0):
    """The widest field the repo builds (isolet signature), host-trained,
    served fp32 and int8 through the fused backend and checked against
    the reference."""
    from repro.forest.pack import ForestPack
    clf = phase_train(ds, n_trees=n_trees, grove_size=grove_size,
                      depth=depth, trainer="host", seed=seed, tag="wide")
    packs = {p: ForestPack.from_groves(clf.gc_, p) for p in ("fp32", "int8")}
    log("[wide] tables " + " ".join(f"{p}={k.table_bytes}B"
                                    for p, k in packs.items()))
    phase_check(packs, ds, seed=seed, tag="wide")


def replica_placement(dispatcher) -> str:
    parts = []
    for i, fn in enumerate(dispatcher.replicas):
        devs = {k: sorted(d.id for d in v.devices())
                for k, v in fn.state.items() if v is not None}
        parts.append(f"r{i}@{dispatcher.devices[i].id}:{devs}")
    return " ".join(parts)


def phase_replicas(registry, tenants, ds, *, n_chips=4, n_requests=4000,
                   n_slots=1024, seed=0):
    """The same stream on ``n_chips`` one-chip replicas and on one chip.
    Every grove votes: identical labels and hops == G per request.  At a
    gate the replicas draw their own start groves, so accuracy must agree
    within 0.5% absolute."""
    import jax
    import numpy as np
    from repro.launch.mesh import serve_devices
    G = registry.load(tenants["fp32"])[0].n_groves
    for thr in (1.1, 0.3):
        runs = {}
        for n in (1, n_chips):
            t0 = time.perf_counter()
            done, rows, disp = serve_stream(
                registry, tenants, ds, n_requests=n_requests,
                n_slots=n_slots,
                devices=serve_devices(n) if n > 1 else jax.devices()[:1],
                threshold=thr, seed=seed)
            runs[n] = (done, rows, time.perf_counter() - t0)
            if n > 1:
                log(f"[replicas] placement {replica_placement(disp)}")
        (d1, rows, s1), (dn, _, sn) = runs[1], runs[n_chips]
        lab1 = np.asarray([d1[i].generated[0] for i in range(n_requests)])
        labn = np.asarray([dn[i].generated[0] for i in range(n_requests)])
        hopsn = np.asarray([dn[i].hops[0] for i in range(n_requests)])
        acc1, accn = accuracy(lab1, ds.y_test[rows]), accuracy(
            labn, ds.y_test[rows])
        if thr > 1:
            if (lab1 != labn).any() or (hopsn != G).any():
                raise AssertionError(
                    f"{int((lab1 != labn).sum())} labels differ, "
                    f"{int((hopsn != G).sum())} requests with hops != {G}")
        elif abs(acc1 - accn) > 0.005:
            raise AssertionError(f"accuracy {accn:.4f} on {n_chips} chips "
                                 f"vs {acc1:.4f} on 1")
        log(f"[replicas] thr={thr} requests={n_requests} slots={n_slots} "
            f"1chip: acc={acc1:.4f} wall_s={s1:.3f} | {n_chips}chips: "
            f"acc={accn:.4f} wall_s={sn:.3f} labels_equal="
            f"{int((lab1 == labn).sum())} hops_eq_G={int((hopsn == G).sum())}")


def phase_ring(packs, ds, *, n_chips=4, seed=0):
    import jax
    import jax.numpy as jnp
    mesh = jax.make_mesh((n_chips,), ("grove",))
    x = jnp.asarray(ds.x_test)
    x = x[: x.shape[0] - x.shape[0] % n_chips]
    for p in ("fp32", "int8"):
        compare_backends(packs[p], x, key=jax.random.key(seed),
                         backend="ring", mesh=mesh, tag="ring")


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def require_tpu(n_chips: int):
    """The device list when JAX sees at least ``n_chips`` TPU chips; else
    a reason to refuse."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX runs on {devs[0].platform}"
    if len(devs) < n_chips:
        return None, f"--chips {n_chips} needs {n_chips} chips, JAX sees " \
                     f"{len(devs)}"
    return devs, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (replicas, ring)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are missing ({SRC})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    devs, why = require_tpu(args.chips)
    if devs is None:
        print(f"chip_smoke: refusing to run: {why}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()          # read at the first compile
    from repro.data import make_dataset

    t0 = time.perf_counter()
    pen = make_dataset("penbased")
    clf = phase_train(pen, seed=args.seed,
                      tag="train" if args.chips == 1 else "setup")
    registry, packs, tenants = phase_pack_publish(
        clf, ROOT / ".smoke_registry")
    if args.chips == 1:
        phase_serve(registry, tenants, pen, seed=args.seed)
        phase_check(packs, pen, seed=args.seed)
        phase_wide(make_dataset("isolet"), seed=args.seed)
    else:
        phase_replicas(registry, tenants, pen, n_chips=args.chips,
                       seed=args.seed)
        phase_ring(packs, pen, n_chips=args.chips, seed=args.seed)
    log(f"[done] wall_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
