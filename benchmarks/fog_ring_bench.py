"""FoG ring on a device mesh (§3.2.2 scaled): per-hop wall time + traffic.

Runs the FogEngine ring backend (shard_map + ppermute) on 8 devices and
reports lane occupancy decay (how fast confident lanes die -> the load
self-balancing the paper's queue priority scheme provides), for both the
classic 1-grove-per-shard ring and the generalized multiple-groves-per-shard
placement.  ``benchmarks.run`` runs it in its own process with eight host
devices forced (``--xla_force_host_platform_device_count=8``, which only
the CPU platform reads).
"""
from __future__ import annotations

import time


def run() -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FogEngine, FogPolicy, split
    from repro.data import make_dataset
    from repro.forest import TrainConfig, train_random_forest

    ds = make_dataset("penbased")
    rf = train_random_forest(ds.x_train, ds.y_train, ds.n_classes,
                             TrainConfig(n_trees=16, max_depth=8, seed=1))
    gc = split(rf, 2)
    x = jnp.asarray(ds.x_test[:1024] if len(ds.x_test) >= 1024 else ds.x_test)
    rows = []
    for n_shards in [8, 4]:
        mesh = jax.make_mesh((n_shards,), ("grove",))
        engine = FogEngine(gc, backend="ring", mesh=mesh)
        for thresh in [0.1, 0.3, 0.5]:
            t0 = time.perf_counter()
            res = engine.eval(x, jax.random.key(0),
                              policy=FogPolicy(threshold=thresh, max_hops=8))
            res.proba.block_until_ready()
            dt = (time.perf_counter() - t0) * 1e6
            hops = np.asarray(res.hops)
            acc = (np.asarray(res.label) == ds.y_test[: len(hops)]).mean()
            occ = [float((hops > j).mean()) for j in range(8)]
            rows.append(f"CSV,fog_ring,shards={n_shards},thresh={thresh},"
                        f"us={dt:.0f},acc={acc:.4f},"
                        f"mean_hops={hops.mean():.2f},occupancy="
                        + "|".join(f"{o:.2f}" for o in occ))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
