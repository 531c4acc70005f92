"""Serving driver: continuous batching + optional FoG early-exit decode.

    python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        --requests 8 [--fog --thresh 0.3]

``--smoke`` serves the reduced config on host devices; the full config +
production mesh path goes through serve/decode.make_serve_step (the same
functions the dry-run lowers).

Each device holds one replica (:class:`~repro.serve.dispatch.HostReplica`):
its own params copy and the KV caches for a fixed span of slots.
``--devices N`` serves data-parallel over N of them: the batcher's router
places each refill burst on the replica with the most free slots, and each
step fans out through a :class:`~repro.serve.dispatch.DeviceDispatcher`
(on CPU, export ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
first).
``--max-queue`` / ``--shed-policy`` expose the admission-control knobs.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.core.policy import BACKENDS, PRECISIONS, FogPolicy
from repro.data.lm_data import DataConfig, batch_at_step
from repro.models import transformer as T
from repro.models.fog_exit import decode_step_fog, grove_boundaries, lm_hop_energy
from repro.serve.governor import EnergyGovernor, lane_pricer
from repro.serve.scheduler import ContinuousBatcher, Request


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI (a function so tests can assert the choices stay in
    sync with the engine's registries — see the --fog-backend regression)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM architecture to serve (required unless "
                         "--registry selects forest serving)")
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="serve forest models from a ModelRegistry "
                         "directory instead of an LM: multi-tenant "
                         "(model, version, precision)-bucketed dispatch "
                         "through a VMEM-budgeted PackCache")
    ap.add_argument("--tenant", action="append", default=None,
                    help="registry tenant(s) to drive demo traffic at "
                         "(repeatable; default: every published tenant)")
    ap.add_argument("--cache-budget-mb", type=float, default=64.0,
                    help="PackCache VMEM byte budget for resident packed "
                         "tables (registry mode)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=160)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--fog", action="store_true")
    ap.add_argument("--fog-backend", default="reference",
                    choices=list(BACKENDS),
                    help="engine backend for the exit gate (kernel-flavored "
                         "choices route the pallas top-2 margin kernel; "
                         "'ring' additionally needs a grove mesh)")
    ap.add_argument("--thresh", type=float, default=0.3)
    ap.add_argument("--fog-precision", default=None,
                    choices=list(PRECISIONS),
                    help="default FogPolicy precision stamped on the "
                         "batcher (forest-backed decode_fns read it to pick "
                         "their packed tables; this LM layer-grove gate has "
                         "no forest tables and ignores it); requests may "
                         "override per-policy — the batcher dispatches one "
                         "program per precision group")
    ap.add_argument("--hop-budget", type=int, default=None,
                    help="per-request grove budget (anytime decoding cap)")
    ap.add_argument("--energy-budget-nj", type=float, default=None,
                    help="serving SLO: rolling nJ/classification target — "
                         "installs an EnergyGovernor that walks a "
                         "threshold-tightening / hop-capping ladder when "
                         "the rolling estimate breaches the budget "
                         "(energy priced by the LM layer-grove FLOP proxy, "
                         "models/fog_exit.lm_hop_energy)")
    ap.add_argument("--devices", type=int, default=1,
                    help="data-parallel serving: replicate params over the "
                         "first N local devices, each owning a fixed span "
                         "of slots; the scheduler routes each refill burst "
                         "to the replica with the most free slots (CPU: "
                         "export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission control: bound the request queue "
                         "(default unbounded)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "oldest"],
                    help="who is shed when the queue is full")
    ap.add_argument("--sync", action="store_true",
                    help="registry mode: synchronous packed step instead "
                         "of the default double-buffered pipeline (host "
                         "bookkeeping overlapped with device compute)")
    ap.add_argument("--telemetry-every", type=int, default=None,
                    help="replay buffered telemetry every k harvests "
                         "instead of per step (default 8 with --registry, "
                         "else 1)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _splice_row(batch_leaf, row_leaf, slot: int, n_slots: int):
    """Write a 1-row prefill cache leaf into lane ``slot`` of a batched
    cache leaf (axis found by its ``n_slots`` extent)."""
    for ax in range(batch_leaf.ndim):
        if batch_leaf.shape[ax] == n_slots and row_leaf.shape[ax] == 1:
            sl = [slice(None)] * batch_leaf.ndim
            sl[ax] = slice(slot, slot + 1)
            for sax in range(batch_leaf.ndim):
                if sax != ax and row_leaf.shape[sax] != batch_leaf.shape[sax]:
                    sl[sax] = slice(0, row_leaf.shape[sax])
            return batch_leaf.at[tuple(sl)].set(row_leaf)
    return batch_leaf


def _serve_registry(args) -> None:
    """Registry demo: N tenants' live forests behind one batcher, mixed
    per-request precisions, per-tenant energy governors when an SLO is
    given.  Feature rows are synthetic (the demo exercises the serving
    plane, not the datasets)."""
    from repro.registry import ModelRegistry, PackCache
    from repro.serve.dispatch import DeviceDispatcher, ForestReplicaServer
    from repro.serve.governor import TenantLedger, default_ladder

    registry = ModelRegistry(args.registry)
    tenants = args.tenant or registry.tenants()
    if not tenants:
        raise SystemExit(f"registry {args.registry} has no tenants; "
                         "publish one with ModelRegistry.publish first")
    cache = PackCache(registry,
                      budget_bytes=int(args.cache_budget_mb * 2**20))
    pack0, extra0 = registry.load(tenants[0])
    n_features = int(extra0.get("n_features_in",
                                int(np.asarray(pack0.feature).max()) + 1))
    server = ForestReplicaServer(None, n_features,
                                 backend=args.fog_backend
                                 if args.fog_backend != "reference"
                                 else "fused",
                                 registry=registry, cache=cache)
    if args.devices > 1:
        from repro.launch.mesh import serve_devices
        devices = serve_devices(args.devices)
    else:
        devices = jax.devices()[:1]
    # the packed replica protocol: device-resident slot state + fused
    # dispatch, which is what makes the pipelined step safe
    dispatcher = DeviceDispatcher(server.packed_factory, devices)

    default_policy = FogPolicy(threshold=args.thresh,
                               hop_budget=args.hop_budget,
                               precision=args.fog_precision)
    ledger = None
    if args.energy_budget_nj is not None:
        ledger = TenantLedger()
        for t in tenants:
            model = server.energy_model(tenant=t)
            ledger.add(t, EnergyGovernor(
                default_ladder(default_policy, model,
                               args.energy_budget_nj),
                args.energy_budget_nj, model=model,
                window=max(args.slots * 4, 16)))
    batcher = ContinuousBatcher(args.slots, None, server.prefill, eos_id=-1,
                                default_policy=default_policy,
                                governor=ledger, dispatcher=dispatcher,
                                registry=registry,
                                max_queue=args.max_queue,
                                shed_policy=args.shed_policy,
                                pipeline=not args.sync,
                                telemetry_every=(args.telemetry_every
                                                 if args.telemetry_every
                                                 is not None else 8))
    rng = np.random.default_rng(args.seed)
    admitted = 0
    for rid in range(args.requests):
        t = tenants[rid % len(tenants)]
        admitted += batcher.submit(Request(
            rid=rid, prompt=rng.standard_normal(n_features), model=t,
            max_new_tokens=1))
    t0 = time.time()
    done = batcher.run()
    dt = time.time() - t0
    print(f"[serve] registry {args.registry}: {len(done)}/{admitted} "
          f"requests over {len(tenants)} tenants in {dt:.2f}s")
    for t in tenants:
        v = registry.live_version(t)
        st = registry.stats_for(t, v)
        print(f"  {t} v{v}: {st.n_events} events, "
              f"mean hops {st.mean_hops:.2f}"
              + (f", {st.mean_energy_nj:.3f} nJ/event"
                 if st.has_energy else ""))
    print(f"[serve] cache {cache.summary()}")
    if ledger is not None:
        print(f"[serve] ledger\n{ledger.summary()}")


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()
    if args.registry is not None:
        _serve_registry(args)
        return
    if args.arch is None:
        ap.error("--arch is required (or pass --registry DIR for "
                 "forest-registry serving)")
    if args.energy_budget_nj is not None and not args.fog:
        # without --fog the decode step reports no hop telemetry: the
        # governor would be a silent no-op, which is worse than an error
        ap.error("--energy-budget-nj requires --fog (the governor needs "
                 "the FoG decode path's hop telemetry)")
    if args.devices > 1 and args.slots % args.devices:
        ap.error(f"--slots {args.slots} must divide evenly over "
                 f"--devices {args.devices} (fixed per-device spans)")
    _serve_lm(args)


def _serve_lm(args) -> ContinuousBatcher:
    """LM serving: one :class:`~repro.serve.dispatch.HostReplica` per
    device, each with its own params copy and the KV caches for its span
    of slots (lanes never migrate, so a prefill touches exactly one
    device's cache).  Returns the drained batcher."""
    from repro.launch.mesh import serve_devices
    from repro.serve.dispatch import DeviceDispatcher, HostReplica

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    if cfg.frontend:
        raise SystemExit(f"{cfg.name}: stub-frontend archs serve via "
                         "precomputed embeddings; use serve/decode.py directly")
    params = T.init_params(cfg, jax.random.key(args.seed), jnp.float32)

    default_policy = FogPolicy(threshold=args.thresh,
                               hop_budget=args.hop_budget,
                               backend=args.fog_backend,
                               precision=args.fog_precision)

    governor = None
    if args.energy_budget_nj is not None:
        model = lm_hop_energy(cfg)
        t = args.thresh
        # quality-descending LM ladder: tighten the exit threshold, then
        # cap hops at whatever the budget affords (int8 rungs are moot —
        # the layer-grove gate has no packed forest tables).  An explicit
        # --hop-budget stays a ceiling on every rung: the bottom rung may
        # only TIGHTEN it, or the ladder would stop descending
        cap = model.hops_within(args.energy_budget_nj * 1e3)
        if args.hop_budget is not None:
            cap = min(cap, args.hop_budget)
        ladder = [default_policy,
                  default_policy.replace(threshold=t * 0.5),
                  default_policy.replace(threshold=t * 0.25),
                  default_policy.replace(threshold=t * 0.25,
                                         hop_budget=cap)]
        governor = EnergyGovernor(ladder, args.energy_budget_nj,
                                  model=model, window=max(args.slots * 4, 16))
    price = lane_pricer(governor)
    devices = serve_devices(args.devices)
    span = args.slots // args.devices
    n_groves = len(grove_boundaries(cfg))
    states: dict[int, dict] = {}

    def prefill_fn(slot: int, prompt: np.ndarray) -> int:
        _, c = T.prefill(params, cfg,
                         tokens=jnp.asarray(prompt)[None, :],
                         max_seq=args.max_seq)
        st = states[slot // span]
        st["caches"] = jax.tree.map(
            lambda b, r: _splice_row(b, r, slot % span, span),
            st["caches"], jax.device_put(c, st["device"]))
        return len(prompt)

    def factory(index, device, span):
        params_d = jax.device_put(params, device)
        st = states[index] = {"device": device, "caches": jax.device_put(
            T.cache_init(cfg, span, args.max_seq, jnp.float32), device)}

        def decode(tokens, lengths, policy):
            # the span shares one position counter: its longest lane
            length = jnp.int32(int(np.asarray(lengths).max()))
            if args.fog:
                logits, st["caches"], hops = decode_step_fog(
                    params_d, cfg, tokens, st["caches"], length, policy)
                return logits, hops
            logits, st["caches"] = T.decode_step(params_d, cfg, tokens,
                                                 st["caches"], length)
            return logits, None     # dense: every grove, every token

        return HostReplica(decode, prefill_fn, span, index=index,
                           device=device, price=price,
                           policy=default_policy, n_groves=n_groves)

    batcher = ContinuousBatcher(args.slots, None, prefill_fn, eos_id=-1,
                                default_policy=default_policy,
                                governor=governor,
                                dispatcher=DeviceDispatcher(factory, devices),
                                max_queue=args.max_queue,
                                shed_policy=args.shed_policy,
                                telemetry_every=(args.telemetry_every
                                                 if args.telemetry_every
                                                 is not None else 1))
    dcfg = DataConfig(cfg.vocab_size, 32, 8, seed=args.seed + 7)
    admitted = 0
    for rid in range(args.requests):
        prompt = batch_at_step(dcfg, rid)["tokens"][0, :24] % cfg.vocab_size
        admitted += batcher.submit(Request(rid=rid, prompt=prompt,
                                           max_new_tokens=args.max_new))
    t0 = time.time()
    done = batcher.run()
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)}/{admitted} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens / dt:.1f} tok/s)"
          + (f" on {args.devices} devices" if args.devices > 1 else ""))
    if batcher.stats.n_shed:
        print(f"[serve] admission shed {batcher.stats.n_shed}/"
              f"{batcher.stats.n_offered} "
              f"({100 * batcher.stats.shed_rate:.1f}%)")
    if args.fog:
        for r in sorted(done, key=lambda r: r.rid):
            h = np.asarray(r.hops, np.float64)
            print(f"  req {r.rid}: groves/token {h.mean():.2f} "
                  f"(flops frac {h.mean() / n_groves:.2f})")
        print(f"[serve] fleet {batcher.stats.summary(n_groves)}")
        if governor is not None:
            print(f"[serve] governor {governor.summary()}")
    return batcher


if __name__ == "__main__":
    main()
