"""Serve a small LM with batched requests + FoG early-exit decoding.

    PYTHONPATH=src python examples/serve_fog_lm.py

Demonstrates the continuous-batching scheduler driving decode_step_fog
with MIXED-QOS traffic: every request carries its own FogPolicy (threshold
+ hop budget), the host replica resolves them into per-lane vectors, and
one compiled decode step serves the whole batch.  The decode step runs
behind the serving plane's replica protocol
(``repro.serve.dispatch.host_replica_factory``), the same dispatch /
harvest cycle the forest's replicas serve through.  Per-request grove usage
(hops) is the LM analogue of the paper's energy meter — easy tokens exit
after 1 grove, hard tokens use the full stack, and budget-capped requests
never exceed their energy contract.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core.policy import FogPolicy
from repro.data.lm_data import DataConfig, batch_at_step
from repro.models import transformer as T
from repro.models.fog_exit import decode_step_fog, grove_boundaries
from repro.serve.dispatch import DeviceDispatcher, host_replica_factory
from repro.serve.scheduler import ContinuousBatcher, Request

cfg = smoke_config("tinyllama-1.1b").scaled(n_layers=4, fog_groups=4)
params = T.init_params(cfg, jax.random.key(0), jnp.float32)
# untrained demo weights -> tiny logit margins; 0.01 shows the per-token
# variation. A trained model exits much earlier (benchmarks/lm_fog_exit.py).
N_SLOTS, MAX_SEQ, THRESH = 4, 160, 0.01

caches = T.cache_init(cfg, N_SLOTS, MAX_SEQ, jnp.float32)


def prefill_fn(slot: int, prompt: np.ndarray) -> int:
    # per-slot prefill: run the prompt row, splice its cache into the batch
    _, c = T.prefill(params, cfg, tokens=jnp.asarray(prompt)[None, :],
                     max_seq=MAX_SEQ)
    def splice(batch_leaf, row_leaf):
        return batch_leaf.at[..., slot : slot + 1, :row_leaf.shape[-2], :] \
            .set(row_leaf[..., 0:1, :, :]) \
            if batch_leaf.ndim >= 3 else batch_leaf
    global caches
    caches = jax.tree.map(
        lambda b, r: _splice_cache(b, r, slot), caches, c)
    return len(prompt)


def _splice_cache(batch_leaf, row_leaf, slot):
    # leaves: [n_blocks, B, S, ...] (stack) or [B, S, ...] (prefix);
    # mamba states [.., B, H, P, N]; conv tails [.., B, K-1, C]
    b_axis = 1 if batch_leaf.ndim == row_leaf.ndim and \
        batch_leaf.shape[0] != row_leaf.shape[0] * 0 + batch_leaf.shape[0] else 0
    # find the axis where batch_leaf has N_SLOTS and row_leaf has 1
    for ax in range(batch_leaf.ndim):
        if batch_leaf.shape[ax] == N_SLOTS and row_leaf.shape[ax] == 1:
            sl = [slice(None)] * batch_leaf.ndim
            sl[ax] = slice(slot, slot + 1)
            # seq axis may be shorter in row_leaf (prefill length)
            for sax in range(batch_leaf.ndim):
                if sax != ax and row_leaf.shape[sax] != batch_leaf.shape[sax]:
                    sl[sax] = slice(0, row_leaf.shape[sax])
            return batch_leaf.at[tuple(sl)].set(row_leaf)
    return batch_leaf


def decode_fn(tokens, lengths, policy):
    global caches
    # the batch shares one position counter in this demo: use max length;
    # policy carries the per-lane thresholds/budgets of the lanes' requests
    length = jnp.int32(int(lengths.max()))
    logits, caches, hops = decode_step_fog(params, cfg, tokens, caches,
                                           length, policy)
    return logits, hops


# three QoS tiers sharing ONE continuous batch: premium (hop until really
# confident), standard, and a budget tier capped at 2 groves per token
TIERS = {
    "premium": FogPolicy(threshold=0.05),
    "standard": FogPolicy(threshold=THRESH),
    "budget": FogPolicy(threshold=0.05, hop_budget=2),
}
dispatcher = DeviceDispatcher(
    host_replica_factory(decode_fn, prefill_fn, policy=TIERS["standard"]),
    jax.devices()[:1])
batcher = ContinuousBatcher(N_SLOTS, None, prefill_fn, eos_id=-1,
                            default_policy=TIERS["standard"],
                            dispatcher=dispatcher)
rng = np.random.default_rng(0)
dcfg = DataConfig(cfg.vocab_size, 32, 8, seed=7)
tier_of = {}
for rid in range(8):
    prompt = batch_at_step(dcfg, rid)["tokens"][0, :24]
    tier = list(TIERS)[rid % len(TIERS)]
    tier_of[rid] = tier
    batcher.submit(Request(rid=rid, prompt=prompt, max_new_tokens=16,
                           policy=TIERS[tier]))

done = batcher.run(max_steps=200)
n_groups = len(grove_boundaries(cfg))
print(f"served {len(done)} requests, {n_groups} groves, mixed QoS tiers")
for req in sorted(done, key=lambda r: r.rid):
    h = np.asarray(req.hops, np.float64)
    print(f"  req {req.rid} [{tier_of[req.rid]:>8}]: "
          f"{len(req.generated)} tokens, "
          f"mean groves/token {h.mean():.2f}  "
          f"(flops frac vs full stack: {h.mean() / n_groups:.2f})")
