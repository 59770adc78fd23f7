"""Parsa expert placement for MoE serving (DESIGN §3.2): build the
token-group × expert affinity graph from measured routing counts of a
reduced deepseek-family model, then place experts to shrink the all-to-all.

``build_expert_placement`` runs the partition through the unified
``repro.api.partition()`` facade (host backend by default — pass
``backend=`` to move it on-device).

    PYTHONPATH=src python examples/moe_placement.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.moe_placement import alltoall_traffic, build_expert_placement
from repro.models.model import build_model
from repro.models.moe import apply_moe
from repro.runtime import enable_compile_cache

enable_compile_cache()
cfg = get_config("deepseek-v2-236b").reduced(num_experts=16,
                                             num_experts_per_tok=4)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
k = 4

print("collecting routing statistics from the reduced model ...")
rng = np.random.default_rng(0)
groups = []
moe_params = jax.tree.map(lambda a: a[0], params["stack"])["moe"]
# token groups come from a handful of domains (code/news/dialog/...): groups
# of the same domain route to the same expert family — the structure Parsa
# exploits.  6 domains × ~5 groups each.
domains = rng.normal(0, 1, (6, cfg.d_model)) * 2.5
for g in range(32):
    center = domains[g % 6]
    x = jnp.asarray(center + rng.normal(0, 0.25, (1, 16, cfg.d_model)),
                    jnp.float32)
    _, aux = apply_moe(moe_params, x, cfg, dtype=jnp.float32, return_aux=True)
    groups.append(np.asarray(aux["expert_counts"]))
counts = np.stack(groups)
print(f"  routing matrix: {counts.shape} (groups × experts)")

pl = build_expert_placement(counts, k)
t = alltoall_traffic(counts, pl)
print(f"\nall-to-all crossing tokens, round-robin experts: "
      f"{t['crossing_tokens_roundrobin']}")
print(f"all-to-all crossing tokens, Parsa placement   : "
      f"{t['crossing_tokens_parsa']}")
print(f"reduction: {t['reduction']*100:.0f}%")
print(f"expert→shard: {pl.expert_to_shard.tolist()}")
