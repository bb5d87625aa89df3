"""The paper's technique inside the model: irregular MoE expert loads.

Routes a real batch through the reduced Mixtral router, takes the
per-expert load histogram (the m_i of the paper), and runs BOTH MoE
communication phases over 8 host devices:

* **dispatch** — tokens travel from their data shard to their expert's
  owner device through the composed TUW ``alltoallv`` (8 rooted scatter
  trees packed into permutation rounds);
* **combine** — per-expert token blocks gather back to the coordinator
  with the TUW gatherv tree;

comparing moved bytes against the padded regular alternatives.  Both
phases route through the autotuning ``repro.tuner.PlannerService``: the
service selects the schedule under its calibrated (alpha, beta), caches
the lowered plan by quantized size signature, and serves the repeated
dispatch signature of the second batch from the cache (no tree
construction — watch the hit counter).

Run WITHOUT setting XLA_FLAGS yourself — the script forces 8 host devices
for the shard_map demo and moves the slabs with the jnp reference data
plane (the compiled Pallas kernels need a TPU):

    PYTHONPATH=src python examples/moe_irregular.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config
from repro.core.composed import independent_scatter_bytes
from repro.core.jax_collectives import set_dataplane
from repro.models import init_params
from repro.models.moe import moe_apply
from repro.tuner import PlannerService

cfg = get_config("mixtral-8x7b").reduced()
params = init_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model),
                      jnp.float32)
moe_p = jax.tree.map(lambda a: a[0], params["body"][0]["ffn"])
_, aux = moe_apply(moe_p, x, cfg.moe)
loads = np.asarray(aux["load"])
E = cfg.moe.n_experts
print(f"routed {4 * 64} tokens x top-{cfg.moe.top_k} over "
      f"{E} experts; loads = {loads.tolist()} "
      f"(dropped {int(aux['dropped'])})")

set_dataplane("xla")
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
svc = PlannerService(mesh=mesh, axis_name="x", quantum=4)

# ---------------------------------------------------------------- dispatch
# 8-device layout: device j owns expert j (the reduced config has E=4
# experts, so devices E..7 own none — their columns are zero, exercising
# the scheduler's sparsity path); each device starts holding the slice of
# every expert's tokens that was routed FROM its data shard — an 8x8
# irregular size matrix S[i][j] = tokens of expert j sitting on shard i.
S = np.zeros((8, 8), np.int64)
for j, l in enumerate(loads[:8]):
    base, rem = divmod(int(l), 8)
    S[:, j] = base
    S[:rem, j] += 1
blocks = [[rng.standard_normal((int(S[i, j]), cfg.d_model)).astype(np.float32)
           for j in range(8)] for i in range(8)]
recv, plan = svc.alltoallv(blocks)
for j in range(8):
    want = np.concatenate([blocks[i][j] for i in range(8)],
                          axis=0).reshape(-1, cfg.d_model)
    np.testing.assert_allclose(recv[j], want)
pred = independent_scatter_bytes(S)
algo = svc.last_selection.chosen if svc.last_selection else "cached"
print(f"alltoallv dispatch over mesh{mesh.shape}: OK ({algo}), "
      f"{plan.tree_bytes_exact} rows moved in {plan.num_rounds} rounds "
      f"(TUW cost model predicted {pred}, padded {plan.tree_bytes_padded})")
pad_rows = 8 * 7 * int(S.max())  # regular alltoall: every block max-padded
print(f"padded all-to-all alternative: {pad_rows} rows "
      f"({pad_rows / max(plan.tree_bytes_padded, 1):.1f}x more)")

# a second batch routes the SAME per-expert loads (the steady-state MoE
# signature): the planner serves it from cache — no tree construction
h0, c0 = svc.plan_hits, svc.compiled_hits
blocks2 = [[rng.standard_normal((int(S[i, j]), cfg.d_model))
            .astype(np.float32) for j in range(8)] for i in range(8)]
recv2, plan2 = svc.alltoallv(blocks2)
assert plan2 is plan, "warm replan must reuse the cached plan object"
print(f"warm dispatch replan: plan cache hit (+{svc.plan_hits - h0}), "
      f"compiled executable hit (+{svc.compiled_hits - c0}), "
      f"plan identity stable")

# ----------------------------------------------------------------- combine
# expert outputs return to the expert-parallel coordinator: EP=4 experts x
# DP=2 token shards; gather all ragged half-shards with the TUW tree
shard_sizes = []
for l in loads:
    shard_sizes += [int(l) // 2, int(l) - int(l) // 2]
blocks = [rng.standard_normal((s, cfg.d_model)).astype(np.float32)
          for s in shard_sizes]
got, plan = svc.gatherv(blocks, root=0)
want = np.concatenate(blocks, axis=0)
np.testing.assert_allclose(got, want)
algo = svc.last_selection.chosen if svc.plan_misses else "cached"
print(f"TUW gatherv combine over mesh{mesh.shape}: OK ({algo}), "
      f"{plan.tree_bytes_exact} rows moved (padded {plan.tree_bytes_padded})")
pad_rows = 8 * 7 * max(int(l) for l in loads)
print(f"padded all-gather alternative: {pad_rows} rows "
      f"({pad_rows / max(plan.tree_bytes_padded, 1):.1f}x more)")
