"""Integration tests that need multiple XLA host devices: run in a
subprocess with XLA_FLAGS set before jax import (the main test process must
keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import bfs as B, engine as E
from repro.core.partition import partition_graph
from repro.core.oracle import bfs_levels
from repro.graphs.rmat import rmat_graph, pick_sources
from repro.graphs.synthetic import cora_like
from repro.launch.mesh import make_test_mesh
from repro.models import gnn as G
from repro.models.common import materialize
from repro.models.gnn import GraphBatch
from repro.train import gnn_batches as GB, gnn_dist as GD

mesh = make_test_mesh((2, 4), ("pod", "data"))
axes = ("pod", "data")
sh = lambda x: jax.device_put(x, NamedSharding(mesh, P(axes, *([None] * (np.ndim(x) - 1)))))

# ---- BFS under real shard_map matches the oracle
g = rmat_graph(11, seed=5)
pg = partition_graph(g, th=45, p_rank=2, p_gpu=4)
cfg = B.BFSConfig(max_iters=32, enable_do=True)
run = B.make_sharded_bfs(mesh, axes, cfg)
pgv_s = jax.tree.map(sh, B.device_view(pg))
src = int(pick_sources(g, 1, seed=3)[0])
out = jax.tree.map(np.asarray, run(pgv_s, jax.tree.map(sh, B.init_state(pg, src, cfg))))
assert np.array_equal(B.gather_levels(pg, out), bfs_levels(g, src)), "BFS mismatch"
assert out.nn_overflow.sum() == 0
print("BFS shard_map OK")

# ---- distributed GCN grads under shard_map == local reference
g2, feats, labels, mask = cora_like(n=96, avg_deg=4, d_feat=12, seed=3)
pg2 = partition_graph(g2, th=10, p_rank=2, p_gpu=4)
pgv2 = B.device_view(pg2)
plan = E.build_exchange_plan(pg2)
w = E.build_edge_weights(pg2, g2.out_degrees(), "sym")
batch = jax.tree.map(jnp.asarray, GB.gcn_batch(pg2, feats, labels, mask))
cfgG = G.GCNConfig(n_layers=2, d_in=12, d_hidden=8, n_classes=7)
params = materialize(G.gcn_param_specs(cfgG), 0)

def local(prm, pgl, pl, wl, bt):
    sq = lambda t: jax.tree.map(lambda x: x[0], t)
    gr = jax.grad(lambda q: GD.dist_gcn_loss(cfgG, q, sq(pgl), sq(pl), sq(wl), sq(bt), axes))(prm)
    return jax.lax.pmean(gr, axes)

in_specs = (jax.tree.map(lambda _: P(), params),
            *[jax.tree.map(lambda x: P(axes, *([None]*(x.ndim-1))), t)
              for t in (pgv2, plan, w, batch)])
gfn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                            out_specs=jax.tree.map(lambda _: P(), params), check_vma=False))
gdist = gfn(params, *jax.tree.map(sh, (pgv2, plan, w, batch)))
gb = GraphBatch(nodes=jnp.asarray(feats), senders=jnp.asarray(g2.src, jnp.int32),
                receivers=jnp.asarray(g2.dst, jnp.int32))
gref = jax.grad(lambda p: G.gcn_loss(cfgG, p, gb, jnp.asarray(labels), jnp.asarray(mask)))(params)
for k in gref:
    np.testing.assert_allclose(np.asarray(gdist[k]), np.asarray(gref[k]), rtol=3e-3, atol=3e-5)
print("GCN shard_map grads OK")
"""


@pytest.mark.slow
def test_shardmap_integration():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "BFS shard_map OK" in r.stdout
    assert "GCN shard_map grads OK" in r.stdout
