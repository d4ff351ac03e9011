"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler ships with ``libtpu``: these tests lower the lane-word
Pallas kernels with ``interpret=False`` at the widths the scale-20 serving
run uses, one vmap-emulated msBFS block with the kernels routed in, and
one 4-device sharded block, and compile them for a ``v5e:2x2`` topology.
Nothing runs: a pass says the chip's compiler accepts the program (block
layouts, VMEM, device memory), never that its answers or times are right.

The topology is described inside a module-scoped fixture (never while a
module is imported), so under several pytest workers only the worker that
runs this file loads ``libtpu``. The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import bfs as B
from repro.core import engine as E
from repro.core import msbfs as M
from repro.core.comm import CommConfig
from repro.core.partition import partition_graph
from repro.graphs.rmat import rmat_graph
from repro.kernels import ops
from repro.kernels.ell_pull_multi import ell_pull_multi
from repro.kernels.ell_pull_payload import ell_pull_payload
from repro.kernels.mask_reduce import mask_reduce, payload_min_fold

# Widths of the scale-20 serving run (chip_smoke.py): W=32 lanes (one
# uint32 lane word), pull_chunk=32 parent columns, p=4 partitions of
# n_local = 2**20 / 4 rows; the delegate folds span p=4 partials of the
# 60,561 delegates that th=64 selects on the seed-0 graph.
W, NW, CHUNK, P4 = 32, 1, 32, 4
N_LOCAL = 2 ** 20 // P4
N_DELEGATE_WORDS = 60561
CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(tree, sharding_of):
    return jax.tree.map(
        lambda x: _spec(np.shape(x), np.asarray(x).dtype, sharding_of(x)),
        tree)


_KERNELS = {
    "ell_pull_multi": (
        lambda a, b, c: ell_pull_multi(a, b, c, interpret=False),
        [((N_LOCAL, CHUNK), jnp.int32), ((N_LOCAL, NW), jnp.uint32),
         ((N_LOCAL, NW), jnp.uint32)]),
    "ell_pull_payload": (
        lambda a, b, c, d: ell_pull_payload(a, b, c, d, interpret=False),
        [((N_LOCAL, CHUNK), jnp.int32), ((N_LOCAL, W), jnp.int32),
         ((N_LOCAL, CHUNK), jnp.int32), ((N_LOCAL, W), jnp.int32)]),
    "mask_reduce": (
        lambda a, b: mask_reduce(a, b, interpret=False),
        [((P4, N_DELEGATE_WORDS), jnp.uint32), ((N_DELEGATE_WORDS,), jnp.uint32)]),
    "mask_reduce_fold": (
        lambda a, b: mask_reduce(a, b, interpret=False, with_count=False),
        [((P4, N_DELEGATE_WORDS), jnp.uint32), ((N_DELEGATE_WORDS,), jnp.uint32)]),
    "payload_min_fold": (
        lambda a, b: payload_min_fold(a, b, interpret=False),
        [((P4, N_DELEGATE_WORDS * W), jnp.int32),
         ((N_DELEGATE_WORDS * W,), jnp.int32)]),
    "payload_min_fold_fold": (
        lambda a, b: payload_min_fold(a, b, interpret=False,
                                      with_count=False),
        [((P4, N_DELEGATE_WORDS * W), jnp.int32),
         ((N_DELEGATE_WORDS * W,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _KERNELS[name]
    args = [_spec(s, dt, one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # inputs + temporaries of one call fit the chip's 16 GB with room for
    # the graph and lane state that share it
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 * 2 ** 30


@pytest.fixture(scope="module")
def small_graph():
    pg = partition_graph(rmat_graph(12, seed=0), th=64, p_rank=2, p_gpu=2)
    return pg, B.device_view(pg), E.build_exchange_plan(pg)


def test_emulated_block_runs_compiled_kernels(small_graph, one_chip,
                                              no_persistent_cache,
                                              monkeypatch):
    """The vmap-emulated served block with ``kernel_pull="pallas"`` and the
    Pallas delegate fold lowers the kernels as Mosaic custom calls (not the
    interpreter) when the backend is a TPU."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    pg, pgv, plan = small_graph
    cfg = M.MSBFSConfig(n_queries=W, kernel_pull="pallas", payload=True,
                        comm=CommConfig(local_fold="pallas"))
    st = M.init_multi_state(pg, [0], cfg)
    block = M.make_msbfs_block_emulated(cfg, 8, donate=True)
    args = (_specs(pgv, lambda _: one_chip), _specs(plan, lambda _: one_chip),
            _specs(st, lambda _: one_chip), _spec((W,), jnp.bool_, one_chip))
    text = block.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_block_compiles_for_four_chips(small_graph, topo,
                                               no_persistent_cache):
    """One shard_map block over the 2x2 mesh: each argument carries its
    partition's quarter, and the delegate combine / nn exchange become
    real collectives."""
    from jax.sharding import AxisType, Mesh

    pg, pgv, plan = small_graph
    mesh = Mesh(np.asarray(topo.devices[:P4]).reshape(2, 2),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    axes = ("data", "model")
    cfg = M.MSBFSConfig(n_queries=W)
    st = M.init_multi_state(pg, [0], cfg)

    def part(x):
        return NamedSharding(mesh, P(axes, *([None] * (np.ndim(x) - 1))))

    block = M.make_sharded_msbfs_block(mesh, axes, cfg, 8, donate=True)
    compiled = block.lower(
        _specs(pgv, part), _specs(plan, part), _specs(st, part),
        _spec((W,), jnp.bool_, NamedSharding(mesh, P()))).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "all-reduce" in text or "all-gather" in text


# ------------------------------------------------- persistent compile cache
_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
where = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == where, where
print(where)
print(DEFAULT_DIR)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(CHECKOUT / "src")] + ([env["PYTHONPATH"]]
                                   if env.get("PYTHONPATH") else []))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)], env=env,
        capture_output=True, text=True, check=True, timeout=120)
    return out.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the cache lands there and only
    there. Unset: the entry points use the fixed ``<checkout>/.jax_cache``
    (checked without compiling, so the checkout stays clean)."""
    where, default = _probe(tmp_path / "cache", True)
    assert where == str(tmp_path / "cache")
    assert os.listdir(where)                       # entries written
    assert default == str(CHECKOUT / ".jax_cache")

    where, default = _probe(None, False)
    assert where == default
