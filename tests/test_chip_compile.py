"""Main-path kernels compiled for a described TPU v5e, at real sizes.

Nothing runs: the installed TPU compiler compiles each kernel for a
chip that is described, not attached, which refuses what interpret
mode and the CPU backend accept (Mosaic tiling rules, SMEM/VMEM over
budget, a program that does not fit HBM). The topology is described
inside a module fixture, never at import: only one process may load
the TPU library, and every xdist worker imports this file.
"""
import os

import numpy as np
import pytest

#: One TPU v5e chip's HBM (Google Cloud "TPU v5e" documentation).
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sd(topo):
    """ShapeDtypeStruct factory placed on the described chip 0."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=one_chip)
    return make


def _compile(fn, args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    return compiled


def _wgl_args(sd, B, N, W, V, K1=32):
    return (sd((B, N), np.int8), sd((B, N), np.int8),
            sd((B, N, W), np.int8), sd((K1, V), np.int32))


@pytest.mark.parametrize("V,W,B", [
    (8, 8, 1024),          # the issue's headline shape
    (8, 16, 1024),         # widest class at the default 1024-row chunk
    (8, 18, 256),          # data1wide: 2^26 frontier words per chunk
])
def test_wgl_scan_compiles(sd, V, W, B):
    from jepsen_tpu.ops.linearize import get_kernel
    _compile(get_kernel(V, W, shared_target=True),
             _wgl_args(sd, B, 1024, W, V))


@pytest.mark.parametrize("W,n_frontier", [(17, 2), (18, 4)])
def test_frontier_sharded_compiles_on_four_chips(topo, W, n_frontier):
    """The frontier-sharded kernel on the described 2x2 host, at the
    four-chip cell's shape: 64 rows, 2^W masks split over
    ``n_frontier`` chips, hypercube exchanges and psums."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jepsen_tpu.parallel.frontier import frontier_sharded_kernel
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4 // n_frontier,
                                                   n_frontier),
                ("data", "frontier"))

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=NamedSharding(mesh, P(*spec)))
    B, N, V, K1 = 64, 256, 8, 32
    compiled = _compile(
        frontier_sharded_kernel(V, W, mesh),
        (arg((B, N), np.int8, "data"), arg((B, N), np.int8, "data"),
         arg((B, N, W), np.int8, "data"),
         arg((B, K1, V), np.int32, "data")))
    text = compiled.as_text()
    assert text.startswith("HloModule jit_frontier_check")
    assert "collective-permute" in text and "all-reduce" in text


@pytest.mark.parametrize("V,W,shared", [
    (8, 8, True), (64, 10, True), (64, 10, False), (8, 1, True)])
def test_pallas_wgl_compiles(sd, V, W, shared):
    from jepsen_tpu.ops.pallas_wgl import make_pallas_kernel
    kern = make_pallas_kernel(V, W, shared_target=shared,
                              interpret=False)
    B, N, K1 = 1024, 1024, 32
    args = list(_wgl_args(sd, B, N, W, V, K1))
    if not shared:
        args[3] = sd((B, K1, V), np.int32)
    compiled = _compile(kern, args)
    assert "tpu_custom_call" in compiled.as_text()


def test_dc_peel_compiles(sd):
    from jepsen_tpu.ops.dc_monitor import get_dc_kernel
    B, E = 1024, 1024
    _compile(get_dc_kernel(E), (sd((B, E), np.int32),
                                sd((B, E), np.int32),
                                sd((B, E), bool)))


@pytest.mark.parametrize("V", [32, 256])
def test_graph_closure_compiles(sd, V):
    from jepsen_tpu.ops.graph import N_LEVELS, graph_kernel
    from jepsen_tpu.ops.schedule import GRAPH_CHUNK_ROWS
    _compile(graph_kernel(V),
             (sd((GRAPH_CHUNK_ROWS, N_LEVELS, V, V // 32), np.uint32),))


@pytest.mark.parametrize("V", [32, 256])
def test_txn_closure_compiles(sd, V):
    from jepsen_tpu.ops.schedule import GRAPH_CHUNK_ROWS
    from jepsen_tpu.ops.txn_graph import N_TXN_PLANES, txn_kernel
    _compile(txn_kernel(V),
             (sd((GRAPH_CHUNK_ROWS, N_TXN_PLANES, V, V // 32),
                 np.uint32),))


@pytest.mark.parametrize("kernel", ["_set_kernel", "_tq_kernel"])
def test_folds_compile(sd, kernel):
    from jepsen_tpu.ops import folds
    B, N, V = 2048, 1024, 1024
    args = [sd((B, N), np.int32) for _ in range(3)]
    if kernel == "_set_kernel":
        args.append(sd((B, V), bool))
    _compile(getattr(folds, kernel)(V), args)
