"""Compile the Pallas kernels of the partition path, the kernel-path
and jnp-path scans, and the Alg 4 program across a 2x2 mesh, for a
described TPU v5e at deployment widths — no chip needed.

Interpret mode accepts what Mosaic refuses (dynamic slices of vectors,
scoped-VMEM overflow), so only these compiles show that the kernels run on
the chip.  The topology is described inside a fixture, never at import:
one process at a time may load the TPU compiler's library, so run this
module on one worker (``pytest -n N --dist loadfile`` keeps a file on one
worker).  The fixture skips only where libtpu is not installed; any other
failure to describe the chip fails the tests.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.jax_partition import (
    _WORKER_AXIS,
    _parallel_scan_fn,
    _partition_scan,
    _worker_mesh,
)
from repro.kernels.parsa_cost.parsa_cost import parsa_cost_kernel
from repro.kernels.parsa_cost.select import (
    SKETCH_KERNEL_MAX_WORDS,
    packed_union_delta_kernel,
    parsa_select_kernel,
    refine_sweep_kernel,
    sketch_select_kernel,
)

W = 131072   # packed words of 2^22 hashed features
B = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # with libtpu present, a failure to describe the chip is a failure
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("k,greedy", [(16, True), (64, True), (16, False)])
def test_parsa_select_kernel_compiles(one_chip, k, greedy):
    _compiled_kernel(
        lambda *a: parsa_select_kernel(*a, greedy=greedy, bw=512),
        _i32((B, W), one_chip), _i32((k, W), one_chip),
        _i32((B, 1), one_chip), _i32((1, k), one_chip),
        _i32((1, k), one_chip))


def test_sketch_select_kernel_compiles_at_width_guard(one_chip):
    ws, k = SKETCH_KERNEL_MAX_WORDS, 16
    _compiled_kernel(
        lambda *a: sketch_select_kernel(*a, greedy=True),
        _i32((B, ws), one_chip), _i32((k, ws), one_chip),
        _i32((B, 1), one_chip), _i32((1, k), one_chip),
        _i32((1, k), one_chip))


@pytest.mark.parametrize("chunk", [1024, 4096])
def test_refine_sweep_kernel_compiles(one_chip, chunk):
    k = 16
    _compiled_kernel(refine_sweep_kernel, _i32((k, chunk // 32), one_chip),
                     _i32((1, chunk), one_chip), _i32((k, 1), one_chip))


def test_parsa_cost_kernel_compiles(one_chip):
    _compiled_kernel(lambda a, b: parsa_cost_kernel(a, b),
                     _i32((B, W), one_chip), _i32((16, W), one_chip))


def test_packed_union_delta_kernel_compiles(one_chip):
    _compiled_kernel(lambda a, b: packed_union_delta_kernel(a, b),
                     _i32((16, W), one_chip), _i32((16, W), one_chip))


def test_kernel_path_partition_scan_compiles(one_chip):
    """A few blocks of the whole ``device_scan`` program, fused select
    kernel inside the block scan, at the deployment's packed width."""
    nb, b, cap, slots, k = 4, 256, 48, 1 << 14, 16
    args = (_i32((nb, b), one_chip, jnp.bool_), _i32((nb, b, cap), one_chip),
            _i32((nb, b, cap), one_chip), _i32((nb, b), one_chip, jnp.bool_),
            _i32((nb, 2), one_chip), _i32((3, slots), one_chip),
            _i32((k, W), one_chip), _i32((k,), one_chip))
    text = _partition_scan.lower(*args, k=k, use_kernel=True,
                                 interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_jnp_path_partition_scan_compiles(one_chip):
    """A few blocks of ``ctr.stream``'s program: the jnp path's scan at
    ``criteo_k16``'s widths, each block choosing on device between the
    compact-space greedy and the densified one."""
    nb, b, cap, slots, k = 4, 256, 48, 1 << 17, 16
    args = (_i32((nb, b), one_chip, jnp.bool_), _i32((nb, b, cap), one_chip),
            _i32((nb, b, cap), one_chip), _i32((nb, b), one_chip, jnp.bool_),
            _i32((nb, 2), one_chip), _i32((3, slots), one_chip),
            _i32((k, W), one_chip), _i32((k,), one_chip))
    text = _partition_scan.lower(*args, k=k, use_kernel=False,
                                 interpret=False).compile().as_text()
    assert text.startswith("HloModule jit__partition_scan")
    assert "conditional(" in text


def test_parallel_partition_scan_compiles_for_four_chips(topo):
    """The Alg 4 program (``_parallel_partition_scan``) over the described
    2x2 host at ``criteo_k16_w4``'s widths: each worker's blocks sharded
    to its chip, the overflow list and the live sets replicated, the sets
    merged by an all-gather and the sizes by an all-reduce."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = tuple(topo.devices)
    mesh = _worker_mesh(devices)
    by_worker = NamedSharding(mesh, P(_WORKER_AXIS))
    replicated = NamedSharding(mesh, P())
    workers, nb, b, cap, slots, k = 4, 2, 256, 48, 1 << 17, 16
    args = (_i32((workers, nb, b), by_worker, jnp.bool_),
            _i32((workers, nb, b, cap), by_worker),
            _i32((workers, nb, b, cap), by_worker),
            _i32((workers, nb, b), by_worker, jnp.bool_),
            _i32((workers, nb, 2), by_worker),
            _i32((3, slots), replicated),
            _i32((k, W), replicated), _i32((k,), replicated))
    fn = _parallel_scan_fn(devices, k, 1, False, False)
    text = fn.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit__parallel_partition_scan")
    assert "all-gather(" in text and "all-reduce(" in text
