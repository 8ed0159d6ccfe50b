"""Compile-only checks of the FedGS Pallas kernels for a TPU v5e.

Interpret mode (how every other test runs the kernels on the CPU) checks
neither tiling alignment nor the VMEM limit.  These tests hand each kernel
of the main path, at the widths ``chip_smoke.py`` phase B runs (N=4096
clients, m=64 sampled, the small CNN's P=13946 flat params), to the TPU
compiler for a described, unattached ``v5e:2x2`` topology, through the same
``kernels/ops.py`` wrappers (padding included) the engine calls.  Nothing
runs: a pass means the chip's compiler accepts the kernel and lowers it to
a native ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the fixture skips the
file where it cannot be described.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp

N, M, P, D_EMB = 4096, 64, 13946, 10


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU compile written to the persistent cache cannot be read back
    without a chip: keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_tiles(monkeypatch):
    """Resolve ``tile="auto"`` as the chip does: the tuned table has no
    TPU rows, so the heuristic defaults apply (here the live backend is
    the CPU, whose tuned rows would otherwise be picked)."""
    from repro.kernels import autotune, ops
    monkeypatch.setattr(ops, "resolve",
                        functools.partial(autotune.resolve, platform="tpu"))


def _cases():
    from repro.kernels import ops
    f32, i32 = jnp.float32, jnp.int32
    return {
        "fused_adjacency": (
            lambda u: ops.fused_adjacency(u, eps=0.1, sigma2=0.01,
                                          clamp=True, interpret=False),
            [((N, D_EMB), f32)]),
        "floyd_warshall": (
            lambda h: ops.floyd_warshall(h, interpret=False),
            [((N, N), f32)]),
        "masked_argmax": (
            lambda d, r, mk: ops.greedy_argmax(d, r, mk, interpret=False),
            [((N,), f32), ((N,), f32), ((N,), jnp.bool_)]),
        "swap_gain_fused": (
            lambda h, z, s, sel, v, a, b: ops.swap_best_fused(
                h, z, s, sel, v, a, b, interpret=False),
            [((N, N), f32), ((N,), f32), ((), f32), ((M,), i32),
             ((M,), jnp.bool_), ((M,), f32), ((N,), f32)]),
        "memagg": (
            lambda mem, upd, sel, v, w: ops.memory_aggregate(
                mem, upd, sel, v, w, interpret=False),
            [((N, P), f32), ((M, P), f32), ((M,), i32), ((M,), jnp.bool_),
             ((N,), f32)]),
        "krum": (
            lambda x: ops.krum_distances(x, interpret=False),
            [((M, P), f32)]),
        "pairwise_similarity": (
            lambda u: ops.pairwise_similarity(u, interpret=False),
            [((N, D_EMB), f32)]),
    }


@pytest.mark.parametrize("kernel", ["fused_adjacency", "floyd_warshall",
                                    "masked_argmax", "swap_gain_fused",
                                    "memagg", "krum", "pairwise_similarity"])
def test_kernel_compiles_for_v5e(kernel, one_chip, chip_tiles):
    fn, shapes = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel} did not lower to a native TPU kernel"
