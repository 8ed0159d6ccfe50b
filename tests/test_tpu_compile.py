"""Compile-only checks of the FedGS Pallas kernels for a TPU v5e.

Interpret mode (how every other test runs the kernels on the CPU) checks
neither tiling alignment nor the VMEM limit.  These tests hand each kernel
of the main path, at the widths ``chip_smoke.py`` phase B runs (N=4096
clients, m=64 sampled, the small CNN's P=13946 flat params), to the TPU
compiler for a described, unattached ``v5e:2x2`` topology, through the same
``kernels/ops.py`` wrappers (padding included) the engine calls.  Nothing
runs: a pass means the chip's compiler accepts the kernel and lowers it to
a native ``tpu_custom_call``.  One more case compiles the cell-vmapped
local trainer and compares the bytes it moves with the nested vmap's.

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


def _cifar_cnn_loss(p, x, y):
    """Two 5x5 convolutions of 64 with ReLU and 2x2 max pool, then the
    logits: the convolutions of the benchmark's CIFAR-10 CNN."""
    for w, b in (("c1", "b1"), ("c2", "b2")):
        x = jax.lax.conv_general_dilated(
            x, p[w], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[b]
        x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    logits = x.reshape(x.shape[0], -1) @ p["d"] + p["db"]
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits),
                                         y[:, None], axis=1))


def test_folded_local_training_bytes_for_v5e(one_chip):
    """The cell-vmapped trainer at 32x32x3 compiles for the chip and reads
    and writes at most 0.6x the bytes of the nested vmap (which copies each
    conv activation and cotangent between layouts)."""
    from test_local_train_fold import nested_trainer

    from repro.fed.client import make_local_trainer
    cells, clients, n_max, batch = 3, 4, 40, 16
    f32 = jnp.float32

    def s(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = {"c1": s((cells, 5, 5, 3, 64)), "b1": s((cells, 64)),
              "c2": s((cells, 5, 5, 64, 64)), "b2": s((cells, 64)),
              "d": s((cells, 5 * 5 * 64, 10)), "db": s((cells, 10))}
    args = (params, s((cells, clients, n_max, 32, 32, 3)),
            s((cells, clients, n_max), jnp.int32),
            s((cells, clients), jnp.int32), s(()),
            s((cells, clients, 2), jnp.uint32))

    def bytes_accessed(factory):
        trainer = factory(_cifar_cnn_loss, local_steps=2, batch_size=batch)
        compiled = jax.jit(jax.vmap(trainer, in_axes=(0, 0, 0, 0, None, 0))
                           ).lower(*args).compile()
        cost = compiled.cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["bytes accessed"]
    assert bytes_accessed(make_local_trainer) <= \
        0.6 * bytes_accessed(nested_trainer)


def test_xsilo_granite_segment_fits_v5e(one_chip):
    """The cross-silo hybrid LM cell's segment program (granite-4.0-h-micro,
    one period of 10 layers, 798M float32 parameters, two silos a round
    trained client by client) compiles for the chip, and the peak of the
    buffers the compiler assigns it (arguments, temporaries and code) is
    under 15.5 GB of the v5e's 16 GiB."""
    import numpy as np

    from chipbench import run
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from repro.fed.scan_engine import ScanEngine, stack_cells
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = run.load_cell(root, "xsilo-granite-h-micro")
    cfg, traffic = cell["cfg"], cell["traffic"]
    ds = make_dataset(cfg)
    tables = mode_tables(traffic, ds)
    eng = ScanEngine(fed_dataset(ds), fed_model(root, cfg, ds),
                     engine_config(cfg, traffic), use_masks=True)
    n = ds.n_clients
    cells = engine_cells(eng, traffic, cell_specs(
        cfg, traffic, ds, 4100000000, 0, tables), np.zeros((n, n)))
    flags = eng._flags(cells)
    assert flags[4] == (("fedgs",), ("fedavg",))    # client by client
    stacked = stack_cells(cells)

    def s(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    carry = jax.eval_shape(eng._init_program(stacked, flags), stacked)
    compiled = eng._segment_program(
        stacked, flags, traffic["segment_rounds"]).lower(
        jax.tree_util.tree_map(s, stacked), jax.tree_util.tree_map(s, carry),
        s(jnp.int32(0))).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.5e9
