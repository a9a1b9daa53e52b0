"""The device hot path compiles for a TPU v5e at fleet widths.

No chip is needed: the TPU compiler builds each jitted entry for one
device of a described ``v5e:2x2`` topology, which raises whatever the
chip's compiler would (VMEM overflow, misaligned tiles, programs that do
not fit HBM).  Nothing runs, so these tests say nothing about results or
speed.  The topology is described inside a fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but can never be read back without the chip: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def test_fused_scan_compiles_15x200(one_chip):
    """``_scan_assign_multi`` under float64 at a 15x200 fleet with a
    1280-row task bucket per region."""
    from repro.core.micro import MicroAllocator
    from repro.core.micro_jax import _scan_assign_multi
    from repro.sim.state import WARM_SLOTS
    from repro.workload.batch import EMBED_DIM

    r, s, n = 15, 200, 1280
    k, w, e = MicroAllocator.KEEP, WARM_SLOTS, EMBED_DIM

    def spec(shape, dtype):
        return _spec(one_chip, shape, dtype)

    with jax.enable_x64(True):
        f64, i32 = jnp.float64, jnp.int32
        server = [spec((r, s), f64), spec((r, s), f64), spec((r, s), i32),
                  spec((r, s), f64), spec((r, s), i32),
                  spec((r, s, w), i32), spec((r, s), f64),
                  spec((r, s), jnp.bool_), spec((r, s), f64),
                  spec((r, s), f64)]
        rings = [spec((r, s, k), i32), spec((r, s, k), i32),
                 spec((r, s, k, e), jnp.float32),
                 spec((r, s, k), jnp.float32)]
        tasks = [spec((r, n), i32), spec((r, n), i32), spec((r, n), f64),
                 spec((r, n), f64), spec((r, n, e), jnp.float32),
                 spec((r, n), jnp.float32), spec((r, n), jnp.bool_),
                 spec((r,), jnp.int64), spec((), i32), spec((), f64)]
        _fits(_scan_assign_multi.lower(*server, *rings, *tasks).compile())


@pytest.fixture(scope="module")
def engine_step_spec(one_chip):
    """The jitted engine step's operands at a 25x500 fleet: the static
    triple, and a maker of its packed float64 / int32 buffer shapes (the
    columns a kernel reads plus the call's own operands)."""
    from repro.sim import make_cluster_state

    st = make_cluster_state(25, seed=3, servers_per_region=(500, 501))
    assert st.n_servers == 12500
    with jax.enable_x64(True):
        statics = tuple(_spec(one_chip, (st.n_servers,), jnp.float64)
                        for _ in range(3))

    def packed(reads, n_floats, n_ints):
        n_f, n_i = (sum(getattr(st, name).size for name in names)
                    for names in reads)
        with jax.enable_x64(True):
            return (_spec(one_chip, (n_f + n_floats,), jnp.float64),
                    _spec(one_chip, (n_i + n_ints,), jnp.int32))

    return statics, packed


def test_engine_warm_and_close_steps_compile_12500(one_chip,
                                                   engine_step_spec):
    from repro.sim.engine_jax import (CLOSE_READS, WARM_IO, close_step,
                                      warm_step)

    statics, packed = engine_step_spec
    with jax.enable_x64(True):                    # + the slot length
        _fits(warm_step.lower(statics, *packed(WARM_IO, 1, 0)).compile())
        _fits(close_step.lower(statics,
                               *packed(CLOSE_READS, 1, 0)).compile())


def test_engine_apply_single_compiles_12500(one_chip, engine_step_spec):
    from repro.sim.engine_jax import APPLY_IO, apply_single, row_bucket

    statics, packed = engine_step_spec
    rows = row_bucket(20000)          # work_raw; server ids, models, valid
    with jax.enable_x64(True):
        _fits(apply_single.lower(statics,
                                 *packed(APPLY_IO, rows, 3 * rows)).compile())


def _kernel_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    return _fits(compiled)


def test_sinkhorn_batched_compiles_r25(one_chip):
    from repro.kernels.sinkhorn import sinkhorn_batched

    r = 25
    f32 = jnp.float32
    _kernel_compiled(jax.jit(sinkhorn_batched).lower(
        _spec(one_chip, (1, r), f32), _spec(one_chip, (1, r), f32),
        _spec(one_chip, (1, r, r), f32)).compile())


@pytest.mark.parametrize("with_locality", [False, True])
def test_compat_score_compiles_2700x500(one_chip, with_locality):
    from repro.kernels.compat_score import compat_score

    n, s = 2700, 500
    f32 = jnp.float32
    args = [_spec(one_chip, (n, 8), f32), _spec(one_chip, (s, 8), f32)]
    if with_locality:
        args.append(_spec(one_chip, (n, s), f32))
    _kernel_compiled(jax.jit(compat_score).lower(*args).compile())


@pytest.mark.parametrize("with_locality", [False, True])
def test_fused_score_compiles_2700x500(one_chip, with_locality):
    from repro.kernels.compat_score import fused_score
    from repro.sim.state import WARM_SLOTS

    n, s = 2700, 500
    f32 = jnp.float32
    args = [_spec(one_chip, (n, 8), f32), _spec(one_chip, (s, 8), f32),
            _spec(one_chip, (n,), f32),
            _spec(one_chip, (s, 1 + WARM_SLOTS), f32)]
    if with_locality:
        args.append(_spec(one_chip, (n, s), f32))
    _kernel_compiled(jax.jit(fused_score).lower(*args).compile())

