"""Compile every op the kernel registry routes to Pallas on TPU, for a
described (not attached) TPU v5e, at the widths ``chip_smoke.py`` serves:
2^22 vertices, 512 sources, 64-wide ELL rows.

Nothing runs: the TPU compiler either accepts each kernel or raises what
the chip would raise (unsupported Mosaic lowerings, VMEM or HBM
overflow). The topology is described inside a module fixture, which
skips where no TPU compiler is installed.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.registry import JNP_ONLY_OPS, dispatch, registered_ops

N = 1 << 22          # vertices
S = 512              # sources (= queries of the batch_1b shape)
W = S // 32          # packed words per vertex
D = 64               # ELL row width (pow2 bucket of the max degree)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


i32 = jnp.int32
# op -> (argument shapes, static trailing arguments)
CASES = {
    "msbfs_count": ([((W, N), i32), ((S, N), jnp.int8)], ()),
    "pairwise_popcount": ([((S, N), jnp.bool_)], ()),
    "path_member": ([((65536, 4), i32), ((65536, D), i32)], ()),
    "rowwise_overlap": ([((65536, 4), i32), ((65536, 4), i32)], ()),
    "path_overlap": ([((4096, 4), i32), ((4096, 4), i32)], ()),
    "flash_attention": ([((8, 512, 128), jnp.bfloat16)] * 3, (True,)),
}


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile()


def _program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def test_cases_cover_every_pallas_routed_op():
    assert set(CASES) == set(registered_ops()) - set(JNP_ONLY_OPS)


@pytest.mark.parametrize("op", sorted(CASES))
def test_pallas_op_compiles_for_v5e(op, one_chip):
    shapes, static = CASES[op]
    args = [_spec(s, dt, one_chip) for s, dt in shapes]
    arm = dispatch(op, "pallas")
    compiled = _compile(lambda *a: arm(*a, *static), args)
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < HBM_BYTES


def _sweep_compiles(table, sharding):
    """Compile the whole index sweep (k=6, 512 sources) over ``table``."""
    from repro.core.msbfs import msbfs_dist_ell
    args = [table, _spec((S,), i32, sharding)]
    compiled = _compile(lambda e, s: msbfs_dist_ell(
        e, s, n=N, k_max=6, backend="pallas"), args)
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < HBM_BYTES // 2


def test_packed_sweep_fits_one_chip(one_chip):
    """The whole index sweep (k=6, 512 sources) at 2^22 vertices."""
    _sweep_compiles(_spec((N + 1, D), i32, one_chip), one_chip)


# a sliced ELL of 2^22 rows: widths of a skewed degree list, the last
# table a degree-0 tail
_HEAD = ((1 << 12, 64), (1 << 16, 32), (1 << 19, 24), (1 << 20, 16),
         (1 << 20, 12), (1 << 20, 8))
SLICES = (*_HEAD, (N - sum(r for r, _ in _HEAD) - (1 << 18), 4),
          (1 << 18, 0))


def test_sliced_sweep_fits_one_chip(one_chip):
    """The same sweep over a degree-sorted sliced ELL of 2^22 rows."""
    from repro.core.graph import SlicedEll
    perm = _spec((N,), i32, one_chip)
    _sweep_compiles(SlicedEll(perm, perm, tuple(_spec(s, i32, one_chip)
                                                for s in SLICES)), one_chip)


def test_similarity_fits_one_chip(one_chip):
    """Γ intersections and sizes for one direction at 2^22 vertices."""
    from repro.core.similarity import _gamma_stats
    args = [_spec((N + 1, S), jnp.int8, one_chip),
            _spec((S,), i32, one_chip), _spec((S,), jnp.int8, one_chip)]
    compiled = _compile(lambda d, c, k: _gamma_stats(
        d, c, k, backend="pallas"), args)
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < HBM_BYTES // 2


def test_slack_vector_streams_the_table(one_chip):
    """One search node's slack from a (2^22+1, 512) distance table: a
    single pass, no (n+1, S) int32 temporary."""
    from repro.core.index import slack_vector
    args = [_spec((N + 1, S), jnp.int8, one_chip),
            _spec((S,), i32, one_chip), _spec((), i32, one_chip)]
    compiled = _compile(slack_vector, args)
    assert compiled.memory_analysis().temp_size_in_bytes < (N + 1) * 8
