"""Compile the sampler's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed without a chip, lowers
each kernel at the shapes the sampler sends and refuses what the chip would
refuse (block layouts, scoped VMEM).  The topology is described inside a
fixture, never at import, because only one process at a time may load the
TPU library.
"""

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to a persistent cache but
    cannot be read back; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize(
    "C,K",
    [
        (24, 1024),    # direct ask: n_ei_candidates x one trial
        (4096, 2048),  # the score table (ops.SCORE_TABLE_SIZE points)
    ],
)
def test_parzen_kernel_compiles_for_v5e(one_chip, no_compile_cache, C, K):
    from repro.kernels.parzen import _parzen_padded

    block_c, block_k = min(256, C), min(1024, K)
    fn = jax.jit(
        lambda c, *comps: _parzen_padded(
            c, *comps, block_c=block_c, block_k=block_k, interpret=False
        )
    )
    compiled = fn.lower(
        _spec((C,), one_chip), *[_spec((K,), one_chip)] * 6
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_parzen_kernel_is_named_for_the_trace(one_chip, no_compile_cache):
    """The kernel's custom call carries the Pallas call's name, which the
    benchmark's ``parzen_kernel_us`` finds in the trace's operations."""
    from repro.kernels.parzen import _parzen_padded

    compiled = _parzen_padded.lower(
        _spec((24,), one_chip), *[_spec((1024,), one_chip)] * 6,
        block_c=24, block_k=1024, interpret=False,
    ).compile()
    calls = [ln for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln]
    assert calls and all(ln.strip().startswith("%parzen_score") for ln in calls)


@pytest.mark.parametrize("n,m", [(64, 5), (512, 8)])
def test_mc_hv_kernel_compiles_for_v5e(one_chip, no_compile_cache, n, m):
    from repro.kernels.hypervolume import _mc_hv_padded, default_block_s

    block_s = default_block_s(n)
    fn = jax.jit(
        lambda p, s: _mc_hv_padded(p, s, block_s=block_s, interpret=False)
    )
    compiled = fn.lower(_spec((n, m), one_chip), _spec((8192, m), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
