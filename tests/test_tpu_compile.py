"""The schedule-DP Pallas sweep compiled for a described TPU v5e chip.

Nothing runs: the TPU's compiler (Mosaic) lowers the kernel at the paper's
task bucket (``n_b=256``) and at twice that, so what it refuses (a block
that is not a tile, an i1 loop carry, more fast memory than a kernel may
use) fails here instead of on the chip.  The topology is described inside
a fixture, never while a module is imported: only the worker that runs
this file loads the TPU library.  The engine's launch is not compiled
here; at any bucket it takes minutes, far beyond a test's budget.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import schedule_dp as sdp  # noqa: E402

ROWS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable for a chip that is not attached cannot be read back
        # from the persistent cache, so keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def compile_sweep(one_chip, n_b: int, tails: bool):
    call = sdp._build_pallas_sweep(n_b, n_b - 6, tails, False, "float32")
    graph = jax.ShapeDtypeStruct((n_b, n_b), jnp.int32, sharding=one_chip)
    links = jax.ShapeDtypeStruct((ROWS, n_b), jnp.int32, sharding=one_chip)
    dur = jax.ShapeDtypeStruct((ROWS, n_b), jnp.float32, sharding=one_chip)
    return call.lower(graph, graph, links, links, dur).compile()


@pytest.mark.parametrize("tails", [True, False])
def test_sweep_pallas_compiles_for_v5e_at_paper_bucket(one_chip, tails):
    compiled = compile_sweep(one_chip, 256, tails)
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel


@pytest.mark.parametrize("tails", [True, False])
def test_sweep_pallas_fits_fast_memory_at_512(one_chip, tails):
    """A kernel that outgrows the chip's scoped fast memory (VMEM) is
    refused by the compile itself; past it, the device-memory footprint is
    exactly the two graph matrices and the row arrays."""
    n_b = 512
    mem = compile_sweep(one_chip, n_b, tails).memory_analysis()
    graph_bytes = 2 * n_b * n_b * 4
    row_bytes = 3 * ROWS * n_b * 4
    assert mem.argument_size_in_bytes == graph_bytes + row_bytes
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes >= 4 * ROWS * n_b * 4
