"""Compile the quorum kernel for a described TPU v5e, without the chip.

Interpret mode hides what Mosaic refuses (in-kernel gathers, blocks not
aligned to the tiling), so these tests run the chip's own compiler at
the shapes the system evaluates:

  * (100, 5):    the ``paper_default`` per-tick in-flight batch,
                 2 clients x 5 in flight x batch 10, on 5 replicas;
  * (100, 9):    the same batch at the 9-replica server-scaling width;
  * (40000, 5):  one call holding every fast-path instance of a full
                 ``paper_default`` run;
  * (8192, 5):   one chunk of the chip benchmark's post-window fold
                 (``bench/fold.py``) on the 5-replica cell.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import pytest

SHAPES = [(100, 5), (100, 9), (40_000, 5), (8192, 5)]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip can write the persistent cache but never read
        # it back: keep these compiles out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("ops,n", SHAPES)
def test_quorum_kernel_compiles_for_v5e(one_chip, ops, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.quorum_commit import quorum_commit_pallas

    spec = jax.ShapeDtypeStruct((ops, n), jnp.float32, sharding=one_chip)
    compiled = quorum_commit_pallas.lower(spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
