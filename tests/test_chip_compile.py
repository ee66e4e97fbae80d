"""The programs a chip-holding worker runs compile for a TPU v5e at full width.

No chip is attached: the TPU compiler compiles for a described ``v5e:2x2``
topology, one device of it.  The shapes are those ``chip_smoke.py`` serves
(N = 8 shares, straggler budget 2, the engine's amortized plans, the
master's default 1 MiB stream chunks):

- Z_2^32 at t=2048, r=s=4096 coalesced in pairs: ``batch_ep_rmfe`` shares
  over GR(2^32,3), ``(2048, 4096, 3) @ (4096, 1024, 3)``, streamed in
  contraction chunks of 29, accumulated with ``ring.add``;
- GR(2^16,3) at 1024^3: ``ep`` shares ``(512, 1024, 3) @ (1024, 512, 3)``,
  streamed in chunks of 86.

Each program compiles at the compiler's lowest optimisation effort: the
same program and the same operations for the chip to accept, in about two
seconds instead of twenty to thirty.  JAX's persistent compile cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core import make_ring

HBM_BYTES = 16 * 2**30  # one v5e chip
LOW_EFFORT = {"exec_time_optimization_effort": -1.0,
              "memory_fitting_effort": -1.0}
GR32_3 = (2, 32, (3,))  # the codeword ring of Z_2^32 at N = 8
GR16_3 = (2, 16, (3,))

PRODUCTS = {  # name: (ring, fa shape, gb shape)
    "z32_share": (GR32_3, (2048, 4096, 3), (4096, 1024, 3)),
    "z32_chunk": (GR32_3, (2048, 29, 3), (29, 1024, 3)),
    "gr16_share": (GR16_3, (512, 1024, 3), (1024, 512, 3)),
    "gr16_chunk": (GR16_3, (512, 86, 3), (86, 512, 3)),
}
SUMS = {"z32_acc": (GR32_3, (2048, 1024, 3)),
        "gr16_acc": (GR16_3, (512, 512, 3))}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile(compiler_options=LOW_EFFORT)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB on one 16 GiB chip"
    return compiled


@pytest.mark.parametrize("name", sorted(PRODUCTS))
def test_share_product_compiles_for_v5e(one_chip, name):
    ring, fa, gb = PRODUCTS[name]
    compiled = _compile(make_ring(*ring).matmul, one_chip, fa, gb)
    # XLA's own dot, not a Pallas kernel
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("name", sorted(SUMS))
def test_stream_accumulate_compiles_for_v5e(one_chip, name):
    ring, shape = SUMS[name]
    _compile(make_ring(*ring).add, one_chip, shape, shape)

