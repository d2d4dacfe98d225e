"""Compile-only checks of the Pallas kernels for a TPU v5e at real widths.

Nothing here runs on a chip: each test lowers a kernel entry point with
abstract shapes placed on one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned blocks, unlowerable ops, VMEM or HBM overflow).  Each
test asserts that the compiled program fits one chip's 16 GB, and that it
holds a Pallas kernel (``tpu_custom_call``) exactly where the path has
one: bf16 and int8 wire quantization and the shard routing are XLA ops.

The topology is described inside a fixture, so collecting this file never
loads the TPU library.  The kernel branch is chosen explicitly
(``interpret=False`` / ``pallas=True``): the default follows
``jax.default_backend()``, which is the CPU here.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, wire_pack

HBM_BYTES = 16e9
ARENA = 10_500_000          # bench_scalability.run_big's MLP arena
K = 10_496                  # its DGS message at density 0.001


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes, kernel=True):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled


def test_scatter_apply_compiles(one_chip):
    _compile(lambda d, i, v: ops.scatter_apply(d, i, v, interpret=False),
             one_chip, ((ARENA,), jnp.float32), ((K,), jnp.int32),
             ((K,), jnp.float32))


def test_scatter_apply_rows_compiles(one_chip):
    _compile(lambda d, i, v: ops.scatter_apply_rows(d, i, v,
                                                    interpret=False),
             one_chip, ((16, ARENA), jnp.float32), ((16, K), jnp.int32),
             ((16, K), jnp.float32))


@pytest.mark.parametrize("n", [1 << 20, ARENA])
def test_hierarchical_topk_compiles(one_chip, n):
    # density 0.001 in the oversampled production mode (r < k)
    _compile(lambda x: ops.hierarchical_topk(x, k=n // 1000, r=4,
                                             interpret=False),
             one_chip, ((n,), jnp.float32))


def test_block_topk_full_block_compiles(one_chip):
    # r == BLOCK: the exact mode's largest sweep count
    _compile(lambda x: ops.block_topk_candidates(x, r=1024,
                                                 interpret=False),
             one_chip, ((1 << 20,), jnp.float32))


def test_samomentum_fused_compiles(one_chip):
    _compile(lambda u, g, t: ops.samomentum_fused(
        u, g, t, momentum=0.7, lr=0.05, interpret=False),
        one_chip, ((ARENA,), jnp.float32), ((ARENA,), jnp.float32),
        ((), jnp.float32))


@pytest.mark.parametrize("mode", ["bf16", "int8", "tern"])
def test_quantize_pack_compiles(one_chip, mode):
    seg = (K - 2059, 2048, 11)
    _compile(lambda v: wire_pack.quantize_pack(v, mode=mode, seg=seg,
                                               pallas=True),
             one_chip, ((K,), jnp.float32), kernel=mode == "tern")


def test_route_by_shard_batch_compiles(one_chip):
    bounds = tuple(ARENA * s // 4 for s in range(5))
    _compile(lambda i, v: ops.route_by_shard_batch(
        i, v, bounds=jnp.asarray(bounds, jnp.int32), n_shards=4, cap=K),
        one_chip, ((16, K), jnp.int32), ((16, K), jnp.float32),
        kernel=False)
