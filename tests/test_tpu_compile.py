"""The chip's compiler on the fused kernels at the benchmark's tile: the
grouped Gaussian one, which Mosaic refused at its default scoped-VMEM limit,
which no interpreted test could see, and the packed bf16 products of the
grouped Bernoulli one and of the chain-batched logistic one.  Compiled here
for a described TPU v5e, with no chip: nothing runs, so this says nothing of
results or times.  One file, one fixture: only the worker that is given this
file loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache and
    # cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_grouped_lmm_kernel_fits_the_cores_vmem_at_the_cells_tile(one_chip):
    from stark_tpu.ops.hier_fused import _LMM_VMEM_LIMIT, _grouped_lmm_call

    c, d, q, groups, tile, k_loc = 16, 8, 2, 64, 8192, 8
    n = 16 * tile

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(beta, u, intercept, xt, zt, y, gl, first_gid):
        return _grouped_lmm_call(
            beta, u, intercept, xt, zt, y, gl, first_gid, k_loc=k_loc,
            lane_tile=tile, interpret=False)

    compiled = jax.jit(call).lower(
        shape((c, d)), shape((c, groups, q)), shape((c,)), shape((d, n)),
        shape((q, n)), shape((n,)), shape((n,), jnp.int32),
        shape((n // tile,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "stark_lmm_ll_grouped" in text
    assert _LMM_VMEM_LIMIT > 16 * 1024 * 1024  # Mosaic's default refused it


@pytest.mark.parametrize("chains", [8, 64])
def test_grouped_bernoulli_kernel_compiles_packed_at_the_cells_tile(
        one_chip, chains, monkeypatch):
    """The flagship's kernel at `highest` with the six bf16 products packed
    (two bf16 dots a tile) at its cells' tile and widths: the 8 chains of
    `hier_n16m.nuts` and the 64 of `hier_n16m.sample`."""
    from stark_tpu.ops.hier_fused import _grouped_call, grouped_mxu_form

    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    d, groups, tile, k_loc = 32, 1000, 8192, 8
    assert grouped_mxu_form(d, k_loc) == ("split6", 216)
    n = 4 * tile

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(beta, alpha, xt, y, gl, first_gid):
        return _grouped_call(beta, alpha, xt, y, gl, first_gid, k_loc=k_loc,
                             lane_tile=tile, interpret=False)

    text = jax.jit(call).lower(
        shape((chains, d)), shape((chains, groups)), shape((d, n)),
        shape((n,)), shape((n,), jnp.int32),
        shape((n // tile,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "stark_hier_ll_grouped" in text


@pytest.mark.parametrize("with_offsets", [False, True], ids=["noff", "off"])
def test_batched_logistic_kernel_compiles_packed_at_the_cells_tile(
        one_chip, with_offsets, monkeypatch):
    """The chain-batched logistic kernel at `highest` with the six bf16
    products packed (two bf16 dots a tile) at the logistic cells' shapes: 8
    chains, D = 32, TILE 8192, `y` as the (1, N) operand the model lays
    out; with offsets as the hierarchical and mixed models' offset paths
    call it.  It fits Mosaic's default scoped-VMEM limit: the call passes
    none."""
    from stark_tpu.ops.logistic_fused import _batched_call, batched_mxu_form

    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    c, d, tile = 8, 32, 8192
    assert batched_mxu_form(d) == ("split6", 192)
    n = 4 * tile

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(beta, xt, y, offsets):
        return _batched_call(beta, xt, y, offsets, lane_tile=tile,
                             interpret=False)

    offsets = shape((c, n)) if with_offsets else None
    text = jax.jit(call).lower(
        shape((c, d)), shape((d, n)), shape((1, n)), offsets
    ).compile().as_text()
    assert "tpu_custom_call" in text and "stark_logistic_ll" in text
