"""`FusedLogistic` lays `y` out for its kernel once, in `prepare_data` (PR 29):
the (1, N) float32 leaf through the plumbing every prepared pytree shares.
The span that says so, SG-HMC's minibatches, consensus' row splits, a fleet
of stacked problems, and data an older tree prepared (no leaf), which takes
the op's rank-1 path to the same bits.  The mesh's share is in
`test_mesh_rows_in_place.py`, the op's in `test_ops_fused.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu import prepare_model_data, telemetry
from stark_tpu.fleet import FleetSpec, sample_fleet
from stark_tpu.kernels.sghmc import make_minibatch_grad
from stark_tpu.model import flatten_model
from stark_tpu.models import FusedHierLogistic, FusedLogistic
from stark_tpu.models.logistic import Y_LANES, synth_logistic_data
from stark_tpu.parallel import consensus_sample

N, D = 1024 + 512, 4  # not a multiple of 1024


class OlderLayout(FusedLogistic):
    """The parent tree's `prepare_data`: `xT` alone."""

    def prepare_data(self, data):
        out = super().prepare_data(data)
        return {k: v for k, v in out.items() if k != Y_LANES}


@pytest.fixture(scope="module")
def raw():
    data, _ = synth_logistic_data(jax.random.PRNGKey(9), N, D)
    return data


def _last_prepare_span():
    return [r for r in telemetry.span_log() if r.name == "prepare_data"][-1]


def test_prepare_span_names_the_leaf_and_counts_its_bytes(raw):
    model = FusedLogistic(D)
    data = prepare_model_data(model, raw)
    sp = _last_prepare_span()
    assert sp.fields["kernel_layout"] == "xT," + Y_LANES
    # x becomes xT byte for byte, y stays, the leaf comes on top
    assert sp.fields["bytes_out"] == sp.fields["bytes_in"] + 4 * N
    assert model.data_row_axes(data) == {"xT": 1, "y": 0, Y_LANES: 1}
    # prepared data comes back as it is, and the span names nothing
    assert prepare_model_data(model, data)[Y_LANES] is data[Y_LANES]
    assert "kernel_layout" not in _last_prepare_span().fields
    # the other fused models keep their one leaf
    hier, _ = synth_logistic_data(jax.random.PRNGKey(1), 256, D, num_groups=4)
    assert Y_LANES not in prepare_model_data(FusedHierLogistic(D, 4), hier)
    assert _last_prepare_span().fields["kernel_layout"] == "xT"


@pytest.mark.parametrize("y_dtype", [np.float32, np.int32, np.float64])
def test_the_leaf_is_float32_whatever_y_was(raw, y_dtype):
    host = {"x": np.asarray(raw["x"]), "y": np.asarray(raw["y"]).astype(y_dtype)}
    data = FusedLogistic(D).prepare_data(host)
    assert data["y"] is host["y"]
    assert data[Y_LANES].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(data[Y_LANES]), np.asarray(raw["y"])[None])


def test_minibatch_cuts_the_leaf_with_xT(raw):
    """SG-HMC gathers every row-carrying leaf along its own axis: the
    leaf's rows are the batch's rows, and the gradient is the one the
    rank-1 `y` of the same batch gives."""
    model = FusedLogistic(D)
    fm = flatten_model(model, lik_scale=N / 64)
    data = prepare_model_data(model, raw)
    older = {k: v for k, v in data.items() if k != Y_LANES}

    def probe(z, batch):
        assert batch[Y_LANES].shape == (1, 64) and batch["xT"].shape == (D, 64)
        return jnp.sum(jnp.abs(batch[Y_LANES][0] - batch["y"])) + jnp.sum(z)

    z = 0.1 * jnp.arange(D, dtype=jnp.float32)
    grad_probe = make_minibatch_grad(probe, data, 64, model.data_row_axes(data))
    new = make_minibatch_grad(fm.potential, data, 64, model.data_row_axes(data))
    old = make_minibatch_grad(fm.potential, older, 64, model.data_row_axes(older))
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        # d/dz of sum(z) alone: the |difference| term is identically zero
        np.testing.assert_array_equal(np.asarray(grad_probe(key, z)), np.ones(D))
        np.testing.assert_array_equal(
            np.asarray(new(key, z)), np.asarray(old(key, z)))


def test_consensus_splits_the_leaf_with_xT(raw):
    """Each sub-posterior's (1, N/S) leaf holds its own rows: the combined
    draws are the ones the older layout gives, bit for bit."""
    kw = dict(num_shards=4, chains=2, kernel="hmc", num_leapfrog=4,
              num_warmup=20, num_samples=10, seed=3)
    new = consensus_sample(FusedLogistic(D), raw, **kw)
    old = consensus_sample(OlderLayout(D), raw, **kw)
    draws = np.asarray(new.draws["beta"])
    assert draws.shape == (2, 10, D) and np.all(np.isfinite(draws))
    np.testing.assert_array_equal(draws, np.asarray(old.draws["beta"]))


def test_fleet_of_stacked_problems_still_samples():
    """A stacked (P, 1, N) leaf is batched data: the op's batching rule
    maps over the problems and hands each its own (1, N)."""
    datasets = [
        synth_logistic_data(jax.random.PRNGKey(20 + i), 256 + 64, D)[0]
        for i in range(2)
    ]
    kw = dict(chains=2, block_size=10, max_blocks=2, min_blocks=2,
              num_warmup=20, kernel="hmc", num_leapfrog=4, seed=1)
    new = sample_fleet(FleetSpec.from_problems(FusedLogistic(D), datasets), **kw)
    old = sample_fleet(FleetSpec.from_problems(OlderLayout(D), datasets), **kw)
    for a, b in zip(new.problems, old.problems):
        assert np.asarray(a.draws_flat).shape == (2, 20, D)
        assert np.all(np.isfinite(np.asarray(a.draws_flat)))
        np.testing.assert_array_equal(a.draws_flat, b.draws_flat)


@pytest.mark.parametrize("kernel", ["chees", "nuts"])
def test_data_an_older_tree_prepared_samples_the_same_draws(raw, kernel):
    """No leaf (an older checkpoint's layout, a caller's own `xT`): the
    rank-1 path, the same draws."""
    model = FusedLogistic(D)
    data = prepare_model_data(model, raw)
    older = {k: v for k, v in data.items() if k != Y_LANES}
    kw = dict(chains=4, num_warmup=20, num_samples=10, seed=2, kernel=kernel)
    if kernel == "chees":
        kw.update(init_step_size=0.1, map_init_steps=5)
    new = stark_tpu.sample(model, data, **kw)
    old = stark_tpu.sample(model, older, **kw)
    draws = np.asarray(new.draws["beta"])
    assert draws.shape == (4, 10, D) and np.all(np.isfinite(draws))
    np.testing.assert_array_equal(draws, np.asarray(old.draws["beta"]))
