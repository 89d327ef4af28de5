"""The per-chain kernels' centre (`kernels.base.CentredState`,
`sampler.ChainBlockKernel`): NUTS and HMC through `sample_until_converged` on
a model with `center_data`, at toy size on the CPU.

A potential over tens of millions of rows is a float32 near 1e7 whose last bit
is a whole nat; a tree's leaf weights and its accept statistic are differences
of it.  The kernels of a model that can centre carry each chain's energies
relative to a centre beside the state.  The chip's readings of the same, at
16M rows through the grouped Pallas kernel, are in PERF.md section 6 (PR 38).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import stark_tpu
from stark_tpu import telemetry
from stark_tpu.backends.jax_backend import JaxBackend
from stark_tpu.checkpoint import load_checkpoint
from stark_tpu.kernels.base import CentredState, HMCState, init_state
from stark_tpu.model import Model, flatten_model, prepare_model_data
from stark_tpu.models import FusedHierLogisticGrouped, HierLogistic
from stark_tpu.models.logistic import synth_logistic_data
from stark_tpu.sampler import SamplerConfig, make_block_runner, make_kernel

N, D, G, CHAINS = 2048, 4, 8, 4
KERNELS = [("nuts", {"max_tree_depth": 3}), ("hmc", {"num_leapfrog": 5})]
IDS = [k for k, _ in KERNELS]
#: what the rows of a deployment add to a toy potential
BIG = -1.2e7


@pytest.fixture(scope="module")
def rows():
    return synth_logistic_data(jax.random.PRNGKey(0), N, D, num_groups=G)[0]


def run(model, rows, kernel, kw, **more):
    return stark_tpu.sample_until_converged(
        model, rows, backend=JaxBackend(), chains=CHAINS, kernel=kernel,
        num_warmup=12, block_size=4, rhat_target=0.0, adaptive_blocks=False,
        min_blocks=1, seed=5, **{**kw, "max_blocks": 2, **more})


class Shifted(HierLogistic):
    """`HierLogistic` with what 16M rows would add to its log-likelihood, and
    a `center_data` that takes a constant off where the big number is made:
    the two float32s near 1e7 are subtracted from one another exactly, and
    what is left keeps float32's resolution."""

    def log_lik(self, p, data):
        ll = super().log_lik(p, data)
        if "ll_center" not in data:
            return ll + BIG
        return ll + (jnp.float32(BIG) - data["ll_center"])

    def center_data(self, data, center):
        return {**data, "ll_center": center}


class ShiftedPlain(HierLogistic):
    """The same posterior without `center_data`: the plain float32 sum."""

    def log_lik(self, p, data):
        return super().log_lik(p, data) + BIG


@pytest.mark.parametrize("kernel, kw", KERNELS, ids=IDS)
def test_centred_draws_are_the_plain_programs_where_float32_holds(
        monkeypatch, rows, kernel, kw):
    """(a) At toy N the potential is a float32 that loses nothing: the model
    with `center_data` draws what the plain program draws, within rounding."""
    model = FusedHierLogisticGrouped(D, G)
    centred = run(model, rows, kernel, kw)
    root = [s for s in telemetry.span_log() if s.name == "run"][-1]
    assert root.fields["centred"] is True and root.fields["kernel"] == kernel
    monkeypatch.setattr(FusedHierLogisticGrouped, "center_data",
                        Model.center_data)
    plain = run(FusedHierLogisticGrouped(D, G), rows, kernel, kw)
    root = [s for s in telemetry.span_log() if s.name == "run"][-1]
    assert root.fields["centred"] is False
    assert centred.draws_flat.shape == plain.draws_flat.shape == (
        CHAINS, 8, D + 2 + G)
    np.testing.assert_allclose(centred.draws_flat, plain.draws_flat,
                               atol=2e-3, rtol=0)


def _parents_block_runner(fm, cfg, block_size):
    """`sampler.make_block_runner`'s plain block as the commit before PR 38
    had it, kept here as the program a model without `center_data` has to go
    on lowering to."""
    from functools import partial

    step_kernel = make_kernel(cfg)

    def block_run(key, state, step_size, inv_mass, data=None):
        potential_fn = fm.bind(data)
        kernel = partial(step_kernel, potential_fn=potential_fn)

        def body(carry, key):
            state, diag = carry
            state, info = kernel(
                key, state, step_size=step_size, inv_mass_diag=inv_mass)
            out = (state.z, info.accept_prob, info.is_divergent, info.energy,
                   info.num_grad_evals)
            return (state, diag), out

        (state, _), outs = jax.lax.scan(
            body, (state, None), jax.random.split(key, block_size))
        return (state,) + outs

    return block_run


@pytest.mark.parametrize("kernel, kw", KERNELS, ids=IDS)
def test_a_model_without_center_data_lowers_to_the_program_it_had(
        rows, kernel, kw):
    model = HierLogistic(D, G)
    fm = flatten_model(model)
    assert fm.chain_centering is None and fm.centering is None
    data = prepare_model_data(model, rows)
    cfg = SamplerConfig(kernel=kernel, **kw)
    z = jnp.zeros((CHAINS, fm.ndim))
    args = (jax.random.split(jax.random.PRNGKey(1), CHAINS),
            jax.vmap(lambda z: init_state(fm.bind(data), z))(z),
            jnp.full((CHAINS,), 0.05), jnp.ones((CHAINS, fm.ndim)), data)

    def text(block_run):
        return str(jax.make_jaxpr(
            jax.vmap(block_run, in_axes=(0, 0, 0, 0, None)))(*args))

    assert text(make_block_runner(fm, cfg, 4)) == text(
        _parents_block_runner(fm, cfg, 4))
    # and the flagship's model asks for a centre a chain, the ensemble
    # sampler's one-chip programs for none
    fm = flatten_model(FusedHierLogisticGrouped(D, G))
    assert fm.centering is None and fm.chain_centering.width == 1


@pytest.mark.parametrize("kernel, kw", KERNELS, ids=IDS)
def test_energy_differences_at_a_potential_of_1e7(tmp_path, rows, kernel, kw):
    """(b) Along each chain, the potential two checkpoints apart against the
    toy potential's own difference (a float32 near 1e3, good to 1e-4): the
    centred program's within 1e-3 nats, the plain float32 sum's half a nat
    and more off."""
    fm = flatten_model(HierLogistic(D, G))
    toy = jax.jit(jax.vmap(fm.bind(prepare_model_data(HierLogistic(D, G),
                                                      rows))))

    def worst(model):
        pes = []
        for blocks in (1, 2):
            ck = str(tmp_path / f"{type(model).__name__}_{blocks}.npz")
            run(model, rows, kernel, kw, max_blocks=blocks, checkpoint_path=ck)
            arrays, _ = load_checkpoint(ck)
            pes.append((np.asarray(arrays["pe"], np.float64),
                        np.asarray(toy(arrays["z"]), np.float64)))
        (pe1, toy1), (pe2, toy2) = pes
        assert abs(pe1.mean() + BIG) < 1e4  # the potential itself, 1.2e7
        return float(np.max(np.abs((pe2 - pe1) - (toy2 - toy1))))

    assert worst(Shifted(D, G)) < 1e-3
    assert worst(ShiftedPlain(D, G)) >= 0.5


@pytest.mark.parametrize("kernel, kw", KERNELS, ids=IDS)
def test_checkpoint_holds_the_potential_and_resume_gives_the_same_draws(
        tmp_path, rows, kernel, kw):
    """(c)"""
    model = Shifted(D, G)
    whole = run(model, rows, kernel, kw)
    ck = str(tmp_path / "ck.npz")
    run(model, rows, kernel, kw, max_blocks=1, checkpoint_path=ck)
    arrays, meta = load_checkpoint(ck)
    # pe is the potential, in float64: the centre's constant plus the float32
    # that was carried, so taking the constant off again leaves a float32
    assert arrays["pe"].dtype == np.float64
    assert arrays["pe_center"].shape == (CHAINS, 1)
    carried = arrays["pe"] - arrays["pe_center"][:, 0].astype(np.float64)
    assert np.all(np.abs(arrays["pe"] + BIG) < 1e4)
    assert np.all(np.abs(carried) < 1e3)
    np.testing.assert_array_equal(
        carried, carried.astype(np.float32).astype(np.float64))
    resumed = run(model, rows, kernel, kw, resume_from=ck)
    np.testing.assert_array_equal(resumed.draws_flat, whole.draws_flat)


def test_a_checkpoint_without_a_centre_resumes_relative_to_zero(
        tmp_path, rows):
    """A file the plain programs wrote (no `pe_center`) is resumed by the
    programs that centre: the constant starts at 0 and the draws go on."""
    ck = str(tmp_path / "plain.npz")
    kernel, kw = KERNELS[0]
    run(HierLogistic(D, G), rows, kernel, kw, max_blocks=1,
        checkpoint_path=ck)
    assert "pe_center" not in load_checkpoint(ck)[0]

    class Centring(HierLogistic):  # the same potential, with a centre to take
        def log_lik(self, p, data):
            ll = HierLogistic.log_lik(self, p, data)
            return ll - data["ll_center"] if "ll_center" in data else ll

        def center_data(self, data, center):
            return {**data, "ll_center": center}

    resumed = run(Centring(D, G), rows, kernel, kw, resume_from=ck)
    assert resumed.draws_flat.shape[1] == 8
    assert np.all(np.isfinite(resumed.draws_flat))


def test_the_centred_state_wraps_the_plain_one():
    st = HMCState(jnp.zeros(3), jnp.zeros(()), jnp.zeros(3))
    both = CentredState(st, jnp.zeros(1))
    assert both.state is st and jax.tree.leaves(both)[-1].shape == (1,)


@pytest.mark.parametrize("kernel, kw", KERNELS, ids=IDS)
def test_on_a_data_mesh_the_same_path_carries_the_centre(
        tmp_path, kernel, kw):
    """`backends/sharded._segmented_parts` gets the carry from the one path:
    rows sharded over ``data``, chains over ``chains``, each shard's tile
    sums less its share of the chain's constant."""
    from stark_tpu.backends import ShardedBackend
    from stark_tpu.models import FusedLogistic
    from stark_tpu.parallel.mesh import make_mesh

    flat_rows = synth_logistic_data(jax.random.PRNGKey(2), N, D)[0]
    model = FusedLogistic(D)
    ck = str(tmp_path / "mesh.npz")
    res = stark_tpu.sample_until_converged(
        model, flat_rows,
        backend=ShardedBackend(make_mesh({"data": 4, "chains": 2})),
        chains=CHAINS, kernel=kernel, num_warmup=8, block_size=4,
        max_blocks=2, rhat_target=0.0, adaptive_blocks=False, min_blocks=1,
        seed=3, checkpoint_path=ck, **kw)
    root = [s for s in telemetry.span_log() if s.name == "run"][-1]
    assert root.fields["centred"] is True and root.fields["mesh_data"] == 4
    arrays, _ = load_checkpoint(ck)
    assert arrays["pe"].dtype == np.float64
    assert arrays["pe_center"].shape == (CHAINS, 1)
    # the checkpoint's pe is the potential: the plain one-device sum's
    fm = flatten_model(model)
    plain = jax.vmap(fm.bind(prepare_model_data(model, flat_rows)))(
        jnp.asarray(arrays["z"]))
    np.testing.assert_allclose(arrays["pe"], np.asarray(plain), atol=5e-3)
    np.testing.assert_array_equal(res.draws_flat[:, -1], arrays["z"])


def test_map_descent_before_the_per_chain_warm_up(rows):
    """`map_init_steps` under the per-chain kernels: the ensemble sampler's
    descent (`chees.map_descent`) before warm-up, under the same spans; a
    run without it compiles no such program."""
    kernel, kw = KERNELS[0]
    model = FusedHierLogisticGrouped(D, G)
    seen = []
    run(model, rows, kernel, kw, max_blocks=1, map_init_steps=7,
        progress_cb=seen.append)
    log = telemetry.span_log()
    last = max(s.run for s in log if s.name == "run")
    mine = [s for s in log if s.run == last]
    (descent,) = [s for s in mine if s.name == "map_init"]
    assert descent.fields["steps"] == 7
    assert descent.fields["grad_evals"] == 7 * CHAINS
    (warm,) = [s for s in mine if s.name == "warmup"]
    assert descent.end_ns <= warm.start_ns
    blocks = [s for s in mine if s.name == "warmup_block"]
    assert sum(s.fields["steps"] for s in blocks) == 12
    assert sum(s.fields["grad_evals"] for s in blocks) == warm.fields[
        "grad_evals"]
    (done,) = [r for r in seen if r.get("event") == "warmup_done"]
    assert done["warmup_grad_evals"] == warm.fields[
        "grad_evals"] + 7 * CHAINS
    run(model, rows, kernel, kw, max_blocks=1)
    last = max(s.run for s in telemetry.span_log() if s.name == "run")
    assert not [s for s in telemetry.span_log()
                if s.run == last and s.name == "map_init"]
