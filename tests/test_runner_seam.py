"""The seam between the runner's block loop and its sampler
(`backends.base.BlockKernel`: `chees.CheesBlockKernel`,
`sampler.ChainBlockKernel`), and the checkpoint format behind it.

The format is pinned from outside the change that built the seam: the files
under `tests/fixtures/runner_ckpt_*` were written by the tree before it
(`_runner_ckpt_fixtures.py`)."""

import jax
import numpy as np
import pytest

from _runner_ckpt_fixtures import (
    CASES, ToyRegression, paths, resume_next_block, toy_rows, write_checkpoint,
)
from stark_tpu.backends import ShardedBackend
from stark_tpu.backends.base import KernelEnv
from stark_tpu.backends.jax_backend import JaxBackend
from stark_tpu.chees import CheesBlockKernel
from stark_tpu.checkpoint import load_checkpoint
from stark_tpu.sampler import ChainBlockKernel, SamplerConfig
from stark_tpu.telemetry import NullTrace

BLOCK = 4


def _kernel(kind, stream_diag=False):
    """A kernel of ``kind`` over toy rows, as the runner builds it."""
    if kind == "chees_centred":
        # a data-sharded potential of a model with `center_data`: the
        # programs carry the potential's centre beside small energies
        from stark_tpu.models import FusedLogistic
        from stark_tpu.models.logistic import synth_logistic_data
        from stark_tpu.parallel.mesh import make_mesh

        model = FusedLogistic(8)
        data, _ = synth_logistic_data(jax.random.PRNGKey(5), 4096, 8)
        backend = ShardedBackend(make_mesh(
            {"data": 4, "chains": 1}, devices=jax.devices()[:4]))
    else:
        model, data, backend = ToyRegression(), toy_rows(), JaxBackend()
    chees = kind.startswith("chees")
    cfg = SamplerConfig(
        kernel="chees" if chees else kind, num_warmup=8, map_init_steps=2,
        init_step_size=0.1, max_leapfrog=8, max_tree_depth=3, num_leapfrog=4,
    )
    chains = 8 if chees else 3
    ap = backend.adaptive_parts(model, cfg, data)
    env = KernelEnv(
        block_size=BLOCK, stream_diag=stream_diag, sync_blocks=False,
        diag_lags=5, seed=2, init_params=None, trace=NullTrace(),
        emit=lambda rec: None, model_name=type(model).__name__,
    )
    cls = CheesBlockKernel if chees else ChainBlockKernel
    return lambda: cls(ap, cfg, chains, env)


def _same(a, b):
    """Two pytrees of arrays, leaf for leaf and bit for bit."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize(
    "kind", ["chees", "chees_warmup", "chees_centred", "nuts", "hmc"])
def test_a_carry_survives_checkpoint_arrays_and_restore(kind):
    make = _kernel("chees" if kind == "chees_warmup" else kind)
    kernel, again = make(), make()
    if kind == "chees_warmup":
        # the full adaptation carry after one warm-up segment
        ap = kernel.ap
        key, key_warm = jax.random.split(jax.random.PRNGKey(0))
        carry = ap.init_j(
            key, ap.put_chains(0.1 * jax.random.normal(key, (8, 4))),
            *ap.extra)
        carry, _ = ap.warm_j(
            carry, jax.random.split(key_warm, 3), np.ones(3, np.float32),
            np.arange(3), np.zeros(3, bool), np.zeros(3, bool), *ap.extra)
        arrays = kernel.warm_checkpoint_arrays(carry, key, key_warm)
        assert arrays["step_size"] == np.exp(arrays["da_log_step"])
        _same(again.warm_carry_from(arrays), carry)
        # the file's way round: the resume finishes the warm-up from there
        got_key, _, fields = again.restore(
            arrays, {"kernel": "chees", "phase": "warmup", "warm_done": 3},
            None)
        _same(got_key, key)
        assert fields["resumed_from_step"] == 3 and again.carry is not None
        return
    key, _, _ = kernel.start()
    pend = kernel.dispatch(jax.random.PRNGKey(9), BLOCK, None, 0)
    pend.key = key
    arrays = kernel.checkpoint_arrays(pend)
    assert all(isinstance(v, np.ndarray) for v in arrays.values())
    got_key, _, fields = again.restore(
        dict(arrays), {"kernel": kernel.cfg.kernel}, None)
    assert fields is None  # a sampling-phase file: no warm-up ran
    _same(got_key, key)
    c = pend.carried
    if kind in ("nuts", "hmc"):
        assert set(arrays) == {
            "z", "pe", "grad", "step_size", "inv_mass", "key"}
        got = again.state
        _same((again.step_size, again.inv_mass),
              (c["step_size"], c["inv_mass"]))
    else:
        got = again.carry.states
        _same(again.carry._replace(states=None),
              kernel.carry._replace(states=None))
        _same(again.step_size, c["step_size"])
    _same((got.z, got.potential_energy, got.grad),
          (c["z"], c["pe"], c["grad"]))
    if kind == "chees_centred":
        # the file holds the potential itself, in float64, and the centre
        centre = np.asarray(kernel.carry.pe_center)
        assert arrays["pe"].dtype == np.float64
        np.testing.assert_array_equal(
            arrays["pe"],
            np.asarray(c["pe"], np.float64) + np.float64(centre))
        np.testing.assert_array_equal(arrays["pe_center"], centre)
        assert float(centre) != 0.0
    elif kind == "chees":
        assert "pe_center" not in arrays and arrays["pe"].dtype == np.float32
    # a reseeded restore branches the key and nothing else
    other = make()
    key2, _, _ = other.restore(dict(arrays), {"kernel": kernel.cfg.kernel}, 1)
    _same(key2, jax.random.fold_in(key, 1))


def test_a_checkpoint_without_a_kernel_record_is_refused_by_chees():
    with pytest.raises(ValueError, match="no kernel record"):
        _kernel("chees")().restore({}, {}, None)


@pytest.mark.parametrize("stream_diag", [False, True])
@pytest.mark.parametrize("kind", ["chees", "nuts", "hmc"])
def test_host_block_gives_what_the_loop_relies_on(kind, stream_diag):
    from stark_tpu import diagnostics
    from stark_tpu.kernels.base import StreamDiagState

    kernel = _kernel(kind, stream_diag)()
    kernel.start()
    assert kernel.stream_diag is stream_diag
    chains, d = kernel.chains, 4
    diag = None
    if stream_diag:
        diag = StreamDiagState(**diagnostics.stream_diag_from_draws(
            np.zeros((chains, 0, d), np.float32), 5, chains=chains, ndim=d,
            dtype=kernel.dtype))
    pend = kernel.dispatch(jax.random.PRNGKey(1), BLOCK, diag, 0)
    assert (pend.diag is not None) is stream_diag and pend.length == BLOCK
    assert set(pend.carried) == {"z", "pe", "grad", "step_size", "inv_mass"}
    hb = kernel.host_block(pend, energy=True)
    assert hb.zs.shape == (chains, BLOCK, d)
    assert hb.accept.shape == hb.divergent.shape == (chains, BLOCK)
    assert hb.mean_accept == float(np.mean(np.asarray(hb.accept)))
    if kind == "chees":
        # draw-major off the device, chains-major as a view: no copy
        assert hb.zs_dm.shape == (BLOCK, chains, d)
        assert np.shares_memory(hb.zs, hb.zs_dm)
        n_leap = np.asarray(pend.outs[3])
        assert n_leap.shape == (BLOCK,)
        assert hb.grad_evals == int(n_leap.sum()) * chains
        assert hb.energy is None and hb.ngrad is None
    else:
        assert hb.zs_dm is None
        assert hb.ngrad.shape == hb.energy.shape == (chains, BLOCK)
        assert hb.grad_evals == int(hb.ngrad.sum())
        if kind == "hmc":
            assert hb.grad_evals == chains * BLOCK * 4
        assert kernel.host_block(pend).energy is None  # only when asked
    assert hb.sched_fields == {}
    # the carried state is the last draw's
    np.testing.assert_array_equal(np.asarray(pend.carried["z"]), hb.zs[:, -1])


# -- the file format, pinned by the tree before the seam ---------------------


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_parent_written_checkpoint_resumes_with_the_parents_draws(name):
    ckpt, nxt = paths(name)
    np.testing.assert_array_equal(
        resume_next_block(name, ckpt), np.load(nxt))


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_fresh_checkpoint_has_the_fixtures_names_dtypes_and_meta(
        name, tmp_path):
    want_arrays, want_meta = load_checkpoint(paths(name)[0])
    fresh = str(tmp_path / "fresh.npz")
    write_checkpoint(name, fresh)
    arrays, meta = load_checkpoint(fresh)
    assert ({k: (v.dtype, v.shape) for k, v in arrays.items()}
            == {k: (v.dtype, v.shape) for k, v in want_arrays.items()})
    assert set(meta) == set(want_meta)


def test_one_chips_centred_file_holds_the_potential_and_its_centre():
    # written by a JaxBackend run of a model with `center_per_chain` (PR 32);
    # the two cases above resume it with its `pe_center`, bit for bit
    arrays, meta = load_checkpoint(paths("chees_centred")[0])
    assert meta["kernel"] == "chees" and meta["model"].endswith("Grouped")
    # a row a chain: the constant, then what the model keeps of the position
    # it was taken at (the noise scale, its logarithm, its inverse square)
    centre = arrays["pe_center"]
    assert arrays["pe"].dtype == np.float64 and centre.shape == (8, 4)
    assert np.all(centre[:, 0] != 0) and np.all(centre[:, 1] > 0)
    np.testing.assert_allclose(
        centre[:, 2:], np.stack([np.log(centre[:, 1]), centre[:, 1] ** -2.0], 1),
        rtol=1e-6, atol=1e-6)
    plain, _ = load_checkpoint(paths("chees_sample")[0])
    assert "pe_center" not in plain and plain["pe"].dtype == np.float32
