"""STARK_RAGGED_NUTS: step-synchronized NUTS block scheduling.

The contract (kernels/nuts_ragged.py): with the knob ON, every lane of a
vmapped NUTS block advances its own tree — one batched gradient
evaluation per lane per loop iteration — and the per-lane op/key
sequence is EXACTLY the legacy nested scan's, so draws / accept stats /
divergences / energies / grad counts / streaming-diag accumulators /
checkpoints are equal to rounding (`_assert_same`: integers exactly,
floats to 1e-5 relative) on the single-runner and fleet paths, per
lane, independent of batch composition and across crash-resume replay.
With the knob OFF (default) nothing changes: no ragged code runs and the
metrics/trace trails carry none of the scheduling fields.

Plus the occupancy story: lane_iters accounting in the carry, the
useful-grad fraction strictly improving on a mixed-depth synthetic, and
the scheduler fields surfacing in traces / summarize_trace.

Cost discipline: ONE shared model/backend (the runner caches compiled
segments per (model, cfg) on the backend instance) and ONE shared
FleetSpec (fleet parts cache per (model, cfg)) across every end-to-end
run here, so the file pays each scheduler's XLA compile once.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stark_tpu import faults
from stark_tpu.backends.jax_backend import JaxBackend
from stark_tpu.checkpoint import load_checkpoint
from stark_tpu.fleet import FleetSpec, sample_fleet
from stark_tpu.kernels.base import init_state, stream_diag_init
from stark_tpu.kernels.nuts_ragged import ragged_nuts_enabled
from stark_tpu.model import flatten_model, prepare_model_data
from stark_tpu.models import EightSchools, eight_schools_data
from stark_tpu.models.eight_schools import SIGMA, Y
from stark_tpu.runner import sample_until_converged
from stark_tpu.sampler import SamplerConfig, make_block_runner
from stark_tpu.telemetry import RunTrace, read_trace, summarize_trace

#: fields that legitimately differ (timing) or ride only knob-on runs
_TIMING_KEYS = ("wall_s", "t_dispatch_s", "t_diag_s")
_SCHED_KEYS = ("ragged_nuts", "sched_iters", "lane_occupancy")

#: ONE model / data / backend for every single-runner test: the backend
#: caches compiled warmup segments + block runners per (model, cfg), so
#: knob-on/off/crash/resume runs share every legacy compile and pay the
#: ragged compile once
_MODEL = EightSchools()
_DATA = eight_schools_data()
_BACKEND = JaxBackend()


def _strip(history, extra=()):
    drop = set(_TIMING_KEYS) | set(_SCHED_KEYS) | set(extra)
    return [
        {k: v for k, v in rec.items() if k not in drop} for rec in history
    ]


def _assert_same(a, b):
    """What the platform promises of the two schedulers: they are two XLA
    programs over the same per-lane op and key sequence, so every INTEGER
    output (divergences, gradient counts, tree depths, lane_iters) is
    exactly equal, and floats agree to rounding — XLA fuses and orders the
    float arithmetic of each program on its own (observed on XLA:CPU, jax
    0.9: 4 of 42 acceptance rates 2e-6 apart, accumulator sums 1e-7).
    Arrays, scalars and (nested) records alike."""
    if isinstance(a, dict):
        assert sorted(a) == sorted(b)
        for k in a:
            _assert_same(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_same(x, y)
    elif a is None or isinstance(a, (str, bool)):
        assert a == b
    else:
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)


def _block_fixture(chains=3, block=14, max_depth=6, seed=0,
                   steps=(0.25, 0.06, 0.45)):
    fm = flatten_model(_MODEL)
    pdata = prepare_model_data(_MODEL, _DATA)
    cfg = SamplerConfig(kernel="nuts", max_tree_depth=max_depth)
    pot = fm.bind(pdata)
    kz, kb = jax.random.split(jax.random.PRNGKey(seed))
    z0 = jax.vmap(fm.init_flat)(jax.random.split(kz, chains))
    state = jax.vmap(lambda z: init_state(pot, z))(z0)
    step = jnp.asarray(steps[:chains], jnp.float32)
    inv = jnp.ones((chains, fm.ndim), jnp.float32)
    bkeys = jax.random.split(kb, chains)
    return fm, pdata, cfg, state, step, inv, bkeys, block


def test_block_runner_bit_identity():
    """The core contract at the kernel boundary: every output of the
    ragged block runner equals the legacy scan's (`_assert_same`), and the
    carry's lane_iters equals the lane's useful grad evals (one leaf per
    live iteration by construction)."""
    fm, pdata, cfg, state, step, inv, bkeys, block = _block_fixture()
    legacy = jax.jit(jax.vmap(
        make_block_runner(fm, cfg, block), in_axes=(0, 0, 0, 0, None)))
    ragged = jax.jit(jax.vmap(
        make_block_runner(fm, cfg, block, ragged=True),
        in_axes=(0, 0, 0, 0, None)))
    out_l = jax.block_until_ready(legacy(bkeys, state, step, inv, pdata))
    out_r = jax.block_until_ready(ragged(bkeys, state, step, inv, pdata))
    # (state, zs, accept, divergent, energy, ngrad [, lane_iters])
    _assert_same(jax.tree.leaves(out_l[:6]), jax.tree.leaves(out_r[:6]))
    lane_iters = np.asarray(out_r[6])
    np.testing.assert_array_equal(lane_iters, np.asarray(out_r[5]).sum(1))
    # the step-size spread really produced ragged lanes (else this file
    # tests nothing): the slow lane did >2x the fastest lane's work
    assert lane_iters.max() > 2 * lane_iters.min()


def test_block_runner_diag_bit_identity():
    """The streaming-diagnostics variant: the StreamDiagState carried
    through the ragged loop matches the legacy scan's leaf-for-leaf."""
    fm, pdata, cfg, state, step, inv, bkeys, block = _block_fixture()
    lags = 8
    diag0 = jax.vmap(lambda _: stream_diag_init(fm.ndim, lags))(
        jnp.arange(state.z.shape[0])
    )
    legacy = jax.jit(jax.vmap(
        make_block_runner(fm, cfg, block, diag_lags=lags),
        in_axes=(0, 0, 0, 0, 0, None)))
    ragged = jax.jit(jax.vmap(
        make_block_runner(fm, cfg, block, diag_lags=lags, ragged=True),
        in_axes=(0, 0, 0, 0, 0, None)))
    out_l = legacy(bkeys, state, diag0, step, inv, pdata)
    out_r = ragged(bkeys, state, diag0, step, inv, pdata)
    _assert_same(jax.tree.leaves(out_l), jax.tree.leaves(out_r[:7]))


def test_lane_sequence_independent_of_batch():
    """Property test: a lane's per-step leapfrog/accept sequence (hence
    its draws) depends only on its own key/state/step — swapping its
    batch NEIGHBORS for lanes of very different tree depths changes
    nothing, bitwise.  (Same batch WIDTH on both sides: XLA respecializes
    per width with different fusion/rounding, which perturbs even the
    legacy kernel at the ulp level — composition independence, not
    width independence, is the scheduling contract.)"""
    fm, pdata, cfg, state, step, inv, bkeys, block = _block_fixture(
        chains=3, steps=(0.25, 0.06, 0.45))
    ragged = jax.jit(jax.vmap(
        make_block_runner(fm, cfg, block, ragged=True),
        in_axes=(0, 0, 0, 0, None)))

    def lane(tree, i):
        return jax.tree.map(lambda a: np.asarray(a)[i], tree)

    def take(idx):
        ix = jnp.asarray(idx)
        return jax.tree.map(lambda a: a[ix], (bkeys, state, step, inv))

    # lane 0 paired with the DEEP lane vs with the SHALLOW lane: its
    # own iteration count differs wildly relative to the batch's, but
    # its outputs must not move a bit
    with_deep = ragged(*take([0, 1]), pdata)
    with_shallow = ragged(*take([0, 2]), pdata)
    for a, b in zip(
        jax.tree.leaves(lane(with_deep[:6], 0)),
        jax.tree.leaves(lane(with_shallow[:6], 0)),
    ):
        np.testing.assert_array_equal(a, b)
    # and its per-lane iteration accounting is its own too
    assert np.asarray(with_deep[6])[0] == np.asarray(with_shallow[6])[0]


_RUN_KW = dict(
    chains=3, block_size=15, max_blocks=3, min_blocks=1, rhat_target=0.0,
    ess_target=1e9, num_warmup=30, kernel="nuts", max_tree_depth=6,
    seed=3, adaptive_blocks=False,
)


def _run_single(workdir, ragged, **kw):
    os.environ["STARK_RAGGED_NUTS"] = "1" if ragged else "0"
    try:
        trace_path = str(workdir / "t.jsonl")
        res = sample_until_converged(
            _MODEL, _DATA, backend=_BACKEND,
            checkpoint_path=str(workdir / "c.npz"),
            metrics_path=str(workdir / "m.jsonl"),
            trace=RunTrace(trace_path),
            **{**_RUN_KW, **kw},
        )
    finally:
        os.environ.pop("STARK_RAGGED_NUTS", None)
    return res, workdir, trace_path


@pytest.fixture(scope="module")
def single_runs(tmp_path_factory):
    """One knob-off and one knob-on adaptive-runner run (shared backend:
    the second pays only the ragged block compile) with full persistence
    + traces — shared by the identity, trace-purity, and resume tests."""
    td = tmp_path_factory.mktemp("ragged_runner")
    out = {}
    for tag, ragged in (("off", False), ("on", True)):
        d = td / tag
        d.mkdir()
        out[tag] = _run_single(d, ragged)
    return out


def test_runner_bit_identity_and_trace_fields(single_runs):
    """End-to-end through the adaptive runner: knob on vs off produce
    the same draws, metrics history (modulo timing + the knob-on
    scheduling fields; ``ess_forecast`` is an integer cut from float
    arithmetic, so it is held as a float) and checkpoints, to rounding
    (`_assert_same`); the knob-on trace carries the
    occupancy fields and summarize_trace's nutssched section; the
    knob-off trails carry NONE of them (byte-compat with pre-knob
    runs)."""
    res_off, d_off, tp_off = single_runs["off"]
    res_on, d_on, tp_on = single_runs["on"]
    _assert_same(res_off.draws_flat, res_on.draws_flat)
    forecast = lambda h: [float(r.get("ess_forecast", 0)) for r in h]  # noqa: E731
    _assert_same(_strip(res_off.history, extra=("ess_forecast",)),
                 _strip(res_on.history, extra=("ess_forecast",)))
    _assert_same(forecast(res_off.history), forecast(res_on.history))
    a_off, _ = load_checkpoint(str(d_off / "c.npz"))
    a_on, _ = load_checkpoint(str(d_on / "c.npz"))
    _assert_same(a_off, a_on)
    # metrics JSONL: knob-off lines carry no scheduling keys at all
    off_recs = [json.loads(l) for l in open(d_off / "m.jsonl")]
    on_recs = [json.loads(l) for l in open(d_on / "m.jsonl")]
    assert not any(k in r for r in off_recs for k in _SCHED_KEYS)
    on_blocks = [r for r in on_recs if r.get("event") == "block"]
    assert on_blocks and all(
        r.get("ragged_nuts") is True
        and 0.0 < r["lane_occupancy"] <= 1.0
        and r["sched_iters"] > 0
        for r in on_blocks
    )
    # trace events mirror the same split
    ev_off = read_trace(tp_off)
    ev_on = read_trace(tp_on)
    assert not any(k in e for e in ev_off for k in _SCHED_KEYS)
    s_on = summarize_trace(ev_on)
    assert s_on["nutssched"]["ragged"] is True
    assert 0.0 < s_on["nutssched"]["occupancy_min"] <= 1.0
    assert s_on["nutssched"]["blocks"] == len(on_blocks)
    assert summarize_trace(ev_off)["nutssched"] == {}


def test_runner_resume_replay(single_runs, tmp_path):
    """Crash-resume under the knob: a ragged run resumed from its
    block-1 checkpoint replays to the SAME draws as the uninterrupted
    legacy run (checkpoints carry no scheduler state — the knob can even
    flip across the restart)."""
    res_off, _d, _tp = single_runs["off"]
    ck = str(tmp_path / "c.npz")
    os.environ["STARK_RAGGED_NUTS"] = "1"
    faults.configure("runner.block.post=crash@1")
    try:
        with pytest.raises(faults.InjectedFault):
            sample_until_converged(
                _MODEL, _DATA, backend=_BACKEND, checkpoint_path=ck,
                **_RUN_KW,
            )
        faults.configure(None)
        resumed = sample_until_converged(
            _MODEL, _DATA, backend=_BACKEND, checkpoint_path=ck,
            resume_from=ck, **_RUN_KW,
        )
    finally:
        faults.configure(None)
        os.environ.pop("STARK_RAGGED_NUTS", None)
    np.testing.assert_array_equal(res_off.draws_flat, resumed.draws_flat)


#: ONE fleet spec for every fleet test: `fleet._PARTS_CACHE` keys on the
#: (model, cfg) pair, so the runs below share the compiled fleet parts
def _make_fleet_spec(n=3, seed=0):
    rng = np.random.default_rng(seed)
    y, sig = np.asarray(Y), np.asarray(SIGMA)
    return FleetSpec.from_problems(
        _MODEL,
        [{"y": (y + rng.normal(0, 2.0, y.shape)).astype(np.float32),
          "sigma": sig} for _ in range(n)],
    )


_FLEET_SPEC = _make_fleet_spec()

_FLEET_KW = dict(
    chains=2, block_size=15, max_blocks=3, min_blocks=1, num_warmup=30,
    ess_target=1e9, rhat_target=0.0, seed=0, kernel="nuts",
    max_tree_depth=6,
)


def _run_fleet(ragged, **kw):
    os.environ["STARK_RAGGED_NUTS"] = "1" if ragged else "0"
    try:
        return sample_fleet(_FLEET_SPEC, **{**_FLEET_KW, **kw})
    finally:
        os.environ.pop("STARK_RAGGED_NUTS", None)


@pytest.fixture(scope="module")
def fleet_runs(tmp_path_factory):
    """One legacy and one ragged fleet run over the shared spec, with
    metrics — shared by the fleet identity and crash-resume tests."""
    td = tmp_path_factory.mktemp("ragged_fleet")
    out = {}
    for tag, ragged in (("off", False), ("on", True)):
        d = td / tag
        d.mkdir()
        out[tag] = (
            _run_fleet(ragged, metrics_path=str(d / "m.jsonl")), d
        )
    return out


def test_fleet_bit_identity(fleet_runs):
    """The fleet path (doubly-vmapped lanes): ragged vs legacy per-problem
    draws are bit-identical, and the knob-on fleet metrics carry the
    lane-occupancy fields while knob-off ones don't."""
    res_off, d_off = fleet_runs["off"]
    res_on, d_on = fleet_runs["on"]
    for a, b in zip(res_off.problems, res_on.problems):
        np.testing.assert_array_equal(a.draws_flat, b.draws_flat)
    off_recs = [json.loads(l) for l in open(d_off / "m.jsonl")]
    on_recs = [json.loads(l) for l in open(d_on / "m.jsonl")]
    assert not any(k in r for r in off_recs for k in _SCHED_KEYS)
    fb = [r for r in on_recs if r.get("event") == "fleet_block"]
    assert fb and all(
        r.get("ragged_nuts") is True and 0.0 < r["lane_occupancy"] <= 1.0
        for r in fb
    )


def test_fleet_crash_resume_replay(fleet_runs, tmp_path):
    """Fleet crash-resume under the knob: the resumed ragged fleet
    replays to draws bit-identical to the uninjected legacy fleet."""
    baseline, _d = fleet_runs["off"]
    ck = str(tmp_path / "fleet.ckpt.npz")
    faults.configure("fleet.block.post=crash@1")
    try:
        with pytest.raises(faults.InjectedFault):
            _run_fleet(True, checkpoint_path=ck)
    finally:
        faults.configure(None)
    resumed = _run_fleet(True, checkpoint_path=ck, resume_from=ck)
    for a, b in zip(baseline.problems, resumed.problems):
        np.testing.assert_array_equal(a.draws_flat, b.draws_flat)


def test_occupancy_monotone_on_mixed_depths():
    """Occupancy monotonicity: on lanes of deliberately different tree
    depths the ragged schedule never executes MORE batched gradient
    evaluations than the legacy nested loops, and its useful-grad
    fraction is at least the legacy one (strictly better when the lanes
    actually de-synchronize — which the fixture's equal-step
    per-transition depth variance guarantees)."""
    from stark_tpu.benchmarks import _GradEvalProbe

    # near-exchangeable lanes: per-transition depth variance makes the
    # argmax lane CHANGE across rounds, which is exactly when the legacy
    # max-lane sync wastes evaluations (a single always-deepest lane is
    # the one case where legacy is already tight — the octave-spread
    # fixture above lands there, so this test uses equal steps)
    chains = 6
    fm, pdata, cfg, state, step, inv, bkeys, block = _block_fixture(
        chains=chains, block=24, steps=(0.15,) * chains)
    probe = _GradEvalProbe(fm)
    probe.calls = 0
    jax.block_until_ready(
        jax.jit(jax.vmap(probe.bind(pdata).value_and_grad))(state.z)
    )
    per_eval = max(probe.snapshot(), 1)
    executed = {}
    useful = None
    for name, ragged in (("legacy", False), ("ragged", True)):
        fn = jax.jit(jax.vmap(
            make_block_runner(probe, cfg, block, ragged=ragged),
            in_axes=(0, 0, 0, 0, None)))
        probe.calls = 0
        out = jax.block_until_ready(fn(bkeys, state, step, inv, pdata))
        executed[name] = probe.snapshot() // per_eval
        u = int(np.asarray(out[5]).sum())
        assert useful is None or useful == u  # identical useful work
        useful = u
        if ragged:
            # carry accounting == dispatch-probe truth
            assert executed[name] == int(np.asarray(out[6]).max())
    assert executed["ragged"] <= executed["legacy"]
    occ = {k: useful / (v * chains) for k, v in executed.items()}
    assert occ["ragged"] > occ["legacy"]


def test_knob_and_config_gating(monkeypatch):
    """ragged_nuts_enabled: default off; on only for NUTS configs with
    no in-scan heartbeat.  make_block_runner(ragged=True) refuses
    non-NUTS kernels loudly."""
    monkeypatch.delenv("STARK_RAGGED_NUTS", raising=False)
    assert not ragged_nuts_enabled()
    monkeypatch.setenv("STARK_RAGGED_NUTS", "1")
    assert ragged_nuts_enabled()
    assert ragged_nuts_enabled(SamplerConfig(kernel="nuts"))
    assert not ragged_nuts_enabled(SamplerConfig(kernel="hmc"))
    assert not ragged_nuts_enabled(
        SamplerConfig(kernel="nuts", progress_every=10)
    )
    fm = flatten_model(_MODEL)
    with pytest.raises(ValueError, match="NUTS"):
        make_block_runner(
            fm, SamplerConfig(kernel="hmc"), 10, ragged=True
        )
