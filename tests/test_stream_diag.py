"""On-device streaming diagnostics + ESS-forecast adaptive block scheduler.

The tentpole contracts (runner.py / kernels/base.py / diagnostics.py):

* `ess_from_suffstats` is a conservative (lower-bound-leaning) estimate of
  the full-history Geyer ESS, computed from O(chains*d*L) accumulators;
* the device scan's `StreamDiagState` matches the host reference rebuild
  (`stream_diag_from_draws`) — the resume path depends on that;
* the streaming accumulator never perturbs the draw stream: stream-on and
  stream-off runs produce bit-identical draws/checkpoints/stores;
* `STARK_STREAM_DIAG=0 STARK_ADAPTIVE_BLOCKS=0` restores the historical
  fixed-block runner bit-exactly (the escape hatches);
* the convergence gate's host transfer is CONSTANT per block with
  streaming on, the ESS row and the draw counts (``diag_bytes_to_host``
  trace field): the O(chains*d*L) accumulator is reduced on the device, by
  the same `ess_from_suffstats` under ``jit``, one program for every count;
* adaptive scheduling converges in fewer post-warmup draws than the fixed
  march on the eight-schools benchmark at equal targets;
* the streaming gate can NEVER stop a run the full-pass validation rejects
  (drilled via the ``runner.gate.optimistic`` failpoint).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu import diagnostics, faults
from stark_tpu.checkpoint import load_checkpoint
from stark_tpu.kernels.base import (
    STREAM_DIAG_LAGS,
    stream_diag_init,
    stream_diag_update,
)
from stark_tpu.model import Model, ParamSpec
from stark_tpu.telemetry import RunTrace, read_trace, summarize_trace

_DIAG_FIELDS = ("n", "anchor", "s1", "s2", "cross", "ring", "head")


class StdNormal2(Model):
    def param_spec(self):
        return {"x": ParamSpec((2,))}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["x"] ** 2)

    def log_lik(self, p, data):
        return jnp.zeros(())


def _ar1(rng, phi, chains, n, d, mean=5.0):
    x = np.zeros((chains, n, d))
    innov = rng.standard_normal((chains, n, d))
    for t in range(1, n):
        x[:, t] = phi * x[:, t - 1] + innov[:, t] * np.sqrt(1 - phi**2)
    return x + mean


def _stream_ess(draws, lags=STREAM_DIAG_LAGS):
    st = diagnostics.stream_diag_from_draws(
        np.asarray(draws, np.float32), lags
    )
    return diagnostics.ess_from_suffstats(*[st[k] for k in _DIAG_FIELDS])


def test_ess_from_suffstats_tracks_full_ess_on_ar1():
    """Across AR(1) autocorrelation regimes the streaming estimator tracks
    the full-history Geyer ESS within tolerance, and never exceeds it by
    more than estimator noise — it must err LOW (the gate waits), never
    report a chain healthier than the full pass would."""
    rng = np.random.default_rng(0)
    for phi in (0.0, 0.3, 0.6, 0.9):
        x = _ar1(rng, phi, chains=4, n=2000, d=3)
        full = diagnostics.ess(x)
        stream = _stream_ess(x)
        assert np.all(np.isfinite(stream)), (phi, stream)
        # within-tolerance agreement when the autocorrelation resolves
        # inside the tracked lags (tau <= ~19 at phi=0.9, L=50)
        np.testing.assert_allclose(stream, full, rtol=0.15,
                                   err_msg=f"phi={phi}")
        assert np.all(stream <= full * 1.15), (phi, stream, full)


def test_ess_from_suffstats_conservative_when_truncated():
    """tau > L regime: the Geyer pair sequence cannot terminate inside the
    tracked lags, so the geometric tail extension must keep the estimate
    at or below the full-history value — the truncation bias direction is
    DOWN (conservative), so a slow-mixing run keeps sampling."""
    rng = np.random.default_rng(1)
    x = _ar1(rng, 0.99, chains=4, n=2000, d=3)  # tau ~ 199 >> L=50
    full = diagnostics.ess(x)
    stream = _stream_ess(x)
    assert np.all(stream <= full * 1.1), (stream, full)


def test_ess_from_suffstats_frozen_component_nan():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, 500, 2))
    x[:, :, 1] = 7.0  # frozen everywhere
    stream = _stream_ess(x)
    assert np.isfinite(stream[0])
    assert np.isnan(stream[1])


def test_device_accumulator_matches_host_reference():
    """The compiled scan's StreamDiagState == stream_diag_from_draws on
    the same draws (to roundoff) — the resume path rebuilds the device
    carry with the host reference, so they must be the same math."""
    rng = np.random.default_rng(3)
    draws = (rng.standard_normal((3, 37, 5)) * 2 + 1).astype(np.float32)
    lags = 8

    def run_chain(xs):
        def body(s, x):
            return stream_diag_update(s, x), None

        s, _ = jax.lax.scan(body, stream_diag_init(5, lags), xs)
        return s

    dev = jax.vmap(run_chain)(jnp.asarray(draws))
    host = diagnostics.stream_diag_from_draws(draws, lags)
    for k in _DIAG_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(dev, k)), host[k], rtol=2e-4, atol=2e-4,
            err_msg=k,
        )
    e_dev = diagnostics.ess_from_suffstats(
        *[np.asarray(getattr(dev, k)) for k in _DIAG_FIELDS]
    )
    e_host = diagnostics.ess_from_suffstats(*[host[k] for k in _DIAG_FIELDS])
    np.testing.assert_allclose(e_dev, e_host, rtol=1e-3)


# ---------------------------------------------------------------------------
# the estimator under jit: the runner's device program (`stark_stream_ess`)
# against the float64 host reference, on the same float32 accumulators
# ---------------------------------------------------------------------------

#: Why 1e-3.  Both sides start from the same float32 sums; the device then
#: works in float32 (eps 6e-8) where the reference works in float64.  What
#: float32 loses is in the differences: an autocovariance is a cross sum
#: less mean terms of its own size (the sums are anchored at the chain's
#: first draw, so the ratio is the chain's travel over its spread, squared:
#: some 4 for a chain that drifts a hundred standard deviations), and a
#: rho near zero is a difference of two numbers near one, summed over up to
#: 25 pairs.  That reads 1e-7 to 6e-6 on these chains over seeds and 1.3e-5
#: on the drifting one (CPU float32).  A Geyer cut that falls one pair
#: apart moves tau by a pair near zero (the running minimum caps what
#: follows it), so the estimate is continuous in its inputs.  1e-3 is
#: seventy times the worst reading, and a tenth of a draw in a forecast of
#: a hundred: nothing the block scheduler can see.
_DEVICE_RTOL = 1e-3


def _stream_ess_jit():
    return jax.jit(lambda *st: diagnostics.ess_from_suffstats(*st))


_ESS_J = _stream_ess_jit()  # shared: a shape compiles once for the file


def _device_and_reference(ess_j, draws, lags=STREAM_DIAG_LAGS):
    st = diagnostics.stream_diag_from_draws(
        np.asarray(draws, np.float32), lags)
    st = [st[k] for k in _DIAG_FIELDS]
    ref = diagnostics.ess_from_suffstats(*st)
    dev = ess_j(*[jnp.asarray(a) for a in st])
    assert ref.dtype == np.float64 and dev.dtype == jnp.float32
    return np.asarray(dev), ref


def _assert_device_matches(dev, ref):
    np.testing.assert_array_equal(np.isnan(dev), np.isnan(ref))
    ok = ~np.isnan(ref)
    np.testing.assert_allclose(dev[ok], ref[ok], rtol=_DEVICE_RTOL)


#: below, at and past the point where the 50 lags fill
_COUNTS = (3, 4, 5, 30, 50, 51, 52, 400)


@pytest.mark.parametrize("count", _COUNTS)
def test_device_ess_matches_reference_at_count(count):
    rng = np.random.default_rng(100 + count)
    x = _ar1(rng, 0.7, chains=4, n=count, d=5)
    dev, ref = _device_and_reference(_ESS_J, x)
    assert np.all(np.isnan(ref)) == (count < 4)
    _assert_device_matches(dev, ref)


def test_device_ess_is_one_program_for_every_count():
    """The draw count is data: lags not reached yet are masks over the
    full ``lags``, not shapes, so the whole sweep compiles once (on the
    chip: no compile inside the window while the lags fill)."""
    ess_j = _stream_ess_jit()  # its own: the cache below counts this sweep
    rng = np.random.default_rng(7)
    x = _ar1(rng, 0.5, chains=4, n=max(_COUNTS), d=5)
    for count in _COUNTS:
        _assert_device_matches(*_device_and_reference(ess_j, x[:, :count]))
    assert ess_j._cache_size() == 1


def _frozen_component(rng):
    x = rng.standard_normal((3, 200, 4))
    x[:, :, 1] = 7.0
    return x


def _all_frozen(rng):  # every chain stands still, each somewhere else
    return np.repeat(rng.standard_normal((3, 1, 4)), 200, axis=1)


def _one_chain(rng):
    return _ar1(rng, 0.6, chains=1, n=300, d=3)


def _drifting(rng):  # a hundred standard deviations over its length
    return (_ar1(rng, 0.5, chains=4, n=600, d=3)
            - np.linspace(0.0, 100.0, 600)[None, :, None])


def _unterminated(rng):  # tau ~ 199 >> L = 50
    return _ar1(rng, 0.99, chains=4, n=2000, d=3)


@pytest.mark.parametrize("make, n_nan", [
    (_frozen_component, 1), (_all_frozen, 4), (_one_chain, 0),
    (_drifting, 0), (_unterminated, 0)], ids=lambda v: getattr(
        v, "__name__", str(v)).lstrip("_"))
def test_device_ess_edge_cases(make, n_nan):
    """NaN where the reference says NaN (a frozen component fails the
    gate, never passes it), one chain (no between-chain term), a chain
    that comes down all window (the LMM cell's do), and the sequence the
    lags cannot terminate: the tail bound, still erring low."""
    # seed 1: the chains of `..._conservative_when_truncated` above
    x = make(np.random.default_rng(1))
    dev, ref = _device_and_reference(_ESS_J, x)
    assert int(np.isnan(ref).sum()) == n_nan
    _assert_device_matches(dev, ref)
    if make is _unterminated:
        assert np.all(dev <= diagnostics.ess(x) * 1.1)


def test_ragged_counts_raise_on_the_host():
    st = diagnostics.stream_diag_from_draws(
        np.zeros((3, 10, 2), np.float32), 4)
    st["n"] = np.array([10, 9, 10], np.int32)
    with pytest.raises(ValueError, match="ragged"):
        diagnostics.ess_from_suffstats(*[st[k] for k in _DIAG_FIELDS])
    with pytest.raises(ValueError, match="ragged"):
        diagnostics.uniform_count(st["n"])
    assert diagnostics.uniform_count(np.full((3,), 10, np.int32)) == 10


def _run(tmp_path, tag, **kw):
    d = tmp_path / tag
    d.mkdir()
    paths = {
        "ckpt": str(d / "c.npz"),
        "store": str(d / "d.stkr"),
        "metrics": str(d / "m.jsonl"),
    }
    post = stark_tpu.sample_until_converged(
        StdNormal2(),
        checkpoint_path=paths["ckpt"],
        draw_store_path=paths["store"],
        metrics_path=paths["metrics"],
        **kw,
    )
    return post, paths


_KW = dict(chains=2, block_size=20, max_blocks=3, min_blocks=3,
           rhat_target=0.0, num_warmup=30, kernel="hmc", num_leapfrog=4,
           seed=0)


def test_stream_on_off_draw_identity(tmp_path):
    """The accumulator only CONSUMES the draw stream: with fixed blocks,
    stream-on and stream-off runs produce bit-identical draws, checkpoint
    arrays, and draw-store bytes (only the gate's min_ess source and the
    new metrics fields differ)."""
    on, p_on = _run(tmp_path, "on", stream_diag=True,
                    adaptive_blocks=False, **_KW)
    off, p_off = _run(tmp_path, "off", stream_diag=False,
                      adaptive_blocks=False, **_KW)
    np.testing.assert_array_equal(on.draws_flat, off.draws_flat)
    a_on, _ = load_checkpoint(p_on["ckpt"])
    a_off, _ = load_checkpoint(p_off["ckpt"])
    assert set(a_on) == set(a_off)
    for k in a_on:
        np.testing.assert_array_equal(a_on[k], a_off[k], err_msg=k)
    with open(p_on["store"], "rb") as f:
        b_on = f.read()
    with open(p_off["store"], "rb") as f:
        b_off = f.read()
    assert b_on == b_off
    # the new metrics fields ride ONLY the streaming mode
    recs_off = [json.loads(l) for l in open(p_off["metrics"])]
    assert all("diag_bytes_to_host" not in r and "ess_forecast" not in r
               for r in recs_off)
    recs_on = [json.loads(l) for l in open(p_on["metrics"])]
    assert any("diag_bytes_to_host" in r for r in recs_on)


def test_escape_hatch_env_restores_fixed_march(tmp_path, monkeypatch):
    """STARK_STREAM_DIAG=0 STARK_ADAPTIVE_BLOCKS=0 == the explicit
    parameter opt-out: uniform block_size blocks, legacy metrics schema,
    bit-identical draws."""
    off, p_off = _run(tmp_path, "param", stream_diag=False,
                      adaptive_blocks=False, **_KW)
    monkeypatch.setenv("STARK_STREAM_DIAG", "0")
    monkeypatch.setenv("STARK_ADAPTIVE_BLOCKS", "0")
    env, p_env = _run(tmp_path, "env", **_KW)
    np.testing.assert_array_equal(off.draws_flat, env.draws_flat)
    steps = [r["draws_per_chain"] for r in env.history]
    assert steps == [20, 40, 60]  # uniform fixed march
    # identical metrics trail up to timing attribution
    strip = lambda rs: [  # noqa: E731
        {k: v for k, v in r.items()
         if k not in ("wall_s", "t_dispatch_s", "t_diag_s")}
        for r in rs
    ]
    assert strip(off.history) == strip(env.history)


def test_adaptive_budget_run_same_total_draws(tmp_path):
    """rhat_target=0 (budget-bounded): the adaptive scheduler draws
    exactly the fixed march's total — max_blocks*block_size per chain —
    only the block boundaries differ."""
    fixed, _ = _run(tmp_path, "fixed", adaptive_blocks=False, **_KW)
    adapt, _ = _run(tmp_path, "adapt", adaptive_blocks=True, **_KW)
    assert fixed.draws_flat.shape[1] == 60
    assert adapt.draws_flat.shape[1] == 60
    steps = [r["draws_per_chain"] for r in adapt.history]
    assert steps[-1] == 60 and steps[0] < 20  # geometric ramp start


def test_diag_bytes_constant_per_block(tmp_path):
    """With streaming on, the convergence gate's per-block host transfer
    is CONSTANT, and no accumulator is in it: the device reduces that to
    its ESS row behind the block, and the gate fetches the row and the
    draw counts — independent of the accumulated draw count AND of the
    lags; the legacy gate's grows with the history."""
    p = tmp_path / "t.jsonl"
    chains, d, lags = 2, 2, STREAM_DIAG_LAGS
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), trace=tr, stream_diag=True, adaptive_blocks=False,
            **_KW,
        )
    events = read_trace(str(p))
    blocks = [e for e in events if e["event"] == "sample_block"]
    assert len(blocks) == 3
    sizes = [e["diag_bytes_to_host"] for e in blocks]
    # the ESS row (d,) f32 + n (chains,) int32; the accumulator the row
    # came from stays on the device: n + (anchor,s1,s2):(d,) +
    # (cross,ring,head):(L,d) a chain
    expected = d * 4 + chains * 4
    assert sizes == [expected] * 3, (sizes, expected)
    assert expected < chains * 4 * (1 + 3 * d + 3 * lags * d)
    assert all(e["stream_diag"] is True for e in blocks)
    s = summarize_trace(events)
    assert s["diag"]["bytes_last"] == expected
    assert s["diag"]["bytes_max"] == expected
    assert s["diag"]["stream_diag"] is True

    # legacy gate: the transfer grows with the accumulated history
    p2 = tmp_path / "legacy.jsonl"
    with RunTrace(str(p2)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), trace=tr, stream_diag=False,
            adaptive_blocks=False, **_KW,
        )
    legacy = [e["diag_bytes_to_host"]
              for e in read_trace(str(p2)) if e["event"] == "sample_block"]
    assert legacy[0] < legacy[1] < legacy[2], legacy


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_adaptive_reduces_draws_eight_schools():
    """Acceptance: at equal targets on eight schools, the ESS-forecast
    scheduler converges in FEWER post-warmup draws than the fixed march
    (which can only stop on block_size boundaries), and both stops are
    full-pass validated."""
    from stark_tpu.models.eight_schools import EightSchools, eight_schools_data

    kw = dict(chains=4, block_size=400, min_blocks=1, max_blocks=4,
              rhat_target=1.05, ess_target=280.0, num_warmup=150,
              kernel="nuts", max_tree_depth=4, seed=0)
    fixed = stark_tpu.sample_until_converged(
        EightSchools(), eight_schools_data(), adaptive_blocks=False, **kw
    )
    adapt = stark_tpu.sample_until_converged(
        EightSchools(), eight_schools_data(), adaptive_blocks=True, **kw
    )
    assert fixed.converged and adapt.converged
    assert adapt.draws_flat.shape[1] < fixed.draws_flat.shape[1], (
        adapt.draws_flat.shape, fixed.draws_flat.shape
    )
    for post in (fixed, adapt):
        last = post.history[-1]
        assert last["full_min_ess"] > kw["ess_target"]
        assert last["full_max_rhat"] < kw["rhat_target"]
    # the overshoot estimate mirrors the draw saving
    assert adapt.overshoot_draws is not None
    assert fixed.overshoot_draws is not None
    assert adapt.overshoot_draws < fixed.overshoot_draws


def test_streaming_gate_never_stops_past_failed_validation():
    """Tier-1 guard: a (failpoint-forced) optimistic streaming gate makes
    the runner LOOK early, but the full-history validation pass still
    decides — with unreachable targets the run must never report
    convergence, and the rejected validations must be on record."""
    faults.reset()
    faults.configure("runner.gate.optimistic=nan*3")
    try:
        post = stark_tpu.sample_until_converged(
            StdNormal2(), chains=2, block_size=20, max_blocks=4,
            min_blocks=1, rhat_target=1.0001, ess_target=1e9,
            num_warmup=50, kernel="hmc", num_leapfrog=4, seed=0,
        )
    finally:
        faults.reset()
    assert not post.converged
    validated = [r for r in post.history if "full_min_ess" in r]
    assert validated, "forced-optimistic gate never reached validation"
    for r in validated:
        # every recorded validation REJECTED (ess target unreachable) —
        # and the run kept going: the last history record is not a stop
        assert r["full_min_ess"] < 1e9


def test_converged_stop_is_always_validated(tmp_path):
    """Every converged stop carries the full-pass record satisfying the
    targets — the streaming estimate alone can never stop a run."""
    post, _ = _run(
        tmp_path, "v", chains=4, block_size=50, max_blocks=8, min_blocks=1,
        rhat_target=1.2, ess_target=30.0, num_warmup=100, kernel="nuts",
        max_tree_depth=5, seed=0,
    )
    assert post.converged
    last = post.history[-1]
    assert last["full_min_ess"] > 30.0
    assert last["full_max_rhat"] < 1.2


def test_trace_report_renders_diag_table(tmp_path):
    """tools/trace_report.py surfaces the diagnostics-transfer table."""
    import importlib.util
    import io
    from contextlib import redirect_stdout

    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(StdNormal2(), trace=tr, **_KW)
    spec = importlib.util.spec_from_file_location(
        "trace_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_report.py"),
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert trace_report.main([str(p)]) == 0
    out = buf.getvalue()
    assert "diagnostics transfer" in out
    assert "gate transfer / block (last)" in out
    assert "streaming gate" in out
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert trace_report.main([str(p), "--json"]) == 0
    summary = json.loads(buf.getvalue())
    assert summary["diag"]["bytes_last"] > 0


def test_chees_stream_matches_plain_segment(tmp_path):
    """ChEES: the diag-carrying sample segment produces bit-identical
    draws to the plain one (the accumulator must not perturb the
    ensemble transitions)."""
    on, _ = _run(tmp_path, "on", chains=4, block_size=20, max_blocks=2,
                 min_blocks=2, rhat_target=0.0, num_warmup=40,
                 kernel="chees", map_init_steps=5, seed=1,
                 stream_diag=True, adaptive_blocks=False)
    off, _ = _run(tmp_path, "off", chains=4, block_size=20, max_blocks=2,
                  min_blocks=2, rhat_target=0.0, num_warmup=40,
                  kernel="chees", map_init_steps=5, seed=1,
                  stream_diag=False, adaptive_blocks=False)
    np.testing.assert_array_equal(on.draws_flat, off.draws_flat)


def test_resume_rebuilds_stream_state(tmp_path):
    """A resumed streaming run continues the accumulators from the stored
    draws: its post-resume gate sees the WHOLE history (min_ess keeps
    growing), and the adaptive ramp continues instead of restarting."""
    ckpt = str(tmp_path / "c.npz")
    p1 = stark_tpu.sample_until_converged(
        StdNormal2(), chains=2, block_size=50, max_blocks=2, min_blocks=2,
        rhat_target=0.5, num_warmup=100, kernel="hmc", num_leapfrog=8,
        seed=1, checkpoint_path=ckpt,
    )
    assert not p1.converged
    p2 = stark_tpu.sample_until_converged(
        StdNormal2(), block_size=50, max_blocks=4, min_blocks=2,
        rhat_target=0.5, num_warmup=100, kernel="hmc", num_leapfrog=8,
        resume_from=ckpt,
    )
    assert p2.num_samples == 200
    # the resumed run's first gate reading covers the resumed draws too
    first_resumed = p2.history[len(p1.history)]
    assert first_resumed["draws_per_chain"] > p1.history[-1]["draws_per_chain"]


@pytest.mark.slow
def test_sharded_backend_stream_and_adapt():
    """ShardedBackend: the chain-sharded diag carry runs under shard_map
    for both kernels; gate transfer stays O(chains*d*L)."""
    from stark_tpu.backends.sharded import ShardedBackend
    from stark_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 2, "chains": 4})
    for kern, kw in (
        ("nuts", dict(max_tree_depth=4)),
        ("chees", dict(map_init_steps=5)),
    ):
        post = stark_tpu.sample_until_converged(
            StdNormal2(), backend=ShardedBackend(mesh=mesh), chains=4,
            block_size=30, max_blocks=3, min_blocks=3, rhat_target=0.0,
            num_warmup=40, kernel=kern, seed=0, **kw,
        )
        sizes = {r.get("diag_bytes_to_host") for r in post.history}
        assert len(sizes) == 1 and None not in sizes, sizes
