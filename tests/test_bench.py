"""bench.py result-selection and denominator-extrapolation logic.

The driver metric must never report an unconverged ESS/s as the value when
a converged result exists (VERDICT r1 #1), the CPU extrapolation must
follow the measured cost curve, not a one-point linear assumption, and the
artifact must be timeout-proof (VERDICT r2 #1): best-so-far JSON lines are
emitted throughout, so a SIGKILL at any point leaves a parseable record.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_select_result_prefers_converged_over_faster_unconverged():
    results = [
        ("nuts fallback", 50.0, 1.8),  # fast but meaningless (unconverged)
        ("chees", 2.9, 1.008),
    ]
    tag, eps, rhat, converged = bench.select_result(results)
    assert tag == "chees" and eps == 2.9 and converged


def test_select_result_flags_unconverged_only():
    results = [("nuts fallback", 0.05, 1.8)]
    tag, eps, rhat, converged = bench.select_result(results)
    assert not converged and eps == 0.05


def test_select_result_best_among_converged():
    results = [("a", 1.0, 1.005), ("b", 3.0, 1.009), ("c", 9.9, 1.2)]
    tag, eps, rhat, converged = bench.select_result(results)
    assert tag == "b" and converged


def test_select_result_empty():
    assert bench.select_result([]) is None


def test_cpu_extrapolation_follows_cost_curve():
    # cost = 1ms + 1us/row: at n0=10k -> 11 ms/eval; at 1M -> 1.001 s/eval
    rec = {
        "n": 10_000,
        "ess_per_sec": 0.005,
        "fit": {"a": 1e-3, "b": 1e-6},
    }
    got = bench.cpu_ess_per_sec_at(1_000_000, rec)
    expected = 0.005 * (1e-3 + 1e-6 * 1e4) / (1e-3 + 1e-6 * 1e6)
    np.testing.assert_allclose(got, expected, rtol=1e-12)
    # the fixed overhead makes the fitted denominator LARGER (cpu faster)
    # than the legacy linear-in-N assumption — i.e. more honest to us
    legacy = {"n": 10_000, "ess_per_sec": 0.005}
    assert got > bench.cpu_ess_per_sec_at(1_000_000, legacy)


def test_cpu_extrapolation_legacy_record():
    legacy = {"n": 10_000, "ess_per_sec": 0.005}
    np.testing.assert_allclose(
        bench.cpu_ess_per_sec_at(1_000_000, legacy), 0.005 / 100.0
    )


def test_no_result_is_a_failed_run(monkeypatch, capsys):
    """Every leg switched off: bench.py has nothing to report, and says so
    with a non-zero exit instead of a zero-valued JSON line."""
    for k, v in {**_TINY_BENCH_ENV, "BENCH_CHEES": "0", "BENCH_FUSED": "0",
                 "BENCH_AUTODIFF": "0"}.items():
        monkeypatch.setenv(k, v)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "no leg produced a result" in str(exc.value.code)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert lines and all(l["partial"] for l in lines)


@pytest.mark.slow
def test_runner_time_budget_and_progress_cb():
    """time_budget_s stops after the first over-budget block (returning the
    draws so far, flagged), and progress_cb sees every metrics record."""
    import jax.numpy as jnp

    import stark_tpu
    from stark_tpu.model import Model, ParamSpec

    class StdNormal2(Model):
        def param_spec(self):
            return {"x": ParamSpec((2,))}

        def log_prior(self, p):
            return -0.5 * jnp.sum(p["x"] ** 2)

        def log_lik(self, p, data):
            return jnp.zeros(())

    events = []
    post = stark_tpu.sample_until_converged(
        StdNormal2(),
        chains=2,
        block_size=25,
        max_blocks=50,
        min_blocks=1,
        rhat_target=0.0,  # unreachable: only the budget can stop the run
        num_warmup=100,
        kernel="nuts",
        max_tree_depth=5,
        progress_cb=lambda r: events.append(r["event"]),
        time_budget_s=0.0,  # any elapsed time exceeds it
        seed=0,
    )
    assert post.budget_exhausted and not post.converged
    # exactly one block's draws kept (the adaptive scheduler's first
    # block is block_size//2; the fixed march's is block_size)
    assert post.draws_flat.shape[1] == post.history[-1]["draws_per_chain"]
    assert 0 < post.draws_flat.shape[1] <= 25
    assert events[0] == "warmup_done"
    assert events.count("block") == 1
    assert events[-1] == "budget_exhausted"


_TINY_BENCH_ENV = {
    # never litter the repo root with tiny-scale adaptation artifacts
    # (the committed capture-scale artifact must stay pristine)
    "BENCH_ADAPT_REUSE": "0",
    # ...and neither do tiny-scale rows in the committed perf ledger
    # (the documented =0 opt-out for exactly this case)
    "STARK_PERF_LEDGER": "0",
    "JAX_PLATFORMS": "cpu",
    "BENCH_N": "400",
    "BENCH_D": "4",
    "BENCH_GROUPS": "8",
    "BENCH_CHEES": "1",
    "BENCH_AUTODIFF": "0",
    "BENCH_CHEES_CHAINS": "4",
    "BENCH_CHEES_WARMUP": "40",
    "BENCH_CHEES_SAMPLES": "200",
    "BENCH_DISPATCH": "20",
    "BENCH_MAP_INIT": "20",
}


def _bench_proc(tmp_path, extra_env):
    env = {**os.environ, **_TINY_BENCH_ENV, **extra_env}
    err = open(tmp_path / "bench.stderr", "w")
    return subprocess.Popen(
        [sys.executable, "-u", bench.__file__],
        stdout=subprocess.PIPE,
        stderr=err,
        env=env,
        text=True,
    )


@pytest.mark.slow
def test_bench_emits_partials_and_respects_budget(tmp_path):
    """A full tiny run: best-so-far lines at start/warmup/blocks, and a
    small BENCH_TIME_BUDGET stops the draw budget early with the
    budget_exhausted flag on the final (non-partial) line.  The draw
    budget is set absurdly high (5000 blocks of host round-trips and
    checkpoint writes) so the time budget ALWAYS trips first, however
    fast the machine."""
    proc = _bench_proc(
        tmp_path,
        {"BENCH_TIME_BUDGET": "10", "BENCH_CHEES_SAMPLES": "100000"},
    )
    out, _ = proc.communicate(timeout=600)
    lines = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert len(lines) >= 3  # started + >=1 progress + final
    partials = [l for l in lines if l.get("partial")]
    assert partials[0]["phase"] == "starting"
    assert any(l["phase"] == "warmup_done" for l in partials)
    assert any(l["phase"].startswith("block") for l in partials)
    final = lines[-1]
    assert not final.get("partial")
    assert final["unit"] == "ess/sec/chip"
    assert final["budget_exhausted"] is True
    # profiling evidence rides the final line (PR 11): measured from the
    # supervised leg's trace here, and by contract null — never 0.0 —
    # when a trace can't say
    for k in ("compile_s", "dispatch_count", "span_coverage_frac"):
        assert k in final
        assert final[k] is None or final[k] > 0
    assert final["span_coverage_frac"] is None or (
        final["span_coverage_frac"] <= 1.0
    )
    # every line is independently parseable and carries the contract keys
    for l in lines:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(l)


@pytest.mark.slow
def test_bench_sigkill_mid_run_leaves_parseable_artifact(tmp_path):
    """SIGKILL after the first block partial: the captured stdout must still
    end with a parseable best-so-far JSON line (the r2 failure mode —
    rc=124, parsed: null — must be impossible by construction)."""
    proc = _bench_proc(tmp_path, {})
    out_lines = []

    def reader():
        # a hanging bench must not hang the test: the read loop lives in a
        # daemon thread and the main thread owns the deadline
        for line in proc.stdout:
            if line.strip():
                out_lines.append(line)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    deadline = time.time() + 600

    def saw_block_partial():
        for line in list(out_lines):
            rec = json.loads(line)
            if rec.get("partial") and rec.get("phase", "").startswith("block"):
                return True
        return False

    try:
        while time.time() < deadline and not saw_block_partial():
            time.sleep(0.5)
        assert saw_block_partial(), "no block partial before deadline"
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
        t.join(timeout=60)
    assert out_lines, "no output captured before kill"
    last = json.loads(out_lines[-1])
    assert last["partial"] and last["unit"] == "ess/sec/chip"
    assert {"metric", "value", "vs_baseline", "max_rhat"} <= set(last)
