"""The device-facing rules of PR 21: an unreachable or unknown device is an
error, nothing needs a second (CPU) backend, the compile cache sits at one
fixed place, and a failed benchmark fails the command."""

import os

import jax
import numpy as np
import pytest

import stark_tpu
from stark_tpu import platform as plat
from stark_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_info_is_what_jax_reports():
    info = telemetry.device_info()
    dev = jax.local_devices()[0]
    assert info["platform"] == dev.platform == "cpu"
    assert info["device_kind"] == dev.device_kind
    assert info["device_count"] == jax.device_count()


def test_fingerprint_raises_when_the_backend_is_unreachable(monkeypatch):
    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(plat, "_FINGERPRINT", None)
    monkeypatch.setattr(telemetry, "device_info", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        plat.hardware_fingerprint()
    assert plat._FINGERPRINT is None  # no "unknown-..." key was cached


def test_unusable_cache_directory_is_loud(tmp_path, monkeypatch):
    blocker = tmp_path / "a_file"
    blocker.write_text("")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(plat, "_REPO_CACHE_DIR", str(blocker / ".jax_cache"))
    prev = jax.config.jax_compilation_cache_dir
    with pytest.raises(OSError):
        plat.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == prev


def test_supervised_sample_never_keys_the_cache_on_its_workdir(
    tmp_path, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        out = stark_tpu.supervised_sample(
            object(), None, workdir=str(tmp_path),
            _runner=lambda *a, **kw: "ran",
        )
        assert out == "ran"
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
        assert not (tmp_path / ".jax_cache").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cli_run_places_the_cache_before_running(monkeypatch, capsys):
    import stark_tpu.__main__ as cli
    from stark_tpu import config

    calls = []
    monkeypatch.setattr(
        plat, "enable_compilation_cache", lambda: calls.append("cache")
    )
    monkeypatch.setattr(
        config, "run_config_file",
        lambda path: calls.append(path) or {"name": "x"},
    )
    assert cli.main(["run", "some.yaml"]) == 0
    assert calls == ["cache", "some.yaml"]
    assert '"name": "x"' in capsys.readouterr().out


def _no_second_backend(monkeypatch):
    real = jax.local_devices

    def local_devices(*args, **kwargs):
        assert not args and not kwargs, "asked for a specific backend"
        return real()

    monkeypatch.setattr(jax, "local_devices", local_devices)


def test_constrain_draws_needs_no_cpu_backend(monkeypatch):
    from stark_tpu.models import EightSchools
    from stark_tpu.sampler import _constrain_draws

    _no_second_backend(monkeypatch)
    fm = stark_tpu.flatten_model(EightSchools())
    zs = np.random.default_rng(0).normal(size=(2, 3, fm.ndim)).astype("f4")
    draws = _constrain_draws(fm, zs)
    want = fm.constrain(zs[1, 2])
    for k, v in draws.items():
        assert v.shape[:2] == (2, 3)
        np.testing.assert_allclose(v[1, 2], np.asarray(want[k]), rtol=1e-6)


def test_pointwise_log_lik_needs_no_cpu_backend(monkeypatch):
    from stark_tpu import compare
    from stark_tpu.models import EightSchools, eight_schools_data

    _no_second_backend(monkeypatch)
    model, data = EightSchools(), eight_schools_data()
    fm = stark_tpu.flatten_model(model)
    zs = np.random.default_rng(1).normal(size=(2, 4, fm.ndim)).astype("f4")
    post = stark_tpu.Posterior(
        {k: np.asarray(v) for k, v in
         jax.vmap(jax.vmap(fm.constrain))(zs).items()}, {},
    )
    ll = compare.pointwise_log_lik(model, post, data)
    assert ll.shape == (2, 4, 8) and np.isfinite(ll).all()


class _Result:
    name, metric_name, gate = "ok_bench", "ess/s", "rhat"
    ess_per_sec, min_ess, wall_s, max_rhat, extra = 1.0, 100.0, 1.0, 1.001, {}

    def row(self):
        return "ok_bench row"

    def passed(self):
        return True


@pytest.mark.parametrize("broken,code", [(False, 0), (True, 1)])
def test_bench_all_exit_code_carries_a_failed_row(
    monkeypatch, capsys, broken, code
):
    import stark_tpu.__main__ as cli
    from stark_tpu import benchmarks

    def boom():
        raise RuntimeError("leg died")

    table = {"ok_bench": _Result}
    if broken:
        table["broken_bench"] = boom
    monkeypatch.setattr(benchmarks, "ALL_BENCHMARKS", table)
    monkeypatch.setattr(plat, "enable_compilation_cache", lambda: None)
    assert cli.main(["bench-all"]) == code
    out = capsys.readouterr().out
    assert "| ok_bench |" in out
    assert ("FAILED: RuntimeError" in out) == broken
