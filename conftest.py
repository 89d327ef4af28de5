# Root conftest: force a deterministic 8-device CPU platform for the whole
# test suite BEFORE jax is imported anywhere (SURVEY.md §5: multi-device
# without a cluster via xla_force_host_platform_device_count).
#
# Tests are correctness checks and must run the same on every machine, so
# they never land on an accelerator: the platform is hard-overridden here
# (setdefault is not enough when the environment exports JAX_PLATFORMS),
# which is honored because jax backends initialize lazily at first use —
# after this file runs.  The chip is checked by chip_smoke.py, not by tests.
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Hermeticity: the suite must never pick up an operator's committed
# autotuned profile (bench_artifacts/profiles/) — STARK_PROFILE unset
# means "auto" by design (stark_tpu.profile), so default it off here.
# Profile tests monkeypatch/subprocess their own value over this.
os.environ.setdefault("STARK_PROFILE", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# If something imported jax before this file ran (a sitecustomize, a pytest
# plugin), its config has already captured JAX_PLATFORMS from the
# environment and the os.environ write above is too late for it.
# jax.config.update works any time before the backend actually initializes
# (first jax.devices()/dispatch), which is still in the future here.
# XLA_FLAGS is read at CPU-backend init, so the env write above does work.
import jax

jax.config.update("jax_platforms", "cpu")
