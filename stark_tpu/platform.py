"""What the process can say about the device it runs on, and where its
compiled programs are kept.

Nothing here chooses a platform: jax runs on what ``JAX_PLATFORMS`` (or
its own discovery) gives it, and a platform that cannot be reached is an
error for the caller, not something to route around.
"""

from __future__ import annotations

import os
from typing import Optional

#: the persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a FIXED path in the checkout (the directory is part of the
#: cache key, so a path built from a workdir, a temporary name, a pid or
#: the time never hits)
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def named_jit(fn, name: str, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under a fixed program name.  jax
    names a compiled program after the function's ``__name__``
    (``jit_<name>``: the event of the profiler's modules line, the HLO
    module, the compile log), and a trace reduction that keys on it has
    to survive a refactor of the function behind it.  The names in use:
    ``stark_chees_init`` / ``stark_chees_warm`` / ``stark_chees_sample``
    (the ensemble sampler's three programs), ``stark_nuts_block`` /
    ``stark_hmc_block`` (the per-chain kernels' draw block:
    `sampler.block_program`), ``stark_stream_ess`` (the
    streaming gate's reduction of a block's accumulator to its ESS row)
    and ``stark_constrain`` (the final layout of all draws)."""
    import functools

    import jax

    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, **jit_kwargs)


def enable_compilation_cache() -> str:
    """Make sure jax's persistent compilation cache has a directory and
    return the one in effect.  Called by every entry point that compiles
    the big programs (CLI, bench.py, chip_smoke.py, `supervised_sample`).

    ``JAX_COMPILATION_CACHE_DIR`` set (jax reads it itself) -> nothing is
    touched and no other directory is set in code; otherwise
    ``<checkout>/.jax_cache``.  A directory that cannot be created raises:
    a run that silently compiles everything again on every start is not
    the run that was asked for.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    os.makedirs(_REPO_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    return _REPO_CACHE_DIR


def device_memory_stats() -> "list[dict]":
    """Per-local-device memory statistics, best-effort.

    Returns ``[{"device": "0", "kind": "TPU v4", "stats": {...}}, ...]``
    with ``stats`` straight from PJRT's ``Device.memory_stats()``
    (``bytes_in_use``, ``peak_bytes_in_use``, ``bytes_limit``, ... —
    whatever the runtime reports).  Devices without the API (CPU) or a
    runtime that errors produce an empty ``stats`` dict; an unreachable
    backend produces an empty list.  Consumed by the metrics collector at
    block boundaries (`stark_tpu.metrics`) — sampling device memory must
    never be the thing that faults a run, so everything here degrades
    silently.
    """
    out = []
    try:
        import jax

        for i, dev in enumerate(jax.local_devices()):
            stats = {}
            try:
                raw = dev.memory_stats()
                if raw:
                    stats = {
                        k: int(v) for k, v in raw.items()
                        if isinstance(v, (int, float))
                    }
            except Exception:  # noqa: BLE001 — no stats on this device
                pass
            out.append({
                "device": str(i),
                "kind": getattr(dev, "device_kind", "unknown"),
                "stats": stats,
            })
    except Exception:  # noqa: BLE001 — backend unreachable: nothing to report
        return []
    return out


#: process-cached fingerprint (`hardware_fingerprint`): the probe touches
#: every X-stream dtype once, and the whole point of the key is that it
#: never changes within a process
_FINGERPRINT: Optional[str] = None


def _dtype_support() -> "list[str]":
    """The X-stream dtype names (ops.precision.X_DTYPE_NAMES) this
    backend can materialize AND round-trip through f32 — the capability
    half of the hardware fingerprint (two platforms with the same device
    kind but different fp8 support must not share autotuned profiles).
    Best-effort per dtype: an unsupported dtype is simply absent."""
    import jax.numpy as jnp

    from .ops.precision import X_DTYPE_NAMES, _X_DTYPES

    ok = []
    for name in X_DTYPE_NAMES:
        try:
            x = jnp.asarray([1.0, -0.5], dtype=_X_DTYPES[name])
            jnp.asarray(x, jnp.float32).block_until_ready()
            ok.append(name)
        except Exception:  # noqa: BLE001 — unsupported dtype on this backend
            continue
    return ok


def hardware_fingerprint() -> str:
    """Stable hardware identity key: ``<platform>-<device_kind>-<count>d-
    <dtype-support-hash>`` — the comparability key the autotuner
    (tools/autotune.py) files profiles under and `stark_tpu.ledger`
    stamps into rows, so mined history and emitted profiles only ever
    match runs on equivalent hardware.  Deterministic across processes
    on the same machine/config (tests/test_autotune.py pins it): every
    component is a static backend property, and the dtype-support hash
    is a sha1 over the sorted supported X-stream dtype names.  Cached
    per process.  A backend that cannot be reached raises: a profile or
    ledger row keyed ``unknown`` would hide that the device is not
    there."""
    global _FINGERPRINT
    if _FINGERPRINT is not None:
        return _FINGERPRINT
    import hashlib
    import re

    from . import telemetry

    info = telemetry.device_info()
    kind = re.sub(r"[^A-Za-z0-9_.]+", "_", info["device_kind"])
    support = _dtype_support()
    h = hashlib.sha1(",".join(sorted(support)).encode()).hexdigest()[:8]
    _FINGERPRINT = f"{info['platform']}-{kind}-{info['device_count']}d-{h}"
    return _FINGERPRINT
