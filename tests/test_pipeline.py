"""Async block pipeline: sync-vs-pipelined equivalence, overlap telemetry,
draw-major DrawStore appends, the DrawHistory buffer, and the workdir-keyed
compilation cache.

The pipeline's contract (runner.py): with the overlap ON (default) and OFF
(``STARK_SYNC_BLOCKS=1`` / ``sync_blocks=True``) the draws, the metrics
history, the checkpoint contents, and the draw-store bytes are
BIT-IDENTICAL — only wall-clock attribution differs.  These tests hold
that equivalence for both the per-chain (NUTS/HMC) and the ChEES ensemble
paths, and pin the new trace fields bench.py / trace_report consume.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu import diagnostics, faults
from stark_tpu.checkpoint import load_checkpoint
from stark_tpu.drawstore import DrawStore, read_draws
from stark_tpu.model import Model, ParamSpec
from stark_tpu.telemetry import RunTrace, read_trace, summarize_trace


class StdNormal2(Model):
    def param_spec(self):
        return {"x": ParamSpec((2,))}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["x"] ** 2)

    def log_lik(self, p, data):
        return jnp.zeros(())


#: semantic metrics fields (timing attribution legitimately differs
#: between the pipelined and serial loops)
_TIMING_KEYS = ("wall_s", "t_dispatch_s", "t_diag_s")


def _strip_timing(history):
    return [
        {k: v for k, v in rec.items() if k not in _TIMING_KEYS}
        for rec in history
    ]


def _run_both_modes(tmp_path, **kw):
    """One run per mode with full persistence; returns (pipelined, sync,
    paths dict)."""
    out = {}
    for mode in ("pipe", "sync"):
        d = tmp_path / mode
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
            sync_blocks=(mode == "sync"),
            **kw,
        )
        out[mode] = (post, paths)
    return out


def _assert_equivalent(out):
    post_p, paths_p = out["pipe"]
    post_s, paths_s = out["sync"]
    # draws bit-identical
    np.testing.assert_array_equal(post_p.draws_flat, post_s.draws_flat)
    # metrics history identical up to timing attribution
    assert _strip_timing(post_p.history) == _strip_timing(post_s.history)
    # checkpoint contents bit-identical (arrays AND accounting meta)
    ap, mp = load_checkpoint(paths_p["ckpt"])
    as_, ms = load_checkpoint(paths_s["ckpt"])
    assert set(ap) == set(as_)
    for k in ap:
        np.testing.assert_array_equal(ap[k], as_[k], err_msg=k)
    for k in ("blocks_done", "block_size", "draw_rows", "num_divergent",
              "kernel"):
        assert mp[k] == ms[k], k
    # draw-store files byte-identical (covers the draw-major chees append)
    with open(paths_p["store"], "rb") as f:
        b_p = f.read()
    with open(paths_s["store"], "rb") as f:
        b_s = f.read()
    assert b_p == b_s


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_pipeline_matches_sync_nuts(tmp_path):
    out = _run_both_modes(
        tmp_path, chains=2, block_size=25, max_blocks=3, min_blocks=3,
        rhat_target=0.0, num_warmup=50, kernel="nuts", max_tree_depth=4,
        seed=0,
    )
    _assert_equivalent(out)


def test_pipeline_matches_sync_chees(tmp_path):
    out = _run_both_modes(
        tmp_path, chains=4, block_size=20, max_blocks=3, min_blocks=3,
        rhat_target=0.0, num_warmup=40, kernel="chees", map_init_steps=5,
        seed=1,
    )
    _assert_equivalent(out)


def test_sync_env_escape_hatch(tmp_path, monkeypatch):
    """STARK_SYNC_BLOCKS=1 selects the serial loop without code changes;
    the trace records which mode ran."""
    monkeypatch.setenv("STARK_SYNC_BLOCKS", "1")
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), chains=2, block_size=20, max_blocks=2,
            min_blocks=2, rhat_target=0.0, num_warmup=30, kernel="hmc",
            num_leapfrog=4, seed=0, trace=tr,
        )
    blocks = [e for e in read_trace(str(p)) if e["event"] == "sample_block"]
    assert blocks and all(e["pipelined"] is False for e in blocks)


def test_trace_overlap_fields_wellformed(tmp_path):
    """Tier-1 regression for the overlap schema: a traced smoke run emits
    t_host_hidden_s / device_idle_s / t_wait_s on every sample_block, all
    finite and >= 0, and summarize_trace aggregates them into a
    well-formed device-idle fraction."""
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), chains=2, block_size=20, max_blocks=3,
            min_blocks=3, rhat_target=0.0, num_warmup=30, kernel="hmc",
            num_leapfrog=4, seed=0, trace=tr,
        )
    events = read_trace(str(p))
    blocks = [e for e in events if e["event"] == "sample_block"]
    assert len(blocks) == 3
    for e in blocks:
        assert e["pipelined"] is True
        for k in ("t_host_hidden_s", "device_idle_s", "t_wait_s"):
            v = e[k]
            assert np.isfinite(v) and v >= 0.0, (k, e)
    s = summarize_trace(events)
    ov = s["overlap"]
    for k in ("t_host_hidden_s", "device_idle_s", "t_wait_s",
              "device_idle_frac"):
        assert np.isfinite(ov[k]) and ov[k] >= 0.0, (k, ov)
    assert ov["device_idle_frac"] <= 1.0, ov


def test_sync_idle_fraction_bounded_with_checkpoints(tmp_path):
    """Serial mode attributes the WHOLE host cycle (diagnostics +
    checkpoint fsyncs) as device idle; the summarized fraction must still
    land in [0, 1] — the denominator covers the checkpoint phase too."""
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), chains=2, block_size=10, max_blocks=4,
            min_blocks=4, rhat_target=0.0, num_warmup=20, kernel="hmc",
            num_leapfrog=4, seed=0, trace=tr, sync_blocks=True,
            checkpoint_path=str(tmp_path / "c.npz"),
        )
    ov = summarize_trace(read_trace(str(p)))["overlap"]
    assert 0.0 <= ov["device_idle_frac"] <= 1.0, ov
    assert ov["device_idle_s"] >= 0.0


def test_trace_report_renders_overlap(tmp_path):
    """tools/trace_report.py surfaces the device-idle fraction column."""
    import importlib.util
    import io
    from contextlib import redirect_stdout

    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        stark_tpu.sample_until_converged(
            StdNormal2(), chains=2, block_size=20, max_blocks=2,
            min_blocks=2, rhat_target=0.0, num_warmup=30, kernel="hmc",
            num_leapfrog=4, seed=0, trace=tr,
        )
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
    assert "device idle fraction" in out
    assert "host work hidden" in out
    # --json carries the machine-readable overlap dict
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert trace_report.main([str(p), "--json"]) == 0
    summary = json.loads(buf.getvalue())
    assert "device_idle_frac" in summary["overlap"]


def test_drawstore_draw_major_append(tmp_path):
    """append(draw_major=True) writes the identical bytes the chain-major
    path does — the ensemble path's zero-transpose persistence."""
    rng = np.random.default_rng(0)
    blocks = [rng.standard_normal((3, 7, 2)).astype(np.float32)
              for _ in range(3)]
    p_cm = str(tmp_path / "cm.stkd")
    p_dm = str(tmp_path / "dm.stkd")
    with DrawStore(p_cm, chains=3, dim=2) as ds:
        for b in blocks:
            ds.append(b)
    with DrawStore(p_dm, chains=3, dim=2) as ds:
        for b in blocks:
            ds.append(np.ascontiguousarray(b.transpose(1, 0, 2)),
                      draw_major=True)
    with open(p_cm, "rb") as f:
        cm = f.read()
    with open(p_dm, "rb") as f:
        dm = f.read()
    assert cm == dm
    draws, _, _ = read_draws(p_dm)
    np.testing.assert_array_equal(
        draws, np.concatenate([b.transpose(1, 0, 2) for b in blocks])
    )
    # shape validation still fires in draw-major order
    with DrawStore(str(tmp_path / "v.stkd"), chains=3, dim=2) as ds:
        with pytest.raises(ValueError):
            ds.append(np.zeros((3, 7, 2), np.float32), draw_major=True)


def test_draw_history_matches_concatenate():
    """DrawHistory == np.concatenate semantics across growth boundaries,
    including the worst-k fancy-index subset."""
    rng = np.random.default_rng(1)
    hist = diagnostics.DrawHistory(2, 5)
    blocks = []
    for n in (3, 40, 7, 64, 1):
        b = rng.standard_normal((2, n, 5)).astype(np.float32)
        blocks.append(b)
        hist.append(b)
    ref = np.concatenate(blocks, axis=1)
    assert hist.rows == ref.shape[1] and len(hist) == ref.shape[1]
    np.testing.assert_array_equal(hist.view(), ref)
    cols = np.array([4, 0, 2])
    np.testing.assert_array_equal(hist.take(cols), ref[:, :, cols])
    with pytest.raises(ValueError):
        hist.append(np.zeros((2, 3, 4), np.float32))


def test_block_post_failpoint_fires_after_checkpoint(tmp_path):
    """runner.block.post crashes AFTER the block is durable: the
    checkpoint on disk accounts for the block that just completed."""
    ckpt = str(tmp_path / "c.npz")
    faults.reset()
    faults.configure("runner.block.post=crash*1")
    try:
        with pytest.raises(faults.InjectedFault):
            stark_tpu.sample_until_converged(
                StdNormal2(), chains=2, block_size=20, max_blocks=3,
                min_blocks=3, rhat_target=0.0, num_warmup=30, kernel="hmc",
                num_leapfrog=4, seed=0, checkpoint_path=ckpt,
            )
    finally:
        faults.reset()
    _, meta = load_checkpoint(ckpt)
    assert meta["blocks_done"] == 1


def test_compilation_cache_helper(monkeypatch):
    """enable_compilation_cache: JAX_COMPILATION_CACHE_DIR set -> nothing
    is touched; unset -> the fixed <checkout>/.jax_cache, never a path
    built from a workdir or a temporary name."""
    import jax

    from stark_tpu.platform import enable_compilation_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        # an env-configured cache is jax's own business: returned as the
        # directory in effect, and no other directory is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env/cache")
        assert enable_compilation_cache() == "/env/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(repo, ".jax_cache")
        assert enable_compilation_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert os.path.isdir(fixed)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
