"""Adaptive runner tests: run-until-R-hat, metrics JSONL, checkpoint/resume."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu.checkpoint import load_checkpoint, save_checkpoint
from stark_tpu.model import Model, ParamSpec


class StdNormal2(Model):
    def param_spec(self):
        return {"x": ParamSpec((2,))}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["x"] ** 2)

    def log_lik(self, p, data):
        return jnp.zeros(())


def test_sample_until_converged(tmp_path):
    metrics = str(tmp_path / "metrics.jsonl")
    ckpt = str(tmp_path / "state.npz")
    post = stark_tpu.sample_until_converged(
        StdNormal2(),
        chains=4,
        block_size=100,
        max_blocks=20,
        rhat_target=1.02,
        ess_target=200.0,
        num_warmup=150,
        kernel="nuts",
        max_tree_depth=6,
        seed=0,
        metrics_path=metrics,
        checkpoint_path=ckpt,
    )
    assert post.converged, post.history
    assert post.max_rhat() < 1.02
    assert post.min_ess() > 200.0
    # metrics JSONL: warmup event + one line per block
    lines = [json.loads(l) for l in open(metrics)]
    assert lines[0]["event"] == "warmup_done"
    assert sum(1 for l in lines if l["event"] == "block") == len(post.history)
    # checkpoint written and loadable
    arrays, meta = load_checkpoint(ckpt)
    assert arrays["z"].shape == (4, 2)
    assert meta["blocks_done"] == len(post.history)


def test_resume_from_checkpoint(tmp_path):
    ckpt = str(tmp_path / "state.npz")
    post1 = stark_tpu.sample_until_converged(
        StdNormal2(), chains=2, block_size=50, max_blocks=2, min_blocks=2,
        rhat_target=0.5,  # unreachable -> runs exactly max_blocks
        num_warmup=100, kernel="hmc", num_leapfrog=8, seed=1,
        checkpoint_path=ckpt,
    )
    assert not post1.converged
    assert post1.num_samples == 100
    post2 = stark_tpu.sample_until_converged(
        StdNormal2(), block_size=50, max_blocks=4, min_blocks=2,
        rhat_target=0.5, num_warmup=100, kernel="hmc", num_leapfrog=8,
        resume_from=ckpt,
    )
    # resumed run continues from 2 blocks of saved draws to 4 blocks total
    assert post2.num_samples == 200
    assert post2.num_chains == 2


def test_checkpoint_atomic_roundtrip(tmp_path):
    path = str(tmp_path / "c.npz")
    arrays = {"a": np.arange(6).reshape(2, 3), "b": np.ones(4, np.float32)}
    save_checkpoint(path, arrays, {"k": 1})
    out, meta = load_checkpoint(path)
    np.testing.assert_array_equal(out["a"], arrays["a"])
    np.testing.assert_array_equal(out["b"], arrays["b"])
    assert meta == {"k": 1}


# ---------------------------------------------------------------------------
# spans where the work happens (telemetry.span at every layer boundary)
# ---------------------------------------------------------------------------

#: the runner's span names, the operator's contract (README, PERF.md §3),
#: each with its parent.  A run with data has a `prepare_data` span under
#: `compile` stage build besides (the backend's `prepare_model_data`).
FRESH_TREE = {
    "run": None,
    "compile": "run",          # stages build, chain_init, init+map, loop_init
    "map_init": "compile",
    "warmup": "run",
    "warmup_block": "warmup",
    "block.dispatch": "run",
    "block.wait": "run",
    "block.gate": "run",
    "block.record": "run",
    "block.checkpoint": "run",
    "collect": "run",
    "collect.layout": "collect",
    "collect.constrain": "collect",
}
RESUMED_TREE = {
    "run": None,
    "compile": "run",          # stage build alone
    "resume_load": "run",
    "block.dispatch": "run",
    "block.wait": "run",
    "block.gate": "run",
    "block.record": "run",
    "block.checkpoint": "run",
    "collect": "run",
    "collect.layout": "collect",
    "collect.drain": "collect",  # the block dispatched ahead of the stop
    "collect.constrain": "collect",
}


def _spans_of_last_run():
    from stark_tpu import telemetry

    log = telemetry.span_log()
    run = [r for r in log if r.name == "run"][-1].run
    return [r for r in log if r.run == run]


def _tree(spans):
    by_id = {r.id: r for r in spans}
    return {r.name: (by_id[r.parent].name if r.parent else None)
            for r in spans}


def _coverage(spans):
    (root,) = [r for r in spans if r.parent is None]
    kids = [r for r in spans if r.parent == root.id]
    assert all(a.end_ns <= b.start_ns for a, b in zip(
        sorted(kids, key=lambda r: r.start_ns),
        sorted(kids, key=lambda r: r.start_ns)[1:]))  # siblings tile
    return sum(r.end_ns - r.start_ns for r in kids) / (
        root.end_ns - root.start_ns)


def test_runner_span_sites_fresh_then_resumed(tmp_path, caplog):
    """A toy chees run, fresh then resumed: exactly the span names of the
    contract with their nesting, children cover the run, the block
    record's timings are the spans', the record's fields are unchanged,
    and the compiled programs carry their fixed names."""
    import logging

    ckpt = str(tmp_path / "a.npz")
    kw = dict(chains=8, kernel="chees", init_step_size=0.5, num_warmup=40,
              map_init_steps=10, block_size=20, min_blocks=1, seed=3,
              rhat_target=0.0, adaptive_blocks=False)
    recs = []
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        stark_tpu.sample_until_converged(
            StdNormal2(), max_blocks=2, checkpoint_path=ckpt,
            progress_cb=recs.append, **kw)
        fresh = _spans_of_last_run()
        stark_tpu.sample_until_converged(
            StdNormal2(), max_blocks=50, resume_from=ckpt,
            checkpoint_path=str(tmp_path / "b.npz"),
            progress_cb=recs.append, time_budget_s=1e-3, **kw)
        resumed = _spans_of_last_run()
    for name in ("stark_chees_init", "stark_chees_warm",
                 "stark_chees_sample", "stark_stream_ess",
                 "stark_constrain"):
        assert f"transforming {name} " in caplog.text, name

    assert _tree(fresh) == FRESH_TREE
    assert _tree(resumed) == RESUMED_TREE
    (root,) = [r for r in fresh if r.name == "run"]
    assert root.fields["resumed"] is False
    assert [r for r in resumed if r.name == "run"][0].fields["resumed"]
    assert [r.fields["stage"] for r in sorted(
        fresh, key=lambda r: r.start_ns) if r.name == "compile"] == [
        "build", "chain_init", "init+map", "loop_init"]
    (load,) = [r for r in resumed if r.name == "resume_load"]
    assert load.fields["bytes_read"] == os.path.getsize(ckpt)
    assert load.fields["draws_rebuilt"] == 2 * 20 * 8
    (warm,) = [r for r in fresh if r.name == "warmup"]
    (map_init,) = [r for r in fresh if r.name == "map_init"]
    warm_rec = next(r for r in recs if r["event"] == "warmup_done")
    assert warm.fields["grad_evals"] + map_init.fields["grad_evals"] == \
        warm_rec["warmup_grad_evals"]
    assert _coverage(fresh) >= 0.95 and _coverage(resumed) >= 0.95
    # every span is well formed and inside its parent
    for spans in (fresh, resumed):
        by_id = {r.id: r for r in spans}
        assert sorted(by_id) == list(range(1, len(spans) + 1))
        for r in spans:
            assert r.start_ns <= r.end_ns and "error" not in r.fields
            if r.parent:
                up = by_id[r.parent]
                assert up.start_ns <= r.start_ns and r.end_ns <= up.end_ns

    # the block record: same fields as ever, timings from the spans
    blocks = [r for r in recs if r["event"] == "block"]
    assert [b["block"] for b in blocks] == [1, 2, 3]
    assert list(blocks[0]) == [
        "event", "block", "draws_per_chain", "max_rhat", "min_ess",
        "num_stuck_components", "num_divergent", "mean_accept",
        "t_dispatch_s", "t_diag_s", "block_grad_evals", "grad_eval_basis",
        "wall_s", "diag_bytes_to_host", "ess_forecast"]
    for rec, spans in ((blocks[0], fresh), (blocks[1], fresh),
                       (blocks[2], resumed)):
        mine = {r.name: r for r in spans
                if r.fields.get("block") == rec["block"]
                and "event" not in r.fields}
        sec = {k: (r.end_ns - r.start_ns) / 1e9 for k, r in mine.items()}
        assert rec["t_diag_s"] == round(sec["block.gate"], 3)
        assert rec["t_dispatch_s"] == round(
            sec["block.dispatch"] + sec["block.wait"], 3)
        gate = mine["block.gate"].fields
        assert gate["block_grad_evals"] == rec["block_grad_evals"]
        assert gate["diag_bytes_to_host"] == rec["diag_bytes_to_host"]
        # dispatch, wait, gate, record, checkpoint: in that order
        order = sorted(mine.values(), key=lambda r: r.start_ns)
        assert [r.name for r in order][1:] == [
            "block.wait", "block.gate", "block.record", "block.checkpoint"
        ][: len(order) - 1]
    # the budget record closes the window inside a record span of its own
    (budget,) = [r for r in resumed
                 if r.fields.get("event") == "budget_exhausted"]
    assert budget.name == "block.record"
    (drain,) = [r for r in resumed if r.name == "collect.drain"]
    assert drain.start_ns >= budget.end_ns


# ---------------------------------------------------------------------------
# the streaming gate: the ESS is reduced on the device behind each block
# (`stark_stream_ess`), and the gate fetches the row
# ---------------------------------------------------------------------------

_GATE_KW = {
    "hmc": dict(chains=2, kernel="hmc", num_leapfrog=4, num_warmup=30),
    "chees": dict(chains=4, kernel="chees", init_step_size=0.5,
                  num_warmup=30, map_init_steps=5),
}


def _gate_run(kernel, **kw):
    kw = {**dict(block_size=20, max_blocks=3, min_blocks=3, rhat_target=0.0,
                 seed=5, adaptive_blocks=False), **_GATE_KW[kernel], **kw}
    return stark_tpu.sample_until_converged(StdNormal2(), **kw)


def _reference_min_ess(draws, lags):
    """The float64 host reference over the accumulators of ``draws``."""
    from stark_tpu import diagnostics
    from stark_tpu.kernels.base import StreamDiagState

    st = StreamDiagState(**diagnostics.stream_diag_from_draws(
        np.asarray(draws, np.float32), lags))
    return float(np.min(diagnostics.ess_from_suffstats(*st)))


@pytest.mark.parametrize("kernel", sorted(_GATE_KW))
def test_streaming_gate_fetches_the_ess_row(kernel):
    """Through either `BlockKernel`: a block's record says the gate
    fetched ``d`` floats and the chains' draw counts, not the
    ``chains x lags x d`` accumulator three times over, and its
    ``min_ess`` is the host reference's over the draws so far."""
    from stark_tpu.kernels.base import STREAM_DIAG_LAGS as lags

    post = _gate_run(kernel)
    chains, d = post.draws_flat.shape[0], post.draws_flat.shape[2]
    assert [r["diag_bytes_to_host"] for r in post.history] == [
        d * 4 + chains * 4] * 3
    assert d * 4 + chains * 4 < 3 * chains * lags * d * 4
    for r in post.history:
        np.testing.assert_allclose(
            r["min_ess"], _reference_min_ess(
                post.draws_flat[:, : r["draws_per_chain"]], lags),
            rtol=1e-3)  # float32 on the device: tests/test_stream_diag.py


@pytest.mark.parametrize("kernel", sorted(_GATE_KW))
def test_pipelined_and_serial_gates_read_the_same_ess(kernel):
    """The summary program is enqueued at dispatch, behind its block: with
    the next block already in flight (pipelined) or not (serial, where
    the accumulator is donated to the next block AFTER the summary read
    it), every block's ``min_ess`` is the same number."""
    piped = _gate_run(kernel, sync_blocks=False)
    serial = _gate_run(kernel, sync_blocks=True)
    np.testing.assert_array_equal(piped.draws_flat, serial.draws_flat)
    assert [r["min_ess"] for r in piped.history] == [
        r["min_ess"] for r in serial.history]
    assert all(r["min_ess"] is not None for r in piped.history)


@pytest.mark.parametrize("kernel", sorted(_GATE_KW))
def test_resumed_gate_covers_the_whole_history(tmp_path, kernel):
    """A resume rebuilds the device carry from the stored draws
    (`stream_diag_from_draws`), so its first gate's ESS row is over every
    draw, not over the resumed block's alone."""
    from stark_tpu.kernels.base import STREAM_DIAG_LAGS as lags

    ckpt = str(tmp_path / "c.npz")
    _gate_run(kernel, max_blocks=2, min_blocks=2, checkpoint_path=ckpt)
    post = _gate_run(kernel, resume_from=ckpt)
    first = post.history[2]
    assert first["draws_per_chain"] == 60
    whole = _reference_min_ess(post.draws_flat, lags)
    alone = _reference_min_ess(post.draws_flat[:, 40:], lags)
    np.testing.assert_allclose(first["min_ess"], whole, rtol=1e-3)
    assert abs(alone - whole) > 0.05 * whole  # the two are told apart


def test_likelihood_kernels_are_named_in_the_jaxpr():
    """Every Pallas call of the benchmark's cells carries its `name=` (on
    the chip: the instruction name of the kernel's event); pinned on the
    jaxpr, since the interpreter lowers to no custom call."""
    from stark_tpu.ops import hier_fused, logistic_fused

    n, d, g = 512, 8, 16
    xt = jnp.ones((d, n))
    y = jnp.ones((n,))
    one = jax.make_jaxpr(jax.value_and_grad(
        lambda b: logistic_fused.logistic_loglik(b, xt, y)))(jnp.ones(d))
    assert "stark_logistic_ll_1chain" in str(one)
    batched = jax.make_jaxpr(jax.vmap(jax.value_and_grad(
        lambda b: logistic_fused.logistic_loglik(b, xt, y))))(
            jnp.ones((4, d)))
    assert "stark_logistic_ll" in str(batched).replace(
        "stark_logistic_ll_1chain", "")
    data = hier_fused.prepare_grouped(
        {"x": np.ones((n, d), np.float32), "y": np.ones(n, np.float32),
         "g": (np.arange(n) % g).astype(np.int32)}, d)
    grouped = jax.make_jaxpr(jax.vmap(jax.value_and_grad(
        lambda b, a: hier_fused.hier_logistic_loglik(
            b, a, data["xT"], data["y"], data["gl"], data["first_gid"],
            data["k_loc"], data["lt128"]), argnums=(0, 1))))(
                jnp.ones((4, d)), jnp.ones((4, g)))
    assert "stark_hier_ll_grouped" in str(grouped)


def test_prepare_data_spans_and_the_grouped_round_trip():
    """`prepare_model_data` is one `prepare_data` span outside any run;
    the grouped layout's host round trip is its three children."""
    from stark_tpu import telemetry
    from stark_tpu.models import FusedHierLogisticGrouped

    n, d, g = 512, 8, 16
    raw = {"x": jnp.ones((n, d)), "y": jnp.ones((n,)),
           "g": jnp.asarray(np.arange(n) % g, jnp.int32)}
    data = stark_tpu.prepare_model_data(FusedHierLogisticGrouped(d, g), raw)
    to_host, sort, to_dev, prep = telemetry.span_log()[-4:]
    assert [r.name for r in (to_host, sort, to_dev, prep)] == [
        "prepare_data.to_host", "prepare_data.sort",
        "prepare_data.to_device", "prepare_data"]
    assert prep.run == 0 and prep.parent is None
    assert {r.parent for r in (to_host, sort, to_dev)} == {prep.id}
    assert to_host.fields["bytes"] == prep.fields["bytes_in"] == \
        n * d * 4 + n * 4 + n * 4
    assert prep.fields["bytes_out"] >= to_dev.fields["bytes"] >= n * d * 4
    assert "gl" in data and data["xT"].shape == (d, n)
