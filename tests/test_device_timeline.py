"""The program's own device timeline (PR 40): `telemetry.wait` on set-up's
synchronous waits, the completion stamp of every window block
(`runner._DoneWaiter`) and the `sample_block` overlap fields worked out from
the stamps, the budget record's `tail_s`, and the per-chain warm-up's tree
counters."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu import faults, telemetry
from stark_tpu.model import Model, ParamSpec, flatten_model
from stark_tpu.sampler import (SamplerConfig, drive_segmented_warmup,
                               make_warmup_parts, tree_counters)
from stark_tpu.telemetry import RunTrace


class StdNormal3(Model):
    def param_spec(self):
        return {"x": ParamSpec((3,))}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["x"] ** 2)

    def log_lik(self, p, data):
        return jnp.zeros(())


def _mark():
    """The newest closed span: the log is a bounded deque, so a position in
    it says nothing once a worker has closed more spans than it holds."""
    log = telemetry.span_log()
    return log[-1] if log else None


def _new_spans(since):
    """The spans closed after the record `_mark` gave."""
    log = telemetry.span_log()
    start = next((i + 1 for i in range(len(log) - 1, -1, -1)
                  if log[i] is since), 0)
    return log[start:]


def test_wait_counts_on_the_innermost_open_span_only():
    x = jnp.arange(4.0) * 2
    n0 = _mark()
    assert telemetry.wait(x) is x  # no span open: it only waits
    with telemetry.span("outer_t") as outer:
        with telemetry.span("inner_t") as inner:
            before = time.perf_counter_ns()
            assert telemetry.wait({"a": x}) == {"a": x}
            telemetry.wait(x + 1)
            waited = (time.perf_counter_ns() - before) / 1e9
        assert 0.0 <= inner.fields["device_wait_s"] <= waited
        assert "device_wait_s" not in outer.fields
        assert "device_done_ns" not in inner.fields
    assert [s.name for s in _new_spans(n0)] == ["inner_t", "outer_t"]


def _window(sync, budget=None, **kw):
    """One tiny run; -> (the run's spans, its sample_block events)."""
    n0 = _mark()
    tr = RunTrace(None)
    events = []
    tr_listener = events.append
    telemetry.add_event_listener(tr_listener)
    try:
        stark_tpu.sample_until_converged(
            StdNormal3(), chains=2, block_size=8, max_blocks=6, min_blocks=1,
            rhat_target=0.0, num_warmup=20, kernel="hmc", num_leapfrog=3,
            seed=1, sync_blocks=sync, adaptive_blocks=False, trace=tr,
            time_budget_s=budget, **kw)
    finally:
        telemetry.remove_event_listener(tr_listener)
        tr.close()
    spans = _new_spans(n0)
    run = max(s.run for s in spans if s.name == "run")
    return ([s for s in spans if s.run == run],
            [e for e in events if e.get("event") == "sample_block"])


@pytest.mark.parametrize("sync", [False, True], ids=["pipelined", "sync"])
def test_every_window_block_carries_its_device_completion(sync):
    spans, blocks = _window(sync)
    waits = [s for s in spans if s.name == "block.wait"]
    dispatch, gate = ({s.fields["block"]: s for s in spans if s.name == n}
                      for n in ("block.dispatch", "block.gate"))
    assert len(waits) == 6 and len(blocks) == 6
    done = [s.fields["device_done_ns"] for s in waits]
    assert done == sorted(done)
    for s in waits:
        # after its dispatch ended, never later than the host held the
        # block's outputs and ESS row (the gate fetches the row)
        blk = s.fields["block"]
        assert dispatch[blk].end_ns <= s.fields["device_done_ns"] <= (
            gate[blk].end_ns)
        assert s.fields["device_wait_s"] >= 0.0
    for e in blocks:
        assert e["device_idle_s"] >= 0.0 and e["t_host_hidden_s"] >= 0.0
        if sync:
            assert e["t_host_hidden_s"] == 0.0
    # set-up's synchronous waits are counted on their spans
    warm = [s for s in spans if s.name == "warmup_block"]
    assert warm and all("device_wait_s" in s.fields for s in warm)


@pytest.mark.parametrize("sync", [False, True], ids=["pipelined", "sync"])
def test_budget_record_carries_the_window_tail(sync):
    spans, _ = _window(sync, budget=0.0)
    rec = [s for s in spans if s.fields.get("event") == "budget_exhausted"]
    assert len(rec) == 1
    last = [s for s in spans if s.name == "block.wait"][-1]
    assert rec[0].fields["tail_s"] >= 0.0
    assert rec[0].fields["tail_s"] == pytest.approx(
        (rec[0].start_ns - last.fields["device_done_ns"]) / 1e9)


def test_a_host_stall_shows_as_device_idle():
    """A sleep before the host takes block 2 (the device finishes blocks 2
    and 3 meanwhile in the pipeline): block 4, dispatched after it, finds
    the device idle for the sleep less what those blocks ran."""
    sleep = 0.4
    _window(False)  # programs compiled: the blocks are short
    faults.configure(f"runner.block.pre=sleep({sleep})*1@1")
    try:
        spans, blocks = _window(False)
    finally:
        faults.reset()
    done = [s.fields["device_done_ns"] for s in spans
            if s.name == "block.wait"]
    ends = [s.end_ns for s in spans if s.name == "block.dispatch"]
    # the longest a block ran on the device
    longest = max(d - max(e, p) for d, e, p in zip(
        done, ends, [0] + done)) / 1e9
    idle = max(e["device_idle_s"] for e in blocks)
    assert idle >= sleep - 2 * longest - 0.05, (idle, longest)
    assert idle < sleep + 1.0


def _waiters():
    return [t for t in threading.enumerate()
            if t.name == "stark-block-done" and t.is_alive()]


@pytest.mark.parametrize("fault", [None, "runner.block.pre=crash*1@2"],
                         ids=["ends", "crashes"])
def test_the_waiter_ends_with_its_call(fault):
    """The completion waiter is the call's: it is gone when the call
    returns, and when a crash leaves it with a block in flight."""
    before = len(_waiters())
    faults.configure(fault)
    try:
        if fault is None:
            _window(False)
        else:
            with pytest.raises(faults.InjectedFault):
                _window(False)
    finally:
        faults.reset()
    assert len(_waiters()) == before


def test_warmup_tree_counters_match_a_direct_count():
    """Each per-chain warm-up segment's span says what its transitions'
    gradient counts say, counted here by hand."""
    cfg = SamplerConfig(kernel="nuts", num_warmup=12, max_tree_depth=4)
    fm = flatten_model(StdNormal3())
    init_carry, segment, finalize = make_warmup_parts(fm, cfg)
    v_init = jax.jit(jax.vmap(init_carry, in_axes=(0, 0, None)))
    v_seg = jax.jit(jax.vmap(segment, in_axes=(1, None, None, 0, 0, 0, 0,
                                                 None)))
    seen = []

    def counting(*a):
        out = v_seg(*a)
        seen.append(np.asarray(out[-1]))
        return out

    chains = 3
    keys = jax.random.split(jax.random.PRNGKey(3), chains)
    z0 = jnp.full((chains, 3), 0.5)
    n0 = _mark()
    with telemetry.span("warmup"):
        _, _, _, (_, ngrad) = drive_segmented_warmup(
            cfg, v_init, counting, finalize, keys, z0, None, 5)
    segs = [s for s in _new_spans(n0) if s.name == "warmup_block"]
    assert len(segs) == len(seen) == 3
    for s, leaves in zip(segs, seen):
        assert leaves.shape == (chains, s.fields["steps"])
        assert s.fields["tree_leaves"] == sum(
            int(v) for row in leaves for v in row)
        assert s.fields["lane_iterations"] == sum(
            max(int(leaves[c, t]) for c in range(chains))
            for t in range(leaves.shape[1]))
        assert "tree_depths" not in s.fields
        assert s.fields["tree_leaves"] == s.fields["grad_evals"]
    assert sum(s.fields["tree_leaves"] for s in segs) == int(
        np.sum(np.asarray(ngrad)))
    assert tree_counters(seen[0]) == {
        k: segs[0].fields[k] for k in ("tree_leaves", "lane_iterations")}
