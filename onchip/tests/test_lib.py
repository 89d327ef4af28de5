"""The yardstick's arithmetic: the ESS and R-hat copy against the program's
own, the counts on a worked example, and the trace reduction on a small
recorded trace with numbers worked out by hand."""

import json
import os
import sys

import numpy as np
import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP):
    if _p not in sys.path:
        sys.path.insert(0, _p)
import importlib.util

from lib import diag, peaks, tracered

_spec = importlib.util.spec_from_file_location(
    "onchip_counts_glm_rows", os.path.join(ONCHIP, "counts", "glm_rows.py"))
counts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(counts)


@pytest.fixture(scope="module")
def draws():
    rng = np.random.default_rng(7)
    x = np.zeros((4, 300, 5))
    eps = rng.standard_normal(x.shape)
    for t in range(1, 300):  # AR(1), one coordinate slow
        x[:, t] = np.array([0.1, 0.5, 0.9, 0.0, -0.4]) * x[:, t - 1] + eps[:, t]
    x[3] += 0.3  # one chain off
    return x


@pytest.mark.parametrize("fn", ["split_rhat", "ess", "ess_bulk", "rank_rhat"])
def test_diag_copy_agrees_with_the_program(draws, fn):
    from stark_tpu import diagnostics

    np.testing.assert_allclose(
        getattr(diag, fn)(draws), getattr(diagnostics, fn)(draws), rtol=1e-12)


def test_min_bulk_ess_is_over_every_scalar(draws):
    d = {"a": draws[..., :2], "b": draws[..., 2]}
    assert diag.min_bulk_ess(d) == pytest.approx(
        float(np.min(diag.ess_bulk(draws[..., :3]))))
    d["stuck"] = np.ones((4, 300))
    assert np.isnan(diag.min_bulk_ess(d))


def test_counts_worked_example():
    flat = {"n": 1_000_000, "d": 32, "groups": 0}
    grouped = dict(flat, groups=1000)
    assert counts.flops_per_chain_gradient(flat) == 128_000_000
    assert counts.bytes_per_ensemble_gradient(flat) == 132_000_000
    assert counts.bytes_per_ensemble_gradient(grouped) == 136_000_000
    v5e = peaks.peaks("TPU v5 lite")
    t, bound = counts.least_seconds(flat, 8, v5e)
    assert bound == "bytes" and t == pytest.approx(132e6 / 819e9)
    # 1500 chains would make the same pass compute-bound
    t, bound = counts.least_seconds(flat, 1500, v5e)
    assert bound == "flops" and t == pytest.approx(128e6 * 1500 / 197e12)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


def test_union_of_intervals():
    assert tracered.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert tracered.union_ns([]) == 0


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ONCHIP, "testdata", "trace_logistic_v5e.json")) as f:
        return json.load(f)


def test_reduction_of_the_recorded_trace(recorded):
    """Two ensemble gradients of the logistic cell on the v5e (PR 25), cut from
    the sampling program's trace; `by_hand` was worked out from the listed
    starts and durations."""
    events, want = recorded["events"], recorded["by_hand"]
    b = tracered.busy(events)
    assert b["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert b["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    calls, secs = tracered.kernel_time(events, want["kernel_pattern"])
    assert calls == want["kernel_calls"]
    assert secs == pytest.approx(want["kernel_s"], rel=1e-12)
    top = tracered.top_ops(events, 3)
    assert [n for n, _ in top] == want["top3"]
    # the loops that contain the others are not counted as work
    assert not any(n.startswith("while") for n, _ in tracered.top_ops(events, 50))
    marks = tracered.markers(events)
    assert [m[0] for m in marks] == ["onchip.block.0"]
    assert marks[0][1] == 42846346.0
    spans = [("host:gate", *want["gate_span_ns"])]
    gaps = tracered.idle_gaps(events, spans, k=3)
    assert [round(s * 1e9) for _, s in gaps] == want["longest_gaps_ns"]
    assert [w for w, _ in gaps] == want["gaps_are"]
