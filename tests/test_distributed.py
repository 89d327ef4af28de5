"""Multi-process jax.distributed smoke test (SURVEY.md §5 "Distributed").

Spawns TWO separate processes, each with 4 virtual CPU devices, forming one
8-device global mesh with Gloo cross-process collectives.  Each process
holds only its own half of the dataset rows; the sharded backend glues them
into a global row-sharded array, the per-step likelihood psum crosses the
process boundary, and the resulting posterior must (a) agree across
processes after the draw allgather and (b) recover the generating
parameters.

This is the CPU stand-in for a real multi-host TPU slice: the program is
identical, only initialize() resolution and the transport differ.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import json, sys
import jax
jax.distributed.initialize("127.0.0.1:%(port)d", num_processes=2,
                           process_id=int(sys.argv[1]))
import numpy as np
import stark_tpu
import stark_tpu.distributed as dist
from stark_tpu.backends.sharded import ShardedBackend
from stark_tpu.models import Logistic, synth_logistic_data
from stark_tpu.parallel.mesh import make_mesh

assert jax.device_count() == 8 and jax.local_device_count() == 4
assert dist.is_initialized() and dist.process_count() == 2

# every process generates the SAME full dataset (same seed), then keeps
# only its own contiguous row block — standing in for per-host file reads
data, true = synth_logistic_data(jax.random.PRNGKey(0), 2048, 4)
lo, hi = dist.local_row_range(2048)
local = {k: np.asarray(v)[lo:hi] for k, v in data.items()}

mesh = make_mesh({"data": 4, "chains": 2})
kernel = sys.argv[2] if len(sys.argv) > 2 else "nuts"
if kernel == "chees":
    # the ensemble sampler: chains sharded over the cross-process
    # "chains" axis, per-block draw allgather riding gather_draws
    post = stark_tpu.sample(
        Logistic(num_features=4), local, backend=ShardedBackend(mesh),
        chains=8, kernel="chees", num_warmup=200, num_samples=150,
        init_step_size=0.1, seed=0,
    )
elif kernel == "nuts_dispatch":
    # dispatch-bounded per-chain kernels over the multi-process mesh
    # (VERDICT r3 missing #4): the segmented drivers keep chains-sharded
    # keys/state on device; each device program is <= 40 transitions
    post = stark_tpu.sample(
        Logistic(num_features=4), local,
        backend=ShardedBackend(mesh, dispatch_steps=40),
        chains=2, kernel="nuts", max_tree_depth=5, num_warmup=150,
        num_samples=150, seed=0,
    )
elif kernel == "consensus":
    # multi-host consensus (r5): each host samples ITS half of the
    # shards on its own devices with zero cross-host communication; one
    # final draw allgather + identical deterministic combine.  The nuts
    # path slices the GLOBAL key streams, so the combined posterior
    # matches the single-host run (checked by the outer test).
    from stark_tpu.parallel import consensus_sample

    post = consensus_sample(
        Logistic(num_features=4), local, num_shards=4, chains=2,
        kernel="nuts", max_tree_depth=5, num_warmup=150, num_samples=150,
        seed=0,
    )
elif kernel == "coxph":
    # sequence-parallel CoxPH across PROCESSES: rows globally sorted by
    # descending time (synth_survival_data's contract), partitioned
    # contiguously per host; the cross-shard prefix stitching must
    # reproduce the generating betas, and a feed that breaks the global
    # order must be REFUSED (validate_process_blocks), never silently
    # wrong
    from stark_tpu.models import CoxPH, synth_survival_data

    sdata, true = synth_survival_data(jax.random.PRNGKey(0), 2048, 3)
    lo, hi = dist.local_row_range(2048)
    local_s = {k: np.asarray(v)[lo:hi] for k, v in sdata.items()}
    post = stark_tpu.sample(
        CoxPH(num_features=3), local_s, backend=ShardedBackend(mesh),
        chains=2, kernel="nuts", max_tree_depth=6, num_warmup=150,
        num_samples=150, seed=0,
    )
    # swap the hosts' blocks: each block is still locally descending, so
    # only the cross-process check can catch the broken global order
    swapped = {
        k: np.asarray(v)[2048 - hi : 2048 - lo] for k, v in sdata.items()
    }
    try:
        stark_tpu.sample(
            CoxPH(num_features=3), swapped, backend=ShardedBackend(mesh),
            chains=2, kernel="nuts", max_tree_depth=4, num_warmup=8,
            num_samples=4, seed=1,
        )
        raise SystemExit("unsorted multi-process CoxPH was not refused")
    except ValueError as e:
        assert "descending" in str(e), e
elif kernel == "adaptive":
    # the full flagship composition on a multi-process mesh (VERDICT r4
    # missing #3): convergence-gated blocks + per-rank checkpoints +
    # restart supervision, then an explicit resume from the written
    # checkpoint — the path the NotImplementedError used to refuse
    import os
    from stark_tpu.supervise import supervised_sample
    from stark_tpu.runner import sample_until_converged

    wd = sys.argv[3]
    post = supervised_sample(
        Logistic(num_features=4), local, workdir=wd,
        backend=ShardedBackend(mesh), chains=8, kernel="chees",
        num_warmup=150, block_size=50, min_blocks=1, max_blocks=10,
        rhat_target=1.05, ess_target=100.0, init_step_size=0.1, seed=0,
    )
    assert post.converged, "adaptive multi-process run must converge"
    k = dist.process_index()
    assert os.path.exists(os.path.join(wd, f"chain.ckpt.p{k}.npz")), (
        "per-rank checkpoint missing")
    assert os.path.exists(os.path.join(wd, f"metrics.p{k}.jsonl"))
    # resume: re-place the checkpointed (host numpy) state on the mesh
    # and draw two more blocks — exercises put_chains/put_rep re-placement
    # (max_blocks counts blocks_done from the checkpoint, so extend by 2)
    from stark_tpu.checkpoint import load_checkpoint
    _, meta = load_checkpoint(os.path.join(wd, f"chain.ckpt.p{k}.npz"))
    post2 = sample_until_converged(
        Logistic(num_features=4), local, backend=ShardedBackend(mesh),
        chains=8, kernel="chees", block_size=50, min_blocks=1,
        max_blocks=int(meta["blocks_done"]) + 2,
        rhat_target=0.0, ess_target=1e9, num_warmup=150,
        resume_from=os.path.join(wd, "chain.ckpt.npz"),
        init_step_size=0.1, seed=0,
    )
    assert post2.draws_flat.shape[1] == 100, post2.draws_flat.shape
    # skew recovery: tamper rank 0's checkpoint so (phase, blocks_done)
    # disagrees across ranks — both ranks must agree to COLD-start in
    # lockstep (a skewed resume would hang the pod on an unmatched
    # allgather), quarantining their stale state
    from stark_tpu.checkpoint import save_checkpoint
    ck = os.path.join(wd, f"chain.ckpt.p{k}.npz")
    if k == 0:
        arrs, m2 = load_checkpoint(ck)
        m2["blocks_done"] = int(m2.get("blocks_done", 0)) + 1
        save_checkpoint(ck, arrs, m2)
    post3 = supervised_sample(
        Logistic(num_features=4), local, workdir=wd,
        backend=ShardedBackend(mesh), chains=8, kernel="chees",
        num_warmup=150, block_size=50, min_blocks=1, max_blocks=3,
        rhat_target=1.2, ess_target=20.0, init_step_size=0.1, seed=1,
    )
    assert os.path.exists(ck + ".bad"), "skewed checkpoint not quarantined"
    recs = [json.loads(l) for l in open(
        os.path.join(wd, f"metrics.p{k}.jsonl"))]
    warm = [r for r in recs if r["event"] == "warmup_done"]
    # the post-skew attempt ran a FRESH warmup (cold start), not a resume
    assert warm and "resumed_from_step" not in warm[-1]
    # cross-rank BUDGET agreement: with a zero budget both ranks must
    # agree to stop after exactly one block (the agreement allgather runs
    # in lockstep — per-rank wall clocks alone could disagree and hang)
    post4 = sample_until_converged(
        Logistic(num_features=4), local, backend=ShardedBackend(mesh),
        chains=8, kernel="chees", block_size=50, min_blocks=1,
        max_blocks=10, rhat_target=0.0, ess_target=1e9, num_warmup=100,
        time_budget_s=0.0, init_step_size=0.1, seed=2,
    )
    assert post4.budget_exhausted and post4.draws_flat.shape[1] == 50
else:
    assert kernel == "nuts", f"worker has no branch for kernel={kernel!r}"
    post = stark_tpu.sample(
        Logistic(num_features=4), local, backend=ShardedBackend(mesh),
        chains=2, kernel="nuts", max_tree_depth=5, num_warmup=150,
        num_samples=150, seed=0,
    )
beta = np.asarray(post.draws["beta"])
print("RESULT " + json.dumps({
    "proc": dist.process_index(),
    "beta_mean": beta.mean(axis=(0, 1)).tolist(),
    "true": np.asarray(true["beta"]).tolist(),
    "checksum": float(beta.sum()),
    "max_rhat": float(post.max_rhat()),
}), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(script, kernel, extra_args=(), dev_per_proc=4, timeout=600):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={dev_per_proc}",
        "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), kernel, *extra_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(out)

    results = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert lines, out
        results.append(json.loads(lines[-1][len("RESULT "):]))
    return results


@pytest.mark.parametrize(
    "kernel", ["nuts", "chees", "nuts_dispatch", "coxph"]
)
@pytest.mark.slow
def test_two_process_sharded_sampling(tmp_path, kernel):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER % {"port": _free_port()})
    results = _run_workers(script, kernel)

    # both processes must hold the SAME full posterior after the allgather
    assert results[0]["checksum"] == pytest.approx(results[1]["checksum"])
    np.testing.assert_allclose(
        results[0]["beta_mean"], results[1]["beta_mean"], rtol=1e-6
    )
    # and it must recover the generating coefficients
    np.testing.assert_allclose(
        results[0]["beta_mean"], results[0]["true"], atol=0.4
    )
    assert results[0]["max_rhat"] < 1.2


@pytest.mark.slow
def test_two_process_consensus_matches_single_host(tmp_path):
    """Multi-host consensus (r5): hosts sample disjoint shard blocks with
    zero cross-host comm and one final draw allgather; both hosts hold
    the identical combined posterior, and it matches the single-host run
    (the per-chain path slices the same global key streams)."""
    import jax

    from stark_tpu.models import Logistic, synth_logistic_data
    from stark_tpu.parallel import consensus_sample

    data, _ = synth_logistic_data(jax.random.PRNGKey(0), 2048, 4)
    expected = consensus_sample(
        Logistic(num_features=4), data, num_shards=4, chains=2,
        kernel="nuts", max_tree_depth=5, num_warmup=150, num_samples=150,
        seed=0,
    )
    exp_sum = float(np.asarray(expected.draws["beta"]).sum())

    script = tmp_path / "worker.py"
    script.write_text(_WORKER % {"port": _free_port()})
    results = _run_workers(script, "consensus")
    assert results[0]["checksum"] == pytest.approx(results[1]["checksum"])
    assert results[0]["checksum"] == pytest.approx(exp_sum, rel=1e-5)
    np.testing.assert_allclose(
        results[0]["beta_mean"], results[0]["true"], atol=0.4
    )


@pytest.mark.slow
def test_two_process_adaptive_supervised(tmp_path):
    """The flagship production composition on a multi-process mesh
    (VERDICT r4 missing #3): supervised convergence-gated blocks with
    per-rank checkpoints, then an explicit resume re-placement."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER % {"port": _free_port()})
    wd = tmp_path / "wd"
    results = _run_workers(script, "adaptive", extra_args=(str(wd),))
    assert results[0]["checksum"] == pytest.approx(results[1]["checksum"])
    np.testing.assert_allclose(
        results[0]["beta_mean"], results[0]["true"], atol=0.4
    )


_SMOKE_WORKER = r"""
import json, sys
import jax
jax.distributed.initialize("127.0.0.1:%(port)d", num_processes=2,
                           process_id=int(sys.argv[1]))
import numpy as np
import stark_tpu
import stark_tpu.distributed as dist
from stark_tpu.backends.sharded import ShardedBackend
from stark_tpu.models import Logistic, synth_logistic_data
from stark_tpu.parallel.mesh import make_mesh

from stark_tpu.telemetry import RunTrace, read_trace, use_trace

data, _ = synth_logistic_data(jax.random.PRNGKey(0), 256, 2)
lo, hi = dist.local_row_range(256)
local = {k: np.asarray(v)[lo:hi] for k, v in data.items()}
trace_path = sys.argv[3] + "/smoke_trace_%%d.jsonl" %% int(sys.argv[1])
with RunTrace(trace_path) as tr, use_trace(tr):
    post = stark_tpu.sample(
        Logistic(num_features=2), local,
        backend=ShardedBackend(make_mesh({"data": 2, "chains": 1})),
        chains=2, kernel="nuts", max_tree_depth=4, num_warmup=30,
        num_samples=30, seed=0,
    )
comm = [e for e in read_trace(trace_path) if e.get("event") == "comm"]
print("RESULT " + json.dumps({
    "proc": dist.process_index(),
    "checksum": float(np.asarray(post.draws["beta"]).sum()),
    "comm_events": len(comm),
    "comm_participants": sorted({e.get("participants") for e in comm}),
    "comm_primitives": sorted({e.get("primitive") for e in comm}),
}), flush=True)
"""


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_two_process_smoke(tmp_path):
    """DEFAULT-tier 2-process gloo smoke (VERDICT r4 weak #6): tiny
    shapes, one cross-process psum + draw allgather — keeps the
    distributed path from regressing silently between slow-tier runs.
    Since PR 16 each worker also traces its run: the comms observatory
    must account the cross-process draw gather with participants == 2
    (the REAL process count, not the single-process fallback)."""
    script = tmp_path / "worker.py"
    script.write_text(_SMOKE_WORKER % {"port": _free_port()})
    results = _run_workers(
        script, "smoke", extra_args=(str(tmp_path),), dev_per_proc=1,
        timeout=120,
    )
    assert results[0]["checksum"] == pytest.approx(results[1]["checksum"])
    for r in results:
        assert r["comm_events"] > 0, r
        assert "gather_tree" in r["comm_primitives"], r
        assert 2 in r["comm_participants"], (
            "cross-process gather_tree did not account 2 participants", r
        )
