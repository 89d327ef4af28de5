#!/usr/bin/env python3
"""The quickest proof that stark-tpu still starts on the chip.

    python chip_smoke.py            # on the TPU: full width, exits 0 or fails
    python chip_smoke.py --dry-run  # on the CPU: toy sizes, Pallas interpreted

One process, no children that touch jax.  With no arguments it demands the
TPU (``jax.devices()[0].platform == "tpu"``) and drives the main path once,
through the entry points a user calls, at the full width of the flagship
(hierarchical logistic, N=1M rows, d=32, 1000 groups, 64 chains).  Depth is
cut (about 100 warmup transitions and 150 draws) and the data is random,
made from a seed.  Legs, in order; any failure raises and ends the run with
a non-zero exit code, nothing is caught:

  parity        the fused grouped kernel's value and gradient against the
                plain jax.numpy log-likelihood, on the same device
  flagship      `stark_tpu.supervised_sample`, ChEES, max_restarts=0
  eight_schools the CLI entry (`python -m stark_tpu run ...`) in-process:
                per-chain NUTS to a posterior that can be asserted
  four chips    rows sharded over a data=4 mesh, and a fleet over a
                problems=4 mesh — only when four or more devices are visible

Nothing this script prints is a speed.  The last line of standard output is
one JSON object naming the device as jax reports it.
"""

import argparse
import contextlib
import importlib.metadata
import io
import json
import os
import shutil
import sys
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))
#: everything the run writes, wiped at start (chiprun brings chiprun_out/ back)
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

#: the flagship of bench.py, depth cut to "a trainer that takes a few steps"
FULL = dict(n=1_000_000, d=32, groups=1000, chains=64, sharded_chains=32,
            map_steps=500, warmup=100, block=50, blocks=3,
            fleet_problems=32, fleet_warmup=150, fleet_blocks=12)
#: --dry-run: the same code at sizes the Pallas interpreter finishes in seconds
TOY = dict(n=4096, d=8, groups=16, chains=8, sharded_chains=8,
           map_steps=20, warmup=40, block=20, blocks=2,
           fleet_problems=8, fleet_warmup=100, fleet_blocks=8)

#: Fused kernel against the jax.numpy reference, as max|a-b| / max|b| per
#: output.  Both sum 10^6 float32 terms in different orders (8192-lane tile
#: partials, then 123 of them, against XLA's reduction tree); float32 adds
#: round at 2^-24 = 6e-8, so sums of 10^6 terms of like sign may differ by up
#: to about sqrt(10^6) * 6e-8 = 6e-5 of their size when roundings are random,
#: and the MXU's six-pass "highest" f32 matmul adds a few 1e-6 on top.
PARITY_TOL = 2e-4


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def require_device(dry_run):
    """Say what jax runs on, and fail unless it is what was asked for."""
    import jax
    import jaxlib

    if dry_run:
        # the one way onto the CPU; before the backend initializes
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}"
        + (" DRY RUN (toy sizes, Pallas interpreted)" if dry_run else ""))
    if not dry_run and device["platform"] != "tpu":
        sys.exit(
            f"chip_smoke: this run needs the TPU and jax found "
            f"platform={device['platform']!r} ({device['kind']}); "
            f"--dry-run is the CPU path"
        )

    from stark_tpu import profile
    from stark_tpu.platform import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK):
        raise RuntimeError(f"compile cache {cache_dir} is not writable")
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries at start)")
    prof = profile.resolve_profile()
    say(f"active profile: {prof['id'] if prof else None} "
        f"(expected on the chip: None)")
    return device, cache_dir


def leg_parity(cfg, data, dry_run):
    """Value and gradient of ops.hier_fused.hier_logistic_loglik under vmap
    over the chains, against HierLogistic.log_lik differentiated by
    jax.grad at highest matmul precision, same device, same inputs."""
    import jax
    import jax.numpy as jnp

    from stark_tpu import prepare_model_data
    from stark_tpu.models import FusedHierLogisticGrouped, HierLogistic
    from stark_tpu.ops.hier_fused import hier_logistic_loglik

    d, groups, chains = cfg["d"], cfg["groups"], cfg["chains"]
    prepared = prepare_model_data(FusedHierLogisticGrouped(d, groups), data)
    # prepare_grouped returns None for a grouping it cannot tile and the
    # model then takes the offset layout without a word: not here
    assert "gl" in prepared, sorted(prepared)
    say(f"parity: grouped layout lane_tile={128 * prepared['lt128'].shape[0]} "
        f"k_loc={prepared['k_loc'].shape[0]} "
        f"grid={prepared['first_gid'].shape[0]}")
    kb, ka = jax.random.split(jax.random.PRNGKey(7))
    beta = 0.3 * jax.random.normal(kb, (chains, d), jnp.float32)
    alpha = 0.5 * jax.random.normal(ka, (chains, groups), jnp.float32)

    def fused_ll(b, a):
        return hier_logistic_loglik(
            b, a, prepared["xT"], prepared["y"], prepared["gl"],
            prepared["first_gid"], prepared["k_loc"], prepared["lt128"],
        )

    ref_model = HierLogistic(d, groups)

    def ref_ll(b, a):
        p = {"beta": b, "alpha0": 0.0, "sigma_alpha": 1.0, "alpha_raw": a}
        return ref_model.log_lik(p, data)

    def batched(fn):
        return jax.jit(jax.vmap(jax.value_and_grad(fn, argnums=(0, 1))))

    fused = batched(fused_ll).lower(beta, alpha).compile()
    if not dry_run:
        # compiled through Mosaic, not interpreted
        assert "tpu_custom_call" in fused.as_text()
    v, (gb, ga) = fused(beta, alpha)
    with jax.default_matmul_precision("highest"):
        v0, (gb0, ga0) = batched(ref_ll)(beta, alpha)
    errs = {"value": rel_err(v, v0), "grad_beta": rel_err(gb, gb0),
            "grad_alpha": rel_err(ga, ga0)}
    say("parity: max|fused-ref|/max|ref| " + " ".join(
        f"{k}={e:.2e}" for k, e in errs.items()) + f" (tol {PARITY_TOL:g})")
    assert all(e <= PARITY_TOL for e in errs.values()), errs


def leg_flagship(cfg, data, dry_run, model, chains, tag, **backend):
    """The flagship budget through stark_tpu.supervised_sample with no
    restart allowed; ``backend=`` (default: JaxBackend) lets the four-chip
    leg run the same budget on a mesh."""
    import numpy as np

    import stark_tpu
    from stark_tpu import telemetry
    from stark_tpu.checkpoint import load_checkpoint
    from stark_tpu.drawstore import read_draws

    workdir = os.path.join(OUT, tag)
    trace_path = os.path.join(workdir, "trace.jsonl")
    os.makedirs(workdir)
    with telemetry.RunTrace(trace_path) as trace:
        post = stark_tpu.supervised_sample(
            model, data, workdir=workdir, chains=chains, kernel="chees",
            init_step_size=0.1, map_init_steps=cfg["map_steps"],
            num_warmup=cfg["warmup"], block_size=cfg["block"],
            max_blocks=cfg["blocks"], min_blocks=cfg["blocks"],
            rhat_target=0.0,  # the whole draw budget, no early stop
            max_restarts=0, trace=trace, seed=1, **backend,
        )
    ndim = cfg["d"] + cfg["groups"] + 2
    draws = cfg["block"] * cfg["blocks"]
    zs = post.draws_flat
    assert zs.shape == (chains, draws, ndim), zs.shape
    assert np.isfinite(zs).all()
    assert all(np.isfinite(v).all() for v in post.draws.values())

    events = telemetry.read_trace(trace_path)
    summary = telemetry.summarize_trace(events)
    assert summary["restarts"] == 0, summary["restarts"]
    (start,) = [e for e in events if e["event"] == "run_start"]
    want = "cpu" if dry_run else "tpu"
    assert start["platform"] == want, start["platform"]

    arrays, meta = load_checkpoint(os.path.join(workdir, "chain.ckpt.npz"))
    assert np.isfinite(arrays["z"]).all() and arrays["z"].shape == (chains, ndim)
    store_path = os.path.join(workdir, "draws.stkr")
    stored, s_chains, s_dim = read_draws(store_path, mmap=False)
    assert (s_chains, s_dim) == (chains, ndim)
    np.testing.assert_array_equal(np.swapaxes(stored, 0, 1), zs)
    os.remove(store_path)  # tens of MB; trace, metrics and checkpoint stay

    blocks = [r for r in post.history if r.get("event") == "block"]
    widths = np.diff([0] + [r["draws_per_chain"] for r in blocks])
    say(f"{tag}: blocks of {', '.join(map(str, widths))} draws")
    accept = float(np.mean([r["mean_accept"] for r in blocks]))
    # ChEES tunes the step size to a 0.651 acceptance; far outside the band
    # means the trajectory or the gradient is wrong, not that warmup is short
    assert 0.4 <= accept <= 0.99, accept
    say(f"{tag}: draws {zs.shape} finite, restarts 0, run_start platform="
        f"{start['platform']}, checkpoint blocks_done={meta['blocks_done']}, "
        f"draw store {stored.shape} read back equal, mean accept {accept:.3f}; "
        f"max R-hat {post.max_rhat():.3f} min-ESS {post.min_ess():.0f} "
        f"(information only: not a convergence claim at this budget)")


def leg_eight_schools():
    """`python -m stark_tpu run configs/eight_schools.yaml` without a child:
    per-chain NUTS, while_loops and all, to an asserted posterior."""
    import numpy as np

    import stark_tpu.__main__ as cli
    from stark_tpu import config

    posts = []
    run_config = config.run_config

    def keep_posterior(cfg):  # the CLI prints a summary and drops the draws
        post, summary = run_config(cfg)
        posts.append(post)
        return post, summary

    out = io.StringIO()
    with mock.patch.object(config, "run_config", keep_posterior), \
            contextlib.redirect_stdout(out):
        rc = cli.main(["run", os.path.join(REPO, "configs", "eight_schools.yaml")])
    assert rc == 0, rc
    printed = json.loads(out.getvalue().strip().splitlines()[-1])
    (post,) = posts
    rhat = post.max_rhat()
    assert printed["max_rhat"] == round(rhat, 5), printed
    assert rhat < 1.01, rhat
    # published Stan values for the non-centered eight schools, to one
    # decimal (the verify skill quotes them): mu 4.4, tau 3.6.  Allow four
    # Monte-Carlo standard errors plus the 0.05 the quoted decimal hides.
    stats = post.summary()
    for name, ref in (("mu", 4.4), ("tau", 3.6)):
        mean, sd = float(stats[name]["mean"]), float(stats[name]["sd"])
        ess = float(np.min(post.ess()[name]))
        tol = 4.0 * sd / np.sqrt(ess) + 0.05
        assert abs(mean - ref) <= tol, (name, mean, ref, tol)
        say(f"eight_schools: {name} mean {mean:.2f} (ref {ref}, tol {tol:.2f})")
    say(f"eight_schools: CLI rc 0, max R-hat {rhat:.4f} < 1.01, "
        f"divergent {printed['num_divergent']}")


def leg_sharded_flagship(cfg, data, dry_run):
    """Rows over a data=4 mesh: they arrive already sharded by row and are
    prepared where they lie (nothing moves), xT really lives in four shards
    on four devices, the mesh run's programs carry the `stark_chees_*`
    names, the psum'd potential matches the single-chip one, and the
    flagship budget runs through the same supervised entry point."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from stark_tpu import flatten_model, prepare_model_data, telemetry
    from stark_tpu.backends import ShardedBackend
    from stark_tpu.models import FusedHierLogistic
    from stark_tpu.parallel.mesh import make_mesh, row_partition_specs
    from stark_tpu.parallel.primitives import map_shards, shard_put
    from stark_tpu.sampler import SamplerConfig

    mesh = make_mesh({"data": 4, "chains": 1}, devices=jax.devices()[:4])
    backend = ShardedBackend(mesh)
    # the grouped model refuses row sharding by design; the mesh runs the
    # offset-layout model
    model = FusedHierLogistic(cfg["d"], cfg["groups"])
    # the rows as a job too large for one chip has them: global arrays
    # already sharded by row, which the backend must leave where they are
    rows = shard_put(data, mesh, row_partition_specs(data, "data"))
    logged = len(telemetry.span_log())
    ap = backend.adaptive_parts(model, SamplerConfig(kernel="chees"), rows)
    (placing,) = [r for r in telemetry.span_log()[logged:]
                  if r.name == "shard_data"]
    assert placing.fields["moved_bytes"] == 0, placing.fields
    assert ap.samp_j.__name__ == "stark_chees_sample", ap.samp_j.__name__
    xT = ap.data["xT"]
    shard_shapes = sorted({s.data.shape for s in xT.addressable_shards})
    assert len(xT.sharding.device_set) == 4, xT.sharding
    assert shard_shapes == [(cfg["d"], cfg["n"] // 4)], shard_shapes
    assert len({s.device for s in xT.addressable_shards}) == 4

    z = 0.1 * jax.random.normal(jax.random.PRNGKey(11), (ap.fm.ndim,), jnp.float32)
    specs = row_partition_specs(
        ap.data, "data", model.data_shard_row_axes(ap.data)
    )
    pe4, g4 = map_shards(
        ap.fm.potential_and_grad, mesh=mesh, in_specs=(P(), specs),
        out_specs=(P(), P()),
    )(z, ap.data)
    pe1, g1 = jax.jit(flatten_model(model).potential_and_grad)(
        z, prepare_model_data(model, data)
    )
    errs = {"potential": rel_err(pe4, pe1), "grad": rel_err(g4, g1)}
    say(f"sharded: mesh {dict(mesh.shape)}, xT {xT.shape} in 4 shards of "
        f"{shard_shapes[0]} on {sorted(d.id for d in xT.sharding.device_set)}; "
        f"4-chip vs 1-chip " + " ".join(f"{k}={e:.2e}" for k, e in errs.items())
        + f" (tol {PARITY_TOL:g})")
    assert all(e <= PARITY_TOL for e in errs.values()), errs
    leg_flagship(cfg, data, dry_run, model, cfg["sharded_chains"],
                 "sharded_flagship", backend=backend)


def leg_fleet_mesh(cfg):
    """A fleet of eight-schools posteriors over a problems=4 mesh.

    Sharding must add nothing of its own: over one block, where nothing
    converges and every device program keeps its width, the first shard's
    problems are bit-equal to a single-device fleet of the shard's width.
    Run to convergence, the fleet is only required to agree with the
    single-device fleet as posteriors: the active set shrinks as problems
    converge, XLA:TPU compiles another program for another batch width,
    its roundings differ in the last bit, and NUTS amplifies that (PR 21
    chip runs; on the CPU all widths agree bit for bit)."""
    import jax
    import numpy as np

    from stark_tpu import sample_fleet
    from stark_tpu.benchmarks import fleet_eight_schools_spec
    from stark_tpu.parallel.mesh import make_mesh

    kw = dict(chains=2, block_size=50, max_blocks=cfg["fleet_blocks"],
              min_blocks=2, num_warmup=cfg["fleet_warmup"], ess_target=60.0,
              rhat_target=1.05, kernel="nuts", max_tree_depth=5, seed=6)
    b = cfg["fleet_problems"]
    mesh = make_mesh({"problems": 4}, devices=jax.devices()[:4])

    def fleet(problems, **over):
        return sample_fleet(
            fleet_eight_schools_spec(problems, seed=6), **{**kw, **over}
        )

    one_block = dict(max_blocks=1, min_blocks=1, rhat_target=0.0)
    pairs = zip(fleet(b, mesh=mesh, **one_block).problems,
                fleet(b // 4, **one_block).problems)
    for p4, p1 in pairs:
        np.testing.assert_array_equal(
            p4.draws_flat, p1.draws_flat,
            err_msg=f"mesh fleet differs from its own width on {p1.problem_id}",
        )

    four, full = fleet(b, mesh=mesh), fleet(b)
    assert four.shards == 4, four.shards
    assert four.converged_fraction >= 0.95, four.converged_fraction
    assert full.converged_fraction >= 0.95, full.converged_fraction
    worst, same = 0.0, True
    for p4, p1 in zip(four.problems, full.problems):
        assert np.isfinite(p4.draws_flat).all()
        same = same and np.array_equal(p4.draws_flat, p1.draws_flat)
        m4, m1 = p4.draws["mu"], p1.draws["mu"]
        # Monte-Carlo standard error from the ESS target both runs met
        se = np.sqrt((m4.var() + m1.var()) / kw["ess_target"])
        worst = max(worst, abs(m4.mean() - m1.mean()) / se)
    assert worst < 5.0, worst
    say(f"fleet: B={b} over mesh {dict(mesh.shape)}, {four.shards} shards; "
        f"one block: first shard bit-equal to the width-{b // 4} "
        f"single-device fleet; to convergence: converged fraction "
        f"{four.converged_fraction:.2f}, mu means within {worst:.1f} MC "
        f"standard errors of the width-{b} single-device fleet "
        f"(draws bit-equal: {same})")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--dry-run", action="store_true",
        help="CPU, toy sizes, Pallas interpreted; output marked as a dry run",
    )
    args = parser.parse_args(argv)
    device, cache_dir = require_device(args.dry_run)
    cfg = TOY if args.dry_run else FULL
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)

    import jax

    from stark_tpu.models import FusedHierLogisticGrouped, synth_logistic_data

    data, _ = synth_logistic_data(
        jax.random.PRNGKey(0), cfg["n"], cfg["d"], num_groups=cfg["groups"]
    )
    leg_parity(cfg, data, args.dry_run)
    leg_flagship(cfg, data, args.dry_run,
                 FusedHierLogisticGrouped(cfg["d"], cfg["groups"]),
                 cfg["chains"], "flagship")
    leg_eight_schools()
    if device["count"] >= 4:
        leg_sharded_flagship(cfg, data, args.dry_run)
        leg_fleet_mesh(cfg)
    else:
        say(f"four-chip legs NOT RUN and not counted as passed: "
            f"{device['count']} device(s) visible, they need 4")
    say(f"compile cache: {len(os.listdir(cache_dir))} entries at end")
    if not args.dry_run:  # toy programs may all compile under jax's 1 s floor
        assert os.listdir(cache_dir), f"nothing was cached under {cache_dir}"
    print(json.dumps({
        "ok": True, **({"dry_run": True} if args.dry_run else {}),
        "device": device,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
