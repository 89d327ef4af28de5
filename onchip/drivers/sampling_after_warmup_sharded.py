"""Driver `sampling_after_warmup_sharded`: `sampling_after_warmup` over a device
mesh.  One posterior through the program's normal entry with
`backend=ShardedBackend(mesh)`, the mesh built from the configuration's
`mesh` ({"data": 4, "chains": 1}) over the first devices jax shows; the same
three calls, the same records, the same `measured` keys.

  set-up   rows from the seeds, born sharded over `data` (the
           configuration's generator makes shard i on device i); the
           model's own `prepare_data`, every shard where it lies; call A:
           `stark_tpu.sample_until_converged` from cold chains, MAP and the
           configured warm-up and one draw block, checkpointed; then a
           rehearsal of the resume path (A's checkpoint, one more block), so
           that every program of the window is compiled or found in the cache
  window   call B: the same call resumed from A's checkpoint, fixed blocks
           under `time_budget_s`; the window is B's call to its
           `budget_exhausted` record.  What the entry does after that record
           is `collect_s`

A copy of `sampling_after_warmup.py` but for the mesh and the backend: that
file is the accepted benchmark's and a PR that adds a cell edits nothing
there (PERF.md, Open questions: a `benchmark` PR folds the two).
"""

import os
import time


def run(env):
    """`env`: config, sizes, seed, seconds, out dir, clock, hooks, load.
    Returns the run's measurements as a dict."""
    import jax
    import numpy as np

    import stark_tpu
    from stark_tpu import models
    from stark_tpu.backends import ShardedBackend
    from stark_tpu.parallel.mesh import make_mesh

    from lib.seeds import seed_words

    cfg, sizes, out = env["config"], env["sizes"], env["out"]
    sampler = dict(cfg["sampler"], **sizes.get("sampler", {}))
    block = int(sampler.pop("block_size"))
    chains = int(sampler.pop("chains"))
    clock = env["clock"]

    spec = sizes.get("model", cfg["model"])
    model = getattr(models, spec["class"])(*spec["args"])
    # the mesh before any rows: a machine with fewer devices ends here
    want = dict(cfg["mesh"])
    need = int(np.prod(list(want.values())))
    if len(jax.devices()) < need:
        raise SystemExit(f"onchip: the mesh {want} needs {need} devices and "
                         f"jax shows {len(jax.devices())}")
    backend = ShardedBackend(make_mesh(want, devices=jax.devices()[:need]))
    ck_a = os.path.join(out, "ck_a.npz")
    ck_b = os.path.join(out, "ck_b.npz")
    # the chains' seed is the run's: another word of it than the rows' order
    chain_seed = seed_words(env["seed"])[1]

    def entry(rows, **kw):
        return stark_tpu.sample_until_converged(
            model, rows, backend=backend, chains=chains, kernel="chees",
            rhat_target=0.0, adaptive_blocks=False, block_size=block,
            min_blocks=1, seed=chain_seed, **dict(sampler, **kw))

    # rows, born on their chips, and the model's own layout of them, made
    # where they lie; the raw copy is dropped
    t = time.perf_counter()
    raw = env["load"]("rows", cfg["rows"]["generator"]).make(
        cfg["rows"]["params"], sizes, env["seed"])
    jax.block_until_ready(raw)
    clock["rows_s"] = time.perf_counter() - t
    t = time.perf_counter()
    data = stark_tpu.prepare_model_data(model, raw)
    jax.block_until_ready(data)
    del raw
    clock["prepare_s"] = time.perf_counter() - t

    # call A: cold chains -> MAP -> warm-up -> one block
    t_a = time.perf_counter()
    first = {}

    def cb_a(rec):
        if rec.get("event") == "warmup_done" and "t" not in first:
            first["t"] = time.perf_counter() - t_a
            first["rec"] = rec

    entry(data, max_blocks=1, checkpoint_path=ck_a, progress_cb=cb_a)
    clock["call_a_s"] = time.perf_counter() - t_a
    # rehearsal of the resume path: the window's own programs, before it
    t = time.perf_counter()
    entry(data, max_blocks=2, resume_from=ck_a)
    clock["rehearsal_s"] = time.perf_counter() - t

    # the window: call B
    hooks = env["hooks"]
    records, closed = [], {}

    def cb_b(rec):
        now = time.perf_counter()
        records.append(rec)
        hooks["on_record"](rec)
        if rec.get("event") == "budget_exhausted" and not closed:
            closed["t"] = now
            hooks["window_closes"]()

    hooks["window_opens"]()
    t_b = time.perf_counter()
    result = entry(data, max_blocks=1_000_000, resume_from=ck_a,
                   checkpoint_path=ck_b, progress_cb=cb_b,
                   time_budget_s=float(env["seconds"]))
    t_ret = time.perf_counter()
    if not closed:
        closed["t"] = t_ret
        hooks["window_closes"]()
    clock["window_s"] = closed["t"] - t_b
    clock["collect_s"] = t_ret - closed["t"]

    blocks = [r for r in records if r.get("event") == "block"]
    skip = block  # call A's one block comes back with B's result
    measured = {
        "window_s": clock["window_s"], "blocks": blocks,
        "attempted": len(blocks),
        "failed": 0 if blocks and result.budget_exhausted else 1,
        "collect_s": clock["collect_s"],
        "time_to_first_draw_s": first.get("t"),
        "warmup_done": first.get("rec"),
        "draws_flat": np.asarray(result.draws_flat)[:, skip:],
        "draws": {k: np.asarray(v)[:, skip:] for k, v in result.draws.items()},
        "chains": chains, "block_size": block, "sizes": sizes,
        "state_start": _state(ck_a), "state_end": _state(ck_b),
        "full_warmup": bool(cfg.get("full_warmup", False)),
    }
    # free what the program holds on the device before the reference runs
    del result, data, model, backend
    return measured


def _state(path):
    """z, pe, grad of every chain as the program checkpointed them (a plain
    .npz)."""
    import numpy as np

    with np.load(path) as f:
        return {k: np.asarray(f[k]) for k in ("z", "pe", "grad")}
