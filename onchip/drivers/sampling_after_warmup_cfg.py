"""Driver `sampling_after_warmup_cfg`: `sampling_after_warmup` with the
sampler taken from the configuration (`config["sampler"]["kernel"]`: the
per-chain kernels as well as the ensemble sampler), and one thing more for a
check that knows trees.

  set-up   rows from the seed on the device (the configuration's generator);
           the model's own `prepare_data`; call A:
           `stark_tpu.sample_until_converged` from cold chains, the configured
           MAP and warm-up and one draw block, checkpointed; then a rehearsal
           of the resume path (A's checkpoint, one more block), so that every
           program of the window is compiled or found in the cache
  window   call B: the same call resumed from A's checkpoint, fixed blocks
           under `time_budget_s`; the window is B's call to its
           `budget_exhausted` record (every block processed and checkpointed).
           What the entry does after that record, until it returns, is
           `collect_s`
  after    where the configuration's kernel is NUTS: one leaf of the sampler's
           tree from the state the window's last block left (`lib/leaf.py`),
           and what the block spans said of the window's trees

The driver takes the system under test and its records; every number is made
from them by the readers, the cell's check and `lib`.  (`sampling_after_warmup`
writes `kernel="chees"` into the call and `sampling_after_warmup_sharded` adds
a mesh: a `benchmark` PR can fold both into this file.)
"""

import os
import time


def run(env):
    """`env`: config, sizes, seed, seconds, out dir, clock, hooks, load.
    Returns the run's measurements as a dict."""
    import jax
    import numpy as np

    import stark_tpu
    from stark_tpu import models, telemetry
    from stark_tpu.backends.jax_backend import JaxBackend

    from lib import leaf as libleaf
    from lib.seeds import seed_words

    cfg, sizes, out = env["config"], env["sizes"], env["out"]
    sampler = dict(cfg["sampler"], **sizes.get("sampler", {}))
    block = int(sampler.pop("block_size"))
    chains = int(sampler.pop("chains"))
    clock = env["clock"]

    spec = sizes.get("model", cfg["model"])
    model = getattr(models, spec["class"])(*spec["args"])
    backend = JaxBackend()
    ck_a = os.path.join(out, "ck_a.npz")
    ck_b = os.path.join(out, "ck_b.npz")
    # the chains' seed is the run's: another word of it than the rows' order
    chain_seed = seed_words(env["seed"])[1]

    def entry(rows, **kw):
        return stark_tpu.sample_until_converged(
            model, rows, backend=backend, chains=chains, rhat_target=0.0,
            adaptive_blocks=False, block_size=block, min_blocks=1,
            seed=chain_seed, **dict(sampler, **kw))

    # rows, and the model's own layout of them; the raw copy is dropped
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

    # call A: cold chains -> (MAP ->) warm-up -> one block
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
    flat = np.asarray(result.draws_flat)
    measured = {
        "window_s": clock["window_s"], "blocks": blocks,
        "attempted": len(blocks),
        "failed": 0 if blocks and result.budget_exhausted else 1,
        "collect_s": clock["collect_s"],
        "time_to_first_draw_s": first.get("t"),
        "warmup_done": first.get("rec"),
        "draws_flat": flat[:, skip:],
        # call A's block, as B's result hands it back: where each chain stood
        # between warm-up and the window (a check may want how far it has come)
        "draws_before": flat[:, :skip],
        "draws": {k: np.asarray(v)[:, skip:] for k, v in result.draws.items()},
        "chains": chains, "block_size": block, "sizes": sizes,
        "state_start": _state(ck_a), "state_end": _state(ck_b),
        "full_warmup": bool(cfg.get("full_warmup", False)),
        "kernel": sampler.get("kernel", "nuts"),
    }
    if measured["kernel"] == "nuts":
        measured["max_tree_depth"] = depth = int(sampler["max_tree_depth"])
        # what the window's `block.gate` spans said of its trees, block by
        # block (nothing from a program without the counters)
        log = telemetry.span_log()
        last = max((s.run for s in log if s.name == "run"), default=None)
        measured["tree_spans"] = [
            dict(s.fields) for s in log
            if s.name == "block.gate" and s.run == last]
        t = time.perf_counter()
        measured["leaf"] = libleaf.program_leaf(
            model, data, measured["state_end"], depth,
            seed_words(env["seed"])[0])
        clock["leaf_s"] = time.perf_counter() - t
    # free what the program holds on the device before the reference runs
    del result, flat, data, model, backend
    return measured


def _state(path):
    """Every chain's state as the program checkpointed it (a plain .npz): z,
    pe, grad, the step size and the mass, and the centre where the program
    carries one."""
    import numpy as np

    with np.load(path) as f:
        return {k: np.asarray(f[k]) for k in
                ("z", "pe", "grad", "step_size", "inv_mass", "pe_center")
                if k in f.files}
