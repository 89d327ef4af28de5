"""Toy checkpoints that pin the runner's file format from outside a change.

`python tests/_runner_ckpt_fixtures.py` writes, with whatever tree it runs
on, three checkpoints into `tests/fixtures/` (ChEES sampling-phase, ChEES
mid-warm-up, NUTS) and beside each the draws of the block a resume samples
next.  The committed files were written by PR 29's tree (commit 903e46a),
before PR 30 moved the carry and its file behind the kernel seam;
`tests/test_runner_seam.py` resumes each under the tree it runs on and holds
the draws to those, bit for bit.  Write them again only when the format is
meant to change.  `chees_centred` is PR 32's: a model that centres its
potential on one chip, chain by chain (`Model.center_per_chain`), whose file
holds ``pe_center`` (a row a chain) and the potential in float64; no tree
before it could write it
(`python tests/_runner_ckpt_fixtures.py chees_centred` wrote that one alone,
from the repo's root with `PYTHONPATH=.` and, as `conftest.py` sets them for
the tests, `JAX_PLATFORMS=cpu STARK_PROFILE=0
XLA_FLAGS=--xla_force_host_platform_device_count=8`: with one host device the
draws differ in their last digits).  Its next block's draws were written again
by PR 39's tree from the same checkpoint: the grouped Gaussian kernel there
sums in another order, and 62 of the 736 draws moved by one float32 ulp.
"""

import os
import shutil
import sys

import jax.numpy as jnp
import numpy as np

import stark_tpu
from stark_tpu.model import Model, ParamSpec

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


class ToyRegression(Model):
    """Three coefficients and a scale over 24 rows: small, with data (the
    programs then take their trailing ``data`` argument) and a constrained
    parameter."""

    def param_spec(self):
        from stark_tpu.bijectors import Exp

        return {"beta": ParamSpec((3,)), "sigma": ParamSpec((), Exp())}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["beta"] ** 2) - p["sigma"]

    def log_lik(self, p, data):
        r = (data["y"] - data["x"] @ p["beta"]) / p["sigma"]
        return -0.5 * jnp.sum(r * r) - data["y"].shape[0] * jnp.log(p["sigma"])


def toy_rows():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((24, 3)).astype(np.float32)
    y = (x @ np.array([0.5, -1.0, 0.25], np.float32)
         + 0.3 * rng.standard_normal(24).astype(np.float32))
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def toy_grouped():
    """A linear mixed model over 8 groups of 512 rows in all, through the
    grouped kernel (interpreted here): the model that centres chain by chain."""
    import jax

    from stark_tpu.models import FusedLinearMixedModelGrouped, synth_lmm_data

    data, _ = synth_lmm_data(jax.random.PRNGKey(4), 512, 3, 8)
    return FusedLinearMixedModelGrouped(3, 8), data


_CHEES = dict(
    kernel="chees", chains=8, block_size=4, num_warmup=12, map_init_steps=3,
    init_step_size=0.1, max_leapfrog=8, seed=3, rhat_target=0.0,
    adaptive_blocks=False, min_blocks=1,
)
_NUTS = dict(
    kernel="nuts", chains=4, block_size=5, num_warmup=20, max_tree_depth=4,
    seed=5, rhat_target=0.0, adaptive_blocks=False, min_blocks=1,
)

#: name -> (the call's arguments, blocks the writing run samples, blocks the
#: resumed run ends at, the warm-up step whose checkpoint is kept: None for a
#: sampling-phase file)
CASES = {
    "chees_sample": (_CHEES, 2, 3, None),
    "chees_warmup": (_CHEES, 1, 1, 8),
    "nuts_sample": (_NUTS, 2, 3, None),
    "chees_centred": (_CHEES, 2, 3, None),
}


def _job(name):
    """(model, rows) of a case."""
    if name == "chees_centred":
        return toy_grouped()
    return ToyRegression(), toy_rows()


def paths(name):
    base = os.path.join(FIXTURES, f"runner_ckpt_{name}")
    return base + ".npz", base + ".next.npy"


def write_checkpoint(name, path):
    """Run ``name``'s call on this tree and leave its checkpoint at ``path``
    (a mid-warm-up case keeps the file as it stood after that segment)."""
    kw, blocks, _, warm_at = CASES[name]
    if warm_at is None:
        stark_tpu.sample_until_converged(
            *_job(name), max_blocks=blocks, checkpoint_path=path, **kw,
        )
        return
    from stark_tpu import checkpoint as ck

    live = path + ".live.npz"
    real = ck.save_checkpoint

    def keep(p, arrays, meta):
        real(p, arrays, meta)
        if meta.get("phase") == "warmup" and meta.get("warm_done") == warm_at:
            shutil.copyfile(p, path)

    ck.save_checkpoint = keep
    try:
        stark_tpu.sample_until_converged(
            *_job(name), max_blocks=blocks,
            checkpoint_path=live, **kw,
        )
    finally:
        ck.save_checkpoint = real
        os.unlink(live)


def resume_next_block(name, path, **more):
    """Resume ``path`` on this tree; -> the flat draws of the one block the
    resume samples, (chains, block, d)."""
    kw, _, blocks, _ = CASES[name]
    kw = {k: v for k, v in kw.items() if k not in ("chains", "seed")}
    post = stark_tpu.sample_until_converged(
        *_job(name), max_blocks=blocks, resume_from=path, **kw, **more,
    )
    return np.asarray(post.draws_flat)[:, -kw["block_size"]:]


if __name__ == "__main__":
    os.makedirs(FIXTURES, exist_ok=True)
    for case in sys.argv[1:] or CASES:
        ckpt, nxt = paths(case)
        write_checkpoint(case, ckpt)
        np.save(nxt, resume_next_block(case, ckpt))
        print(case, os.path.getsize(ckpt), np.load(nxt).shape)
