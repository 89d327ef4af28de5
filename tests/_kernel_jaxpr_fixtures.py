"""Kernel jaxprs that pin what a change to the packed bf16 helpers may not
move.

`python tests/_kernel_jaxpr_fixtures.py` writes `tests/fixtures/
kernel_jaxprs.json`, with whatever tree it runs on: the jaxpr, as text, of

* the grouped Bernoulli kernel (`stark_hier_ll_grouped`) at ``highest``,
  which runs the packed helpers of `ops/precision.py` with exact rows
  (K > 0), and
* the chain-batched logistic kernel (`stark_logistic_ll`), both links, with
  and without offsets, at ``high`` and ``default``, where it runs one f32
  dot each way at that precision.

The committed file was written by the tree before the chain-batched kernel
took the packed form at ``highest`` (commit 722fd97);
`tests/test_ops_fused.py` traces the same calls under the tree it runs on
and holds each text to it, character for character.  Write it again only
when one of these kernels is meant to change.  Run from the repo's root with
`PYTHONPATH=. JAX_PLATFORMS=cpu`.
"""

import json
import os

import jax
import jax.numpy as jnp

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "kernel_jaxprs.json",
)

#: (precision, case) pairs recorded; a case is a kernel and its variant
CASES = [("highest", "stark_hier_ll_grouped")] + [
    (precision, f"stark_logistic_ll.{link}.{offsets}")
    for precision in ("high", "default")
    for link in ("bernoulli_logit", "gaussian")
    for offsets in ("noff", "off")
]


def _grouped_jaxpr():
    from stark_tpu.ops.hier_fused import _grouped_call

    n, d, chains, groups, k_loc, lane_tile = 1024, 8, 8, 40, 24, 512
    return jax.make_jaxpr(
        lambda b, a, xt, y, gl, fg: _grouped_call(
            b, a, xt, y, gl, fg, k_loc=k_loc, lane_tile=lane_tile,
            interpret=True,
        )
    )(
        jnp.zeros((chains, d)), jnp.zeros((chains, groups)),
        jnp.zeros((d, n)), jnp.zeros((n,)), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n // lane_tile,), jnp.int32),
    )


def _batched_jaxpr(link, offsets):
    from stark_tpu.ops.logistic_fused import _batched_call

    n, d, chains, lane_tile = 1024 + 37, 8, 3, 512
    args = [jnp.zeros((chains, d)), jnp.zeros((d, n)), jnp.zeros((n,))]
    args.append(jnp.zeros((chains, n)) if offsets == "off" else None)
    return jax.make_jaxpr(
        lambda b, xt, y, off: _batched_call(
            b, xt, y, off, lane_tile=lane_tile, interpret=True, link=link,
        )
    )(*args)


def kernel_jaxpr(precision, case):
    """The jaxpr text of ``case`` traced at ``precision``
    (STARK_FUSED_PRECISION), the variable restored afterwards."""
    was = os.environ.get("STARK_FUSED_PRECISION")
    os.environ["STARK_FUSED_PRECISION"] = precision
    try:
        if case == "stark_hier_ll_grouped":
            return str(_grouped_jaxpr())
        _, link, offsets = case.split(".")
        return str(_batched_jaxpr(link, offsets))
    finally:
        if was is None:
            del os.environ["STARK_FUSED_PRECISION"]
        else:
            os.environ["STARK_FUSED_PRECISION"] = was


def main():
    out = {f"{case}@{precision}": kernel_jaxpr(precision, case)
           for precision, case in CASES}
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(FIXTURE, {k: len(v) for k, v in out.items()})


if __name__ == "__main__":
    main()
