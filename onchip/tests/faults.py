"""Faults planted in the program underneath the harness: what `correct` has to
catch.  Each takes `patch(obj, name, value)` (`monkeypatch.setattr` in the
tests; plain `setattr` in `run_with_fault.py`, which reads a fault at a cell's
own size on the chip)."""


def state_unchanged(patch):
    """A step that returns its state unchanged."""
    import stark_tpu.chees as chees

    real = chees.chees_transition

    def frozen(key, states, *a, **kw):
        _, info = real(key, states, *a, **kw)
        return states, info

    patch(chees, "chees_transition", frozen)


def _held(which, old, new):
    """`new`, but for the chains `which` (a mask, chains first), which keep
    `old`: every leaf of a state."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda o, n: jnp.where(
            which.reshape((-1,) + (1,) * (o.ndim - 1)), o, n), old, new)


def a_quarter_handed_back(patch):
    """The same for a quarter of the chains (4 of 16, the first ones): each
    transition's end state overwritten with its start for them, the reported
    acceptance left as it was.  `frozen_chains` counts them, and where a
    cell's limit lets so many stand still (a configuration whose chains are
    not adapted) `accept_gap` has to catch it: the program says they moved."""
    import jax.numpy as jnp

    import stark_tpu.chees as chees

    real = chees.chees_transition

    def frozen(key, states, *a, **kw):
        new, info = real(key, states, *a, **kw)
        held = jnp.arange(states.z.shape[0]) < states.z.shape[0] // 4

        return _held(held, states, new), info

    patch(chees, "chees_transition", frozen)


def one_chain_rejects_all(patch):
    """No fault: what a chain does that stands where the ensemble's one step
    size is too long.  Every proposal of the first chain is rejected AND
    reported so (acceptance 0), so the run has to come out `correct` in a cell
    whose limit lets a chain stand still."""
    import jax.numpy as jnp

    import stark_tpu.chees as chees

    real = chees.chees_transition

    def rejecting(key, states, *a, **kw):
        new, info = real(key, states, *a, **kw)
        first = jnp.arange(states.z.shape[0]) == 0

        return _held(first, states, new), info._replace(
            accept_prob=jnp.where(first, 0.0, info.accept_prob),
            is_accepted=jnp.where(first, False, info.is_accepted))

    patch(chees, "chees_transition", rejecting)


def half_the_rows(patch):
    """Half of the rows left out, the rest counted double (flat model)."""
    from stark_tpu.models import logistic as lg
    from stark_tpu.ops.logistic_fused import logistic_loglik

    def half(self, p, data):
        n = data["y"].shape[0] // 2
        return 2.0 * logistic_loglik(p["beta"], data["xT"][:, :n],
                                     data["y"][:n])

    patch(lg.FusedLogistic, "log_lik", half)


def half_the_rows_grouped(patch):
    """The grouped kernel's tiles cannot be cut: the same fault in kind is
    every row's outcome pulled half way to a coin flip."""
    from stark_tpu.models import logistic as lg

    real = lg.FusedHierLogisticGrouped.log_lik

    def half(self, p, data):
        return real(self, p, dict(data, y=data["y"] * 0.5 + 0.25))

    patch(lg.FusedHierLogisticGrouped, "log_lik", half)


def draw_altered(patch):
    """A draw altered where it is produced."""
    import stark_tpu.chees as chees

    real = chees.chees_transition

    def altered(key, states, *a, **kw):
        states, info = real(key, states, *a, **kw)
        return states._replace(z=states.z + 1e-3), info

    patch(chees, "chees_transition", altered)


def rejected_tries_again(patch):
    """A wrong accept step: every chain gets a second proposal after the
    first, and the first one's acceptance is what is reported.  Position,
    energy and gradient stay consistent; the chains move more often than the
    acceptance probability says."""
    import jax

    import stark_tpu.chees as chees

    real = chees.chees_transition

    def twice(key, states, *a, **kw):
        states, info = real(key, states, *a, **kw)
        states, _ = real(jax.random.fold_in(key, 1), states, *a, **kw)
        return states, info

    patch(chees, "chees_transition", twice)


FAULTS = {f.__name__: f for f in (
    state_unchanged, a_quarter_handed_back, one_chain_rejects_all,
    half_the_rows, half_the_rows_grouped, draw_altered,
    rejected_tries_again)}
