"""`parallel.primitives` — the DrJAX-style MapReduce layer every
parallel composition (consensus, tempering, sharded backend, mesh fleet)
now runs on.

The contracts: the no-mesh fast path is literally ``jax.jit`` (bit- and
trace-identical to the hand-rolled code it replaced); the mesh path's
per-shard results equal the unsharded computation; `reduce_tree` is the
in-program psum/pmax/pmin with an axis-None identity; the placement
helpers land leaves on the requested shardings; `gather_tree` hands back
the global host view.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stark_tpu.parallel.mesh import make_mesh
from stark_tpu.parallel.primitives import (
    axis_size,
    broadcast,
    gather_tree,
    map_shards,
    reduce_tree,
    run_over_chains,
    shard_put,
)


def _mesh(n, axis="data"):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest forces 8)")
    return make_mesh({axis: n}, devices=jax.devices()[:n])


def test_identity_fast_path_is_plain_jit():
    """mesh=None returns exactly jit(fn): same results, and a jitted
    callable (lowering works) — the single-device callers' bit-identity
    rides on there being NO wrapper at all."""

    def f(x, y):
        return x * 2.0 + y

    jf = map_shards(f)
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(jf(x, x)), np.asarray(x * 3.0))
    # a jit-wrapped callable exposes lower() — a plain wrapper would not
    assert hasattr(jf, "lower")


def test_map_shards_matches_unsharded():
    """Per-shard map over "data" == the unsharded vmap, bitwise."""
    mesh = _mesh(4)
    v = jax.vmap(lambda x: jnp.sin(x) * 2.0)
    x = jnp.arange(8.0).reshape(8, 1)
    ref = np.asarray(jax.jit(v)(x))
    out = map_shards(v, mesh=mesh, axis="data")(x)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_map_shards_explicit_mixed_specs():
    """Replicated args (P()) see the FULL value on every shard."""
    mesh = _mesh(2)

    def f(x, c):
        # c is replicated: every shard adds the same full-vector sum
        return x + jnp.sum(c)

    x = jnp.arange(4.0)
    c = jnp.asarray([1.0, 2.0])
    out = map_shards(
        f, mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data")
    )(x, c)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x + 3.0))


def test_map_shards_needs_specs_or_axis():
    with pytest.raises(ValueError, match="axis"):
        map_shards(lambda x: x, mesh=_mesh(2))
    with pytest.raises(ValueError, match="arity"):
        map_shards(lambda *a: a[0], mesh=_mesh(2), axis="data")


def test_reduce_tree_psum_inside_map():
    """The reduce primitive: a psum over the mapped axis equals the
    global sum on every shard — the MapReduce composition."""
    mesh = _mesh(4)

    def f(x):
        return reduce_tree(jnp.sum(x), axis="data")

    x = jnp.arange(8.0)
    out = map_shards(
        f, mesh=mesh, in_specs=(P("data"),), out_specs=P()
    )(x)
    assert float(out) == float(jnp.sum(x))


def test_reduce_tree_identity_and_ops():
    tree = {"a": jnp.asarray([1.0, 2.0])}
    same = reduce_tree(tree, axis=None)
    assert same is tree  # axis=None: shared code runs unchanged
    with pytest.raises(ValueError, match="unknown reduce op"):
        reduce_tree(tree, axis="data", op="mean")


def test_shard_put_and_broadcast_place_leaves():
    mesh = _mesh(2)
    x = np.arange(4.0, dtype=np.float32)
    sharded = shard_put({"x": x}, mesh, P("data"))
    assert sharded["x"].sharding.spec == P("data")
    rep = broadcast({"c": np.float32(3.0)}, mesh)
    assert rep["c"].sharding.spec == P()
    # no mesh: both are the identity
    t = {"x": x}
    assert shard_put(t, None, P("data")) is t
    assert broadcast(t, None) is t


def test_gather_tree_global_host_view():
    mesh = _mesh(2)
    x = np.arange(4.0, dtype=np.float32)
    sharded = shard_put({"x": x}, mesh, P("data"))
    back = gather_tree(sharded)
    assert isinstance(back["x"], np.ndarray)
    np.testing.assert_array_equal(back["x"], x)


def test_axis_size():
    assert axis_size(None, "problems") == 1
    mesh = _mesh(4)
    assert axis_size(mesh, "data") == 4
    with pytest.raises(ValueError, match="no 'chains' axis"):
        axis_size(mesh, "chains")


def test_run_over_chains_parity():
    """The chains-axis dispatch helper (tempering / SG-HMC) returns the
    same values as the plain vmapped computation."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_mesh(
        {"data": 1, "chains": 2}, devices=jax.devices()[:2]
    )
    v = jax.vmap(lambda k, z: (z * 2.0, jnp.sum(z)))
    keys = jnp.zeros((4, 2), jnp.uint32)
    z = jnp.arange(8.0).reshape(4, 2)
    ref = jax.jit(v)(keys, z)
    out = run_over_chains(mesh, v, keys, z)
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
    bad = make_mesh({"data": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="chains"):
        run_over_chains(bad, v, keys, z)


# ---------------------------------------------------------------------------
# scan_shards — the ordered cross-shard scan (PR 19)
# ---------------------------------------------------------------------------


def _exclusive_sums(shard_sums, reverse=False):
    out = []
    for i in range(len(shard_sums)):
        peers = shard_sums[i + 1:] if reverse else shard_sums[:i]
        out.append(float(sum(peers)))
    return out


def test_scan_shards_gather_forward_and_reverse():
    """Gather mode hands ``combine`` the shard-ordered totals and the
    strictly-before mask (strictly-after under ``reverse``) — the
    masked-sum combine reproduces the exclusive prefix per shard."""
    from jax import shard_map
    from stark_tpu.parallel.primitives import scan_shards

    mesh = _mesh(4)
    x = jnp.arange(8.0)  # shard sums: [1, 5, 9, 13]

    def run(reverse):
        def f(xs):
            c = scan_shards(
                jnp.sum(xs), "data", reverse=reverse,
                combine=lambda t, m: jnp.sum(jnp.where(m, t, 0.0)),
            )
            return c[None]

        fn = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P("data"), check_vma=False)
        return np.asarray(jax.jit(fn)(x))

    np.testing.assert_array_equal(
        run(False), _exclusive_sums([1.0, 5.0, 9.0, 13.0])
    )
    np.testing.assert_array_equal(
        run(True), _exclusive_sums([1.0, 5.0, 9.0, 13.0], reverse=True)
    )


def test_scan_shards_axis_none_identity():
    """axis=None is the single-shard case: one stacked total, an
    all-False mask (no predecessors in either direction)."""
    from stark_tpu.parallel.primitives import scan_shards

    def combine(totals, mask):
        assert totals.shape == (1,)
        return jnp.sum(jnp.where(mask, totals, 0.0))

    assert float(scan_shards(jnp.float32(7.0), None, combine=combine)) == 0.0
    v = jnp.arange(6.0)
    np.testing.assert_array_equal(
        np.asarray(scan_shards(v, None, replicated=True)), np.asarray(v)
    )


def test_scan_shards_replicated_ordered_slices():
    """Replicated mode returns shard s's contiguous slice of the full
    replicated sequence — gathering the per-shard slices along the shard
    axis reassembles the sequence exactly."""
    from jax import shard_map
    from stark_tpu.parallel.primitives import scan_shards

    mesh = _mesh(4)
    full = jnp.arange(8.0) * 1.5

    def f(_):
        return scan_shards(full, "data", replicated=True)

    fn = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P("data"), check_vma=False)
    out = jax.jit(fn)(jnp.zeros(4))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_scan_shards_mode_and_divisibility_errors():
    from jax import shard_map
    from stark_tpu.parallel.primitives import scan_shards

    with pytest.raises(ValueError, match="combine"):
        scan_shards(jnp.zeros(2), None)  # gather mode needs combine=
    with pytest.raises(ValueError, match="gather mode"):
        scan_shards(jnp.zeros(2), None, replicated=True,
                    combine=lambda t, m: t)
    mesh = _mesh(4)
    fn = shard_map(
        lambda _: scan_shards(jnp.zeros(7), "data", replicated=True),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    )
    with pytest.raises(ValueError, match="does not divide"):
        jax.jit(fn)(jnp.zeros(4))  # 7 rows over 4 shards would alias


def test_scan_shards_comm_accounted_and_silenceable(tmp_path, monkeypatch):
    """Gather mode emits one comm event per traced scan (wire = payload
    x axis size — the allgather); replicated mode moves nothing and
    emits nothing; STARK_COMM_TELEMETRY=0 silences the accounting with
    bit-identical results."""
    from jax import shard_map
    from stark_tpu.parallel.primitives import scan_shards
    from stark_tpu.telemetry import RunTrace, read_trace, use_trace

    mesh = _mesh(4)
    x = jnp.arange(8.0)

    def compute():
        def f(xs):
            c = scan_shards(
                jnp.sum(xs), "data",
                combine=lambda t, m: jnp.sum(jnp.where(m, t, 0.0)),
            )
            h = scan_shards(jnp.arange(8.0), "data", replicated=True)
            return c + h

        fn = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P("data"), check_vma=False)
        return np.asarray(jax.jit(fn)(x))

    trace_on = str(tmp_path / "on.jsonl")
    with RunTrace(trace_on) as tr, use_trace(tr):
        y_on = compute()
    comm = [e for e in read_trace(trace_on) if e.get("event") == "comm"]
    scans = [e for e in comm if e["primitive"] == "scan_shards"]
    assert len(scans) == 1, comm  # replicated half emits nothing
    (ev,) = scans
    assert ev["axis"] == "data" and ev["participants"] == 4
    assert ev["payload_bytes"] == 4          # one f32 scalar per shard
    assert ev["wire_bytes"] == 16            # allgather: payload x shards

    monkeypatch.setenv("STARK_COMM_TELEMETRY", "0")
    trace_off = str(tmp_path / "off.jsonl")
    with RunTrace(trace_off) as tr, use_trace(tr):
        y_off = compute()
    assert not [e for e in read_trace(trace_off)
                if e.get("event") == "comm"]
    np.testing.assert_array_equal(y_on, y_off)
