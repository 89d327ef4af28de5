"""Rows that arrive already sharded over the mesh stay where they are, and a
mesh run's programs carry the fixed names a one-chip run's do (PR 28): the
program's half of the data-sharded deployment, at toy size on the eight host
devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from stark_tpu import prepare_model_data, telemetry
from stark_tpu.backends import JaxBackend, ShardedBackend
from stark_tpu.chees import CHEES_PROGRAMS
from stark_tpu.model import flatten_model
from stark_tpu.models import FusedLogistic
from stark_tpu.models.logistic import Y_LANES, synth_logistic_data
from stark_tpu.parallel.mesh import make_mesh, shard_data
from stark_tpu.parallel.primitives import map_shards, placed, shard_put
from stark_tpu.runner import _mesh_shape, _psum_counters
from stark_tpu.sampler import SamplerConfig

N, D = 2048, 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"data": 4, "chains": 1}, devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def host_rows():
    data, _ = synth_logistic_data(jax.random.PRNGKey(3), N, D)
    return {k: np.asarray(v) for k, v in data.items()}


@pytest.fixture(scope="module")
def sharded_rows(mesh, host_rows):
    """Global arrays laid out by row over `data`, as a generator that makes
    each shard on its own chip hands them over."""
    return {
        "x": jax.device_put(host_rows["x"], NamedSharding(mesh, P("data", None))),
        "y": jax.device_put(host_rows["y"], NamedSharding(mesh, P("data"))),
    }


def _mark():
    """The newest span there is: the log is a bounded deque, so a position
    in it says nothing once a worker has closed more spans than it holds."""
    log = telemetry.span_log()
    return log[-1] if log else None


def _spans(name, since):
    """The spans called `name` closed after the record `_mark` gave."""
    log = telemetry.span_log()
    start = next((i + 1 for i in range(len(log) - 1, -1, -1)
                  if log[i] is since), 0)
    return [r for r in log[start:] if r.name == name]


def test_prepare_keeps_sharded_rows_on_their_devices(mesh, sharded_rows):
    model = FusedLogistic(D)
    data = prepare_model_data(model, sharded_rows)
    want = NamedSharding(mesh, P(None, "data"))
    assert data["xT"].sharding.is_equivalent_to(want, 2)
    assert data["y"] is sharded_rows["y"]
    for leaf in jax.tree.leaves(data):
        assert len(leaf.sharding.device_set) == 4
    # shard i of xT is the transpose of shard i of x, on the same device
    for sx, st in zip(sharded_rows["x"].addressable_shards,
                      data["xT"].addressable_shards):
        assert sx.device == st.device
        np.testing.assert_array_equal(np.asarray(sx.data).T,
                                      np.asarray(st.data))


def test_prepare_cuts_y_lanes_with_the_rows_of_xT(mesh, sharded_rows):
    """`FusedLogistic`'s (1, N) outcome leaf (PR 29) has row axis 1: made
    shard by shard where `y` lies, in the sharding `shard_data` asks for."""
    model = FusedLogistic(D)
    data = prepare_model_data(model, sharded_rows)
    assert model.data_shard_row_axes(data) == {"xT": 1, "y": 0, Y_LANES: 1}
    assert placed(data[Y_LANES], mesh, P(None, "data"))
    for sy, sl in zip(sharded_rows["y"].addressable_shards,
                      data[Y_LANES].addressable_shards):
        assert sy.device == sl.device and sl.data.shape == (1, N // 4)
        np.testing.assert_array_equal(np.asarray(sy.data)[None],
                                      np.asarray(sl.data))


def test_shard_data_moves_nothing_that_is_placed(mesh, sharded_rows):
    model = FusedLogistic(D)
    data = prepare_model_data(model, sharded_rows)
    since = _mark()
    out = shard_data(data, mesh, "data",
                     row_axes=model.data_shard_row_axes(data))
    (sp,) = _spans("shard_data", since)
    assert sp.fields["moved_bytes"] == 0 and sp.fields["shards"] == 4
    # xT, y and y in the kernel's layout beside it
    assert sp.fields["bytes"] == N * D * 4 + N * 4 + N * 4
    # not a copy: the very arrays that came in
    assert all(out[k] is data[k] for k in ("xT", "y", Y_LANES))


def test_shard_data_counts_what_it_moves_from_the_host(mesh, host_rows):
    since = _mark()
    out = shard_data(host_rows, mesh, "data")
    (sp,) = _spans("shard_data", since)
    assert sp.fields["moved_bytes"] == sp.fields["bytes"] == N * D * 4 + N * 4
    assert placed(out["x"], mesh, P("data", None))
    assert not placed(host_rows["x"], mesh, P("data", None))
    one = jax.device_put(host_rows["y"], jax.devices()[0])
    assert not placed(one, mesh, P("data"))
    assert placed(shard_put(one, mesh, P("data")), mesh, P("data"))


def test_adaptive_parts_leaves_no_leaf_on_one_device(mesh, sharded_rows):
    model = FusedLogistic(D)
    since = _mark()
    ap = ShardedBackend(mesh).adaptive_parts(
        model, SamplerConfig(kernel="chees"), sharded_rows)
    assert _spans("shard_data", since)[0].fields["moved_bytes"] == 0
    for leaf in jax.tree.leaves(ap.data):
        assert len(leaf.sharding.device_set) == 4
        assert len({s.device for s in leaf.addressable_shards}) == 4
    assert ap.data["y"] is sharded_rows["y"]


def _module_name(jitted, *args):
    return jitted.lower(*args).as_text().split("module @")[1].split()[0]


def test_map_shards_takes_a_program_name(mesh, monkeypatch):
    monkeypatch.setenv("STARK_COMM_TELEMETRY", "0")  # the bare jit comes back

    def body(x):
        return x + 1.0

    x = jnp.zeros((8,), jnp.float32)
    named = map_shards(body, mesh=mesh, axis="data", name="stark_test_program")
    assert _module_name(named, x) == "jit_stark_test_program"
    assert _module_name(map_shards(body, mesh=mesh, axis="data"), x) == "jit_body"
    # off the mesh nothing changes: literally jax.jit(fn), named after fn
    assert _module_name(map_shards(body), x) == "jit_body"
    np.testing.assert_array_equal(np.asarray(named(x)), np.ones(8))


def test_mesh_chees_programs_carry_the_fixed_names(mesh, sharded_rows,
                                                   monkeypatch):
    monkeypatch.setenv("STARK_COMM_TELEMETRY", "0")
    model = FusedLogistic(D)
    cfg = SamplerConfig(kernel="chees", num_warmup=10, map_init_steps=2)
    backend = ShardedBackend(mesh)
    ap = backend.adaptive_parts(model, cfg, sharded_rows)
    z0 = ap.put_chains(jnp.zeros((8, D), jnp.float32))
    key = jax.random.PRNGKey(0)
    assert _module_name(ap.init_j, key, z0, ap.data) == (
        "jit_" + CHEES_PROGRAMS["init"])
    carry = ap.init_j(key, z0, ap.data)
    run_carry = ap.chees.finalize(carry)
    keys, us = jax.random.split(key, 3), jnp.ones((3,), jnp.float32)
    assert _module_name(ap.samp_j, run_carry, keys, us, ap.data) == (
        "jit_" + CHEES_PROGRAMS["samp"]) == "jit_stark_chees_sample"
    # the trace of the potential wrote down what a gradient sends
    assert ap.fm.comm == {"psums_per_gradient": 1,
                          "psum_bytes_per_chain": 4 * (1 + D)}
    assert _psum_counters(ap.fm, 8, backend) == {
        "psums_per_gradient": 1, "psum_bytes_per_gradient": 8 * 4 * (1 + D)}
    # a second call for the same model hands back the same flat model: the
    # cached programs closed over it
    assert backend.adaptive_parts(model, cfg, sharded_rows).fm is ap.fm


def test_one_device_has_no_mesh_and_no_psum(host_rows):
    backend = JaxBackend()
    assert _mesh_shape(backend) == (1, 1)
    assert _mesh_shape(None) == (1, 1)
    fm = flatten_model(FusedLogistic(D))
    jax.jit(fm.potential_and_grad)(
        jnp.zeros((D,)), prepare_model_data(FusedLogistic(D), host_rows))
    assert fm.comm == {} and _psum_counters(fm, 8, backend) == {}
