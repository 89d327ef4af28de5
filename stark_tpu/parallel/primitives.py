"""DrJAX-style MapReduce primitives — ONE multi-host-aware collective
layer under every parallel composition (ROADMAP item 4).

Before this module, `parallel/consensus.py`, `parallel/tempering.py`,
`parallel/mesh.py`, and `backends/sharded.py` each called
`shard_map` and hand-rolled their own spec/placement boilerplate —
four bespoke collective call sites whose compositions only worked by
bespoke test matrix.  Following DrJAX ("Scalable and Differentiable
MapReduce Primitives in JAX", PAPERS.md), everything they (and the fleet's
problem-axis sharding) need reduces to a small primitive set with one
implementation:

  * `map_shards`   — map a function over shards of its inputs along a
    named mesh axis: ``jit(shard_map(fn))`` on a mesh, a plain
    ``jit(fn)`` identity fast path with no mesh (the vmapped lanes ARE
    the shards on one device).  The only place in the repo that touches
    `jax.shard_map`.
  * `reduce_tree`  — cross-shard reduction inside a mapped function
    (``lax.psum``/``pmax``/``pmin`` over the axis; identity with no
    axis), the MapReduce "reduce".
  * `broadcast`    — replicate a host value to every device of a mesh
    (multi-host: every process contributes its addressable replicas).
  * `shard_put`    — place a pytree along per-leaf PartitionSpecs
    (multi-host: per-process rows glued into one global array).
  * `gather_tree`  — materialize the global host view of a (possibly
    sharded) pytree; multi-process runs allgather so every host sees the
    same full value.
  * `scan_shards`  — ordered cross-shard scan (the DrJAX ordered-
    computation direction): gather per-shard scan totals IN SHARD ORDER
    and hand the caller its exclusive-scan mask, or slice a replicated
    sequence into this shard's ordered block — the two halves of the
    sequence-parallel likelihood stitching (CoxPH / StochasticVolatility).

Single-device, single-host behavior is bit-identical to the hand-rolled
code it replaced: `map_shards(fn, mesh=None)` is literally ``jax.jit(fn)``
and the placement helpers degrade to ``device_put``/``np.asarray``.

**Hierarchical failure domains (PR 17).**  `DomainTree` describes the
physical placement hierarchy as an ordered axis tree — e.g. ``(region,
host, device)`` — and builds the matching multi-axis mesh.  The
primitives compose over it: `reduce_tree` accepts a SEQUENCE of axis
names and reduces level by level (innermost first), emitting one
comm event per level so wire bytes are accounted PER DOMAIN (the
device-level reduce never leaves its region; only the region-level
reduce crosses the expensive boundary), and `shard_put(..., home=)`
pins process-local data to its home slice of one domain axis instead
of striping it across the whole mesh.  A domain is thereby a unit of
failure the layers above can reason about: consensus drops a whole
region when any shard in it dies (`parallel/consensus.py`
``domains=``), and the mesh fleet re-packs survivors onto a shrunk
mesh (`stark_tpu/fleet.py`, ``STARK_SHARD_DEADLINE``).  The
``primitives.collective_stall`` failpoint drills a hung collective
deterministically at the two host-blocking dispatch sites
(`gather_tree` and the on-mesh `map_shards` dispatch).

**Communication observatory (PR 16).**  Because every collective in the
repo routes through this one module (tools/lint_collectives.py enforces
it), instrumenting HERE accounts for all of them with zero call-site
changes: each primitive dispatch emits a ``comm`` trace event
(`telemetry.COMM_EVENT_TYPES`) carrying the primitive kind, named axis,
participant count, predicted payload/wire bytes (`predict_tree_bytes`,
the `quantize.predict_x_bytes` idiom x collective fan), the host wall
blocked inside the call, the caller site, and a monotone sequence number
from `profiling.comm_probe` (so executed-vs-emitted counts are
testable).  Host-side collectives (`gather_tree`/`shard_put`/
`broadcast`/the `map_shards` on-mesh dispatch) account once per call;
in-program collectives (`reduce_tree`/`gather_axis`) once per TRACE of
the enclosing jit.  All of it is host-side bookkeeping outside the
compiled program's op/key sequence — draws, metrics, and checkpoints are
bit-identical with it on, and ``STARK_COMM_TELEMETRY=0`` removes every
wrapper and restores byte-identical traces.
"""

from __future__ import annotations

import functools
import os
import sys
import time
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import faults

PyTree = Any

#: reduction ops `reduce_tree` accepts -> the lax collective that runs
#: when a mesh axis is in scope
_REDUCE_OPS = ("sum", "max", "min")

#: opt-out knob for the communication observatory (default ON — the
#: accounting is host-side metadata arithmetic; "0" removes every
#: wrapper and restores byte-identical traces)
COMM_TELEMETRY_ENV = "STARK_COMM_TELEMETRY"


def comm_telemetry_enabled() -> bool:
    """True unless ``STARK_COMM_TELEMETRY=0`` — checked per primitive
    call (literal env read so the knob lint ties it to its README row)."""
    return os.environ.get("STARK_COMM_TELEMETRY", "1") != "0"


def predict_tree_bytes(tree: PyTree) -> int:
    """Predicted payload bytes of ONE participant's copy of ``tree`` —
    per-leaf ``prod(shape) * itemsize`` (the `quantize.predict_x_bytes`
    idiom generalized to pytrees).  Pure metadata arithmetic: works on
    tracers and on donated/deleted arrays, never touches buffer data."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            arr = np.asarray(leaf)
            shape, dtype = arr.shape, arr.dtype
        n = 1
        for s in shape:
            n *= int(s)
        total += n * np.dtype(dtype).itemsize
    return int(total)


def _caller_site(depth: int = 2) -> str:
    """``file.py:function`` of the primitive's caller — the zero-
    call-site-changes attribution key for the bytes-by-site ranking."""
    try:
        f = sys._getframe(depth)
        return f"{os.path.basename(f.f_code.co_filename)}:{f.f_code.co_name}"
    except Exception:
        return "unknown"


def _record_comm(
    primitive: str,
    *,
    site: str,
    axis: Optional[str],
    participants: int,
    payload_bytes: int,
    wire_bytes: int,
    host_blocked_s: float,
) -> None:
    """Bump the process CommProbe and emit one ``comm`` event.  The probe
    bump and the emission share this single path, so the acceptance
    invariant (executed count == emitted count) holds by construction
    whenever a trace is installed."""
    from .. import profiling, telemetry

    seq = profiling.comm_probe().bump(site, primitive, wire_bytes)
    tr = telemetry.get_trace()
    if tr is not None and tr.enabled:
        tr.emit(
            "comm",
            primitive=primitive,
            site=site,
            axis=axis,
            participants=int(participants),
            payload_bytes=int(payload_bytes),
            wire_bytes=int(wire_bytes),
            host_blocked_s=round(float(host_blocked_s), 6),
            seq=seq,
        )


def axis_size(mesh: Optional[Mesh], axis: str) -> int:
    """Shard count along ``axis`` — 1 with no mesh (the identity path)."""
    if mesh is None:
        return 1
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    return int(mesh.shape[axis])


class DomainTree:
    """Hierarchical failure-domain placement: an ordered axis tree.

    ``levels`` is a sequence of ``(name, size)`` pairs, OUTERMOST first —
    e.g. ``[("region", 2), ("device", 4)]`` describes 2 regions of 4
    devices.  The tree is pure placement metadata: `mesh()` realizes it
    as a multi-axis `jax.sharding.Mesh` (row-major over the levels, so a
    flat device ordinal's outermost coordinate IS its region), and the
    coordinate helpers answer "which domain does shard ``k`` live in" —
    the question every containment policy above this layer asks
    (consensus drops the whole region of a dead shard; the fleet's
    degraded re-shard excludes a lost domain's devices).

    Composition contract: ``reduce_tree(x, axis=tree.axis_names)``
    reduces level by level, innermost first, so the per-level comm
    events carry per-domain participant counts — wire bytes within a
    region and across regions are accounted separately.
    """

    def __init__(self, levels: Sequence[Tuple[str, int]]):
        levels = [(str(n), int(s)) for n, s in levels]
        if not levels:
            raise ValueError("DomainTree needs at least one level")
        names = [n for n, _ in levels]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate level names in {names}")
        for n, s in levels:
            if s < 1:
                raise ValueError(f"level {n!r} must have size >= 1, got {s}")
        self.levels: Tuple[Tuple[str, int], ...] = tuple(levels)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.levels)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.levels)

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def coords_of(self, ordinal: int) -> Tuple[int, ...]:
        """Per-level coordinates of a flat (row-major) device ordinal."""
        if not 0 <= int(ordinal) < self.size:
            raise ValueError(f"ordinal {ordinal} outside tree of {self.size}")
        out, rem = [], int(ordinal)
        for s in reversed(self.shape):
            out.append(rem % s)
            rem //= s
        return tuple(reversed(out))

    def domain_of(self, ordinal: int, level: Optional[str] = None) -> int:
        """The coordinate of ``ordinal`` at ``level`` (default: the
        OUTERMOST level — its region)."""
        names = self.axis_names
        k = names.index(str(level)) if level is not None else 0
        return self.coords_of(ordinal)[k]

    def ordinals_of(self, level: str, index: int) -> Tuple[int, ...]:
        """Every flat device ordinal whose ``level`` coordinate is
        ``index`` — the membership of one failure domain."""
        k = self.axis_names.index(str(level))
        return tuple(
            o for o in range(self.size) if self.coords_of(o)[k] == int(index)
        )

    def mesh(self, devices: Optional[Sequence[Any]] = None) -> Mesh:
        """Realize the tree as a multi-axis mesh over ``devices`` (default
        ``jax.devices()``), row-major: consecutive ordinals share the
        innermost domains first, so one region is a contiguous device
        range — the contiguity the fleet's shard->device mapping and
        `shard_put(home=)` pinning both rely on."""
        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < self.size:
            raise ValueError(
                f"DomainTree of size {self.size} needs {self.size} devices, "
                f"have {len(devs)}"
            )
        arr = np.asarray(devs[: self.size], dtype=object).reshape(self.shape)
        return Mesh(arr, self.axis_names)


def map_shards(
    fn,
    *,
    mesh: Optional[Mesh] = None,
    axis: Optional[str] = None,
    in_specs: Optional[Tuple] = None,
    out_specs: Any = None,
    check_vma: bool = False,
    donate: Sequence[int] = (),
    name: Optional[str] = None,
):
    """The map primitive: ``fn`` runs once per shard of its inputs along
    the mesh ``axis``, compiled as one program.

    * ``mesh is None`` — identity fast path: returns ``jax.jit(fn,
      donate_argnums=donate)`` exactly (no wrapper, no spec handling), so
      single-device callers are bit- and trace-identical to plain jit.
    * on a mesh — ``jit(shard_map(fn, mesh, in_specs, out_specs))``.
      ``in_specs``/``out_specs`` default to a ``P(axis)`` pytree-prefix
      on every argument/output (the common "everything carries the
      mapped axis leading" layout); pass explicit specs (tuples of specs
      or per-leaf spec pytrees) for mixed replicated/sharded signatures.

    ``donate`` forwards to the outer jit's ``donate_argnums`` (buffer
    donation of carried state) on both paths.  ``name`` is the on-mesh
    program's fixed name (`platform.named_jit`: the trace's modules line
    reads ``jit_<name>``); without one jax names it after ``fn``.

    On-mesh dispatches are comm-accounted: the returned callable wraps
    the jit so each call emits one ``comm`` event (primitive
    ``map_shards``, payload = the argument pytree's bytes, host-blocked
    wall = the enqueue time — dispatch is async, so this is the host
    cost, not device compute).  ``STARK_COMM_TELEMETRY=0`` returns the
    bare jit; the ``mesh=None`` fast path is NEVER wrapped (its
    bit/trace-identity contract is literal ``jax.jit``).
    """
    if mesh is None:
        return jax.jit(fn, donate_argnums=tuple(donate))
    if in_specs is None or out_specs is None:
        if axis is None:
            raise ValueError(
                "map_shards on a mesh needs either explicit in_specs/"
                "out_specs or a default `axis`"
            )
        spec = P(axis)
        if in_specs is None:
            import inspect

            try:
                params = list(inspect.signature(fn).parameters.values())
            except (TypeError, ValueError):
                params = None
            # only plain positional parameters WITHOUT defaults count —
            # *args/**kwargs make the arity unknowable and a defaulted
            # or keyword-only parameter makes it ambiguous (the caller
            # may or may not pass it); explicit in_specs resolves both
            if params is None or any(
                p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD,
                           p.KEYWORD_ONLY)
                or p.default is not p.empty
                for p in params
            ):
                raise ValueError(
                    "map_shards could not infer the arity of fn "
                    "(*args/**kwargs, defaulted, or keyword-only "
                    "parameters); pass in_specs explicitly"
                )
            in_specs = tuple(spec for _ in range(len(params)))
        if out_specs is None:
            out_specs = spec
    mapped = shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=check_vma,
    )
    if name is None:
        jitted = jax.jit(mapped, donate_argnums=tuple(donate))
    else:
        from ..platform import named_jit

        jitted = named_jit(mapped, name, donate_argnums=tuple(donate))
    if not comm_telemetry_enabled():
        return jitted
    site = _caller_site()
    if axis is not None and axis in mesh.axis_names:
        participants = int(mesh.shape[axis])
    else:
        participants = int(mesh.size)

    @functools.wraps(jitted)  # the program's name, and the jit behind it
    def _dispatch(*args):
        # deterministic hung-collective drill (watchdog / shard-deadman
        # chaos): a zero-cost no-op unless the site is armed
        faults.fail_point("primitives.collective_stall")
        # payload BEFORE the call: donated argument buffers are deleted
        # by the dispatch (metadata would survive, but don't rely on it)
        payload = predict_tree_bytes(args)
        t0 = time.perf_counter()
        out = jitted(*args)
        _record_comm(
            "map_shards", site=site, axis=axis, participants=participants,
            payload_bytes=payload // max(participants, 1),
            wire_bytes=payload,
            host_blocked_s=time.perf_counter() - t0,
        )
        return out

    return _dispatch


def mapped_axis_size(axis: Optional[str]):
    """STATIC shard count of a named mesh axis, from INSIDE a mapped
    function: ``lax.psum`` of a literal 1 constant-folds to the axis
    size and moves nothing on the wire (the repo-wide "static axis
    size" idiom, now with one implementation).  1 with no axis.  NOT
    comm-accounted — there is no communication to account."""
    if axis is None:
        return 1
    from jax import lax

    return lax.psum(1, axis)


def reduce_tree(tree: PyTree, axis=None, op: str = "sum"):
    """The reduce primitive, for use INSIDE a mapped function: combine
    every shard's value over the named mesh axis (``psum``/``pmax``/
    ``pmin``).  ``axis=None`` is the single-shard identity, so shared
    likelihood/statistics code runs unchanged under both layouts.

    ``axis`` may also be a SEQUENCE of axis names — a `DomainTree`
    hierarchy — in which case the reduction composes level by level,
    INNERMOST (last) first: a ``("region", "device")`` reduce runs the
    device-level collective inside each region, then the region-level
    collective across regions.  The result equals the flat reduce over
    all named axes (the ops are associative and commutative), but each
    level emits its OWN comm event with that level's participant count,
    so wire bytes within a domain and across domains are accounted
    separately.

    Comm-accounted at TRACE time (the call runs while the enclosing jit
    traces, once per compiled instantiation): wire bytes = leaf payload
    x axis size, host-blocked wall = the tracing cost of the collective.
    The identity path emits nothing — no axis, no communication."""
    if op not in _REDUCE_OPS:
        raise ValueError(f"unknown reduce op {op!r}; one of {_REDUCE_OPS}")
    if axis is None:
        return tree
    from jax import lax

    fn = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}[op]
    levels = list(axis) if isinstance(axis, (tuple, list)) else [axis]
    site = _caller_site()
    for ax in reversed(levels):
        if not comm_telemetry_enabled():
            tree = jax.tree.map(lambda x, a=ax: fn(x, a), tree)
            continue
        payload = predict_tree_bytes(tree)
        t0 = time.perf_counter()
        tree = jax.tree.map(lambda x, a=ax: fn(x, a), tree)
        _record_comm(
            "reduce_tree", site=site, axis=ax,
            participants=_static_axis_count(ax),
            payload_bytes=payload,
            wire_bytes=payload * _static_axis_count(ax),
            host_blocked_s=time.perf_counter() - t0,
        )
    return tree


def gather_axis(x: PyTree, axis: str, *, tiled: bool = False) -> PyTree:
    """In-program allgather over a named mesh axis (``lax.all_gather``),
    for use INSIDE a mapped function: every shard receives every shard's
    value, stacked along a new leading axis (``tiled=True``
    concatenates along the existing leading axis instead).  The only
    sanctioned ``lax.all_gather`` in the repo (tools/lint_collectives).

    Comm-accounted at trace time like `reduce_tree`; wire bytes = local
    payload x axis size (every shard's contribution reaches every
    shard)."""
    from jax import lax

    if not comm_telemetry_enabled():
        return jax.tree.map(lambda v: lax.all_gather(v, axis, tiled=tiled), x)
    t0 = time.perf_counter()
    out = jax.tree.map(lambda v: lax.all_gather(v, axis, tiled=tiled), x)
    payload = predict_tree_bytes(x)
    _record_comm(
        "gather_axis", site=_caller_site(), axis=axis,
        participants=_static_axis_count(axis),
        payload_bytes=payload,
        wire_bytes=payload * _static_axis_count(axis),
        host_blocked_s=time.perf_counter() - t0,
    )
    return out


def scan_shards(
    values: PyTree,
    axis: Optional[str],
    *,
    combine=None,
    reverse: bool = False,
    replicated: bool = False,
):
    """Ordered cross-shard scan — the DrJAX ordered-computation primitive
    (PAPERS.md) the sequence-parallel likelihoods stitch on.

    Shards of a named mesh axis are ORDERED (shard ``s`` holds the
    contiguous global rows [s·m, (s+1)·m)), and a sequential likelihood
    needs each shard's EXCLUSIVE carry over the shards before (after,
    for a reverse scan) it in that order.  Two modes:

    * **gather mode** (default): allgather every shard's per-shard
      contribution ``values`` into ``totals`` (stacked along a new
      leading axis, IN SHARD ORDER) and return ``combine(totals, mask)``
      where ``mask`` is the (P,) bool exclusive-scan mask selecting the
      shards strictly before this one (``reverse=True``: strictly
      after).  ``combine`` keeps the caller's exact reduction arithmetic
      (a masked logsumexp, a first-valid pick, a right-fill) so a
      migration onto this primitive is bit-identical by construction —
      the primitive owns the ORDER and the WIRE, the model owns the
      algebra.  ``axis=None`` is the single-shard identity: ``totals``
      is ``values[None]`` and the mask is all-False (no predecessors).
    * **replicated mode** (``replicated=True``): ``values`` is the FULL
      replicated sequence (every shard computed it identically from
      replicated params) and the primitive returns this shard's ordered
      contiguous slice along axis 0 — the zero-collective half of
      sequence parallelism (StochasticVolatility).  The sequence length
      must divide evenly by the shard count: ``dynamic_slice`` CLAMPS
      out-of-range starts, which would silently alias tail slices.

    Comm-accounted at trace time like its siblings: gather mode emits
    one ``scan_shards`` event per trace (wire bytes = contribution
    payload x axis size — the allgather's wire).  Replicated mode moves
    NOTHING (the input is already replicated) and, like
    `mapped_axis_size`, emits nothing — there is no communication to
    account."""
    from jax import lax

    if replicated:
        if combine is not None:
            raise ValueError(
                "scan_shards(replicated=True) slices a replicated "
                "sequence; combine= applies only to gather mode"
            )
        if axis is None:
            return values
        num = mapped_axis_size(axis)
        n = int(values.shape[0])
        if n % num:
            raise ValueError(
                f"scan_shards(replicated=True): sequence length {n} does "
                f"not divide over {num} shards (dynamic_slice would clamp "
                "and silently alias tail slices)"
            )
        m = n // num
        s = lax.axis_index(axis)
        return lax.dynamic_slice_in_dim(values, s * m, m)
    if combine is None:
        raise ValueError("scan_shards gather mode needs a combine= callable")
    if axis is None:
        totals = jax.tree.map(lambda v: jnp.asarray(v)[None], values)
        return combine(totals, jnp.zeros((1,), bool))
    s = lax.axis_index(axis)
    num = mapped_axis_size(axis)
    idx = jnp.arange(num)
    mask = (idx > s) if reverse else (idx < s)
    if not comm_telemetry_enabled():
        totals = jax.tree.map(lambda v: lax.all_gather(v, axis), values)
        return combine(totals, mask)
    t0 = time.perf_counter()
    totals = jax.tree.map(lambda v: lax.all_gather(v, axis), values)
    payload = predict_tree_bytes(values)
    _record_comm(
        "scan_shards", site=_caller_site(), axis=axis,
        participants=_static_axis_count(axis),
        payload_bytes=payload,
        wire_bytes=payload * _static_axis_count(axis),
        host_blocked_s=time.perf_counter() - t0,
    )
    return combine(totals, mask)


def _static_axis_count(axis: str) -> int:
    """`mapped_axis_size` coerced to a plain int for event fields — 0
    when the size is somehow not static (abstract axis), so the event
    still emits instead of raising mid-trace."""
    try:
        return int(mapped_axis_size(axis))
    except Exception:
        return 0


def broadcast(tree: PyTree, mesh: Optional[Mesh] = None) -> PyTree:
    """Replicate a host value to every device of ``mesh`` (no mesh: the
    identity).  Multi-host aware: each process holds the identical host
    value and contributes its addressable replicas (the
    ``make_array_from_callback`` placement `backends/sharded.py` used to
    hand-roll).

    Comm-accounted as ONE ``broadcast`` event (wire bytes = payload x
    device count — every device receives the full value); the internal
    placement does not double-count as a ``shard_put``."""
    if mesh is None:
        return tree
    specs = jax.tree.map(lambda _: P(), tree)
    if not comm_telemetry_enabled():
        return _shard_put_impl(tree, mesh, specs, from_host_replica=True)
    payload = predict_tree_bytes(tree)
    t0 = time.perf_counter()
    out = _shard_put_impl(tree, mesh, specs, from_host_replica=True)
    n = int(mesh.size)
    _record_comm(
        "broadcast", site=_caller_site(), axis=None, participants=n,
        payload_bytes=payload, wire_bytes=payload * n,
        host_blocked_s=time.perf_counter() - t0,
    )
    return out


def shard_put(
    tree: PyTree,
    mesh: Optional[Mesh],
    specs: Any,
    *,
    process_local: bool = False,
    from_host_replica: bool = False,
    home: Optional[Tuple[str, int]] = None,
) -> PyTree:
    """Place a pytree along per-leaf PartitionSpecs (``specs`` may be a
    single spec applied to every leaf, or a spec pytree).  No mesh: the
    identity.  Two multi-host flavors:

    * ``process_local=True`` — each process passes only ITS rows and jax
      glues one global array (``make_array_from_process_local_data``);
    * ``from_host_replica=True`` — every process holds the identical
      full host value (same-seed host computation) and contributes just
      its addressable shards (``make_array_from_callback``).

    ``home=(axis_name, index)`` PINS the placement to one failure
    domain: the value lands only on the sub-mesh slice at ``index``
    along the named `DomainTree` axis (e.g. ``("region", 0)`` keeps a
    region's process-local rows inside their home region instead of
    striping them across the whole mesh — a region loss then costs only
    that region's tenants).  ``specs`` must then partition over the
    REMAINING axes only, and the comm event's participant count is the
    sub-mesh's device count.

    Comm-accounted per call on a mesh (wire bytes = the full payload —
    each byte is placed once; per-participant payload = payload /
    devices); the identity path emits nothing."""
    if mesh is None:
        return tree
    if home is not None:
        mesh = _home_submesh(mesh, home)
    if isinstance(specs, P):
        specs = jax.tree.map(lambda _: specs, tree)
    if not comm_telemetry_enabled():
        return _shard_put_impl(
            tree, mesh, specs,
            process_local=process_local,
            from_host_replica=from_host_replica,
        )
    payload = predict_tree_bytes(tree)
    t0 = time.perf_counter()
    out = _shard_put_impl(
        tree, mesh, specs,
        process_local=process_local,
        from_host_replica=from_host_replica,
    )
    n = int(mesh.size)
    _record_comm(
        "shard_put", site=_caller_site(), axis=None, participants=n,
        payload_bytes=payload // max(n, 1), wire_bytes=payload,
        host_blocked_s=time.perf_counter() - t0,
    )
    return out


def _home_submesh(mesh: Mesh, home: Tuple[str, int]) -> Mesh:
    """The sub-mesh slice at ``home=(axis_name, index)`` — the home
    failure domain of a `shard_put` pinning.  The home axis is consumed
    (the slice is one coordinate thick), so the mesh must keep at least
    one other axis to partition over."""
    ax, idx = home
    names = list(mesh.axis_names)
    if ax not in names:
        raise ValueError(f"mesh {tuple(names)} has no {ax!r} axis to pin to")
    if len(names) < 2:
        raise ValueError(
            "home pinning needs at least one non-home mesh axis "
            f"(mesh has only {tuple(names)})"
        )
    k = names.index(ax)
    n = int(mesh.shape[ax])
    idx = int(idx)
    if not 0 <= idx < n:
        raise ValueError(f"home index {idx} outside axis {ax!r} of size {n}")
    sub = np.take(np.asarray(mesh.devices), idx, axis=k)
    return Mesh(sub, tuple(nm for nm in names if nm != ax))


def _shard_put_impl(
    tree: PyTree,
    mesh: Mesh,
    specs: Any,
    *,
    process_local: bool = False,
    from_host_replica: bool = False,
) -> PyTree:
    """The uninstrumented placement body `shard_put` and `broadcast`
    share (so a broadcast never double-counts as a shard_put).
    ``specs`` is already a per-leaf spec pytree here."""
    if process_local:
        return jax.tree.map(
            lambda x, spec: jax.make_array_from_process_local_data(
                NamedSharding(mesh, spec), np.asarray(x)
            ),
            tree,
            specs,
        )
    if from_host_replica and jax.process_count() > 1:

        def place(x, spec):
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, NamedSharding(mesh, spec), lambda idx: x[idx]
            )

        return jax.tree.map(place, tree, specs)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        tree,
        specs,
    )


def placed(x, mesh: Mesh, spec) -> bool:
    """True when ``x`` is a device array that already lies over ``mesh`` as
    ``spec`` says: `shard_put`'s ``device_put`` to an equivalent sharding
    hands such an array back as it is, so rows born on their chips are not
    copied (what `mesh.shard_data`'s span reports as ``moved_bytes``)."""
    return isinstance(x, jax.Array) and x.sharding.is_equivalent_to(
        NamedSharding(mesh, spec), x.ndim
    )


def gather_tree(tree: PyTree, *, tiled: bool = True) -> PyTree:
    """Materialize the GLOBAL host view of a (possibly device-sharded)
    pytree as numpy arrays — the view all host-side bookkeeping (gates,
    checkpoints, fault domains) runs on.  Single-process: ``np.asarray``
    already assembles every addressable shard.  Multi-process: each
    leaf is allgathered so every host returns the same full value (the
    `distributed.gather_draws` contract, generalized).

    ``tiled=False`` STACKS per-process values along a new leading axis
    instead of gluing shards of one global array — the
    ``process_allgather(tiled=False)`` per-rank-vote shape
    (`supervise`'s resume agreement); single-process it returns
    ``x[None]`` so rank-indexed consumers see the same (1, ...) layout.

    Comm-accounted per call: payload = the tree's host-view bytes, wire
    = payload x process count (every host receives the full value;
    single-process this is the device->host readback, and the
    host-blocked wall is the readback wall every block pays)."""
    # the other host-blocking collective dispatch the stall drill covers
    # (armed via STARK_FAILPOINTS; independent of the telemetry knob)
    faults.fail_point("primitives.collective_stall")
    if not comm_telemetry_enabled():
        return _gather_tree_impl(tree, tiled=tiled)
    t0 = time.perf_counter()
    out = _gather_tree_impl(tree, tiled=tiled)
    payload = predict_tree_bytes(out)
    n = int(jax.process_count())
    _record_comm(
        "gather_tree", site=_caller_site(), axis=None, participants=n,
        payload_bytes=payload, wire_bytes=payload * n,
        host_blocked_s=time.perf_counter() - t0,
    )
    return out


def _gather_tree_impl(tree: PyTree, *, tiled: bool) -> PyTree:
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return jax.tree.map(
            lambda x: np.asarray(
                multihost_utils.process_allgather(x, tiled=tiled)
            ),
            tree,
        )
    if tiled:
        return jax.tree.map(np.asarray, tree)
    return jax.tree.map(lambda x: np.asarray(x)[None], tree)


def run_over_chains(mesh: Mesh, vrun, *args):
    """shard_map a vmapped chain runner over the mesh "chains" axis and
    run it: every arg (and output) carries chains as its leading axis.
    Shared dispatch for the samplers that parallelize only over chains
    (SG-HMC, tempering) — a `map_shards` + `shard_put` composition."""
    if "chains" not in mesh.axis_names:
        raise ValueError("mesh must have a 'chains' axis")
    fn = map_shards(
        vrun,
        mesh=mesh,
        in_specs=tuple(P("chains") for _ in args),
        out_specs=P("chains"),
    )
    args = tuple(shard_put(a, mesh, P("chains")) for a in args)
    return jax.block_until_ready(fn(*args))
