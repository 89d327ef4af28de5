"""Live run-health HTTP exporter: ``/metrics``, ``/healthz``, ``/status``.

A stdlib-only (`http.server`) daemon thread that serves the in-process
metrics registry (`stark_tpu.metrics`) while a run is in flight — the live
counterpart to the post-hoc trace file.  **Off by default**: it starts
only when ``--status-port`` / ``STARK_STATUS_PORT`` asks for it, and with
the port unset nothing here is imported by the sampling path — no thread,
no registry, no listener (the NullTrace zero-cost contract).

Endpoints:

  * ``GET /metrics``  — Prometheus text exposition (0.0.4) of the
    registry: block/draw/restart counters, chain-health gauges, watchdog
    beat age + deadline, per-device ``memory_stats()`` sampled at block
    boundaries.  Counters are process-monotone: a supervised restart never
    resets them.
  * ``GET /healthz``  — 200 ``ok`` while the run is live; 503 with a JSON
    reason when the watchdog declared a stall or a supervised restart is
    in progress; recovers to 200 at the next attempt's ``run_start``;
    sticky 503 once the restart budget is exhausted.  The deadman logic
    lives in `metrics.RunHealth`, driven by the same trace events the
    supervisor emits.  **Degraded-fleet policy**: a fleet that loses
    problems (lane quarantines — ``problem_quarantined`` events) is a
    PER-TENANT loss, not process unhealth — /healthz stays 200, and the
    degradation is surfaced in ``/status``'s ``fleet`` sub-object
    (``degraded``, ``lost_problems``, ``last_quarantined``) and the
    ``*_fleet_degraded`` / ``*_fleet_problems_quarantined_total``
    metrics.  The same policy covers MESH loss: a fleet whose shard
    deadman (``STARK_SHARD_DEADLINE``) declared shards lost re-packed
    onto the survivors and kept serving — /healthz stays 200 and
    ``/status``'s ``fleet`` carries ``lost_shards`` /
    ``last_shard_lost`` (plus ``*_fleet_shards_lost_total``); 503 stays
    reserved for process-level unhealth (stall, restart in progress,
    restart budget exhausted).
  * ``GET /status``   — JSON snapshot: ``schema`` (contract version —
    `metrics.STATUS_SCHEMA`; consumers key on it before trusting the
    shape), ``uptime_s`` (exporter uptime), current phase, block index,
    ESS progress/forecast, attempt number, restart record, run metadata
    (model/kernel/chains + provenance), per-problem fleet state, and
    ``last_postmortem`` — the most recent flight-recorder bundle this
    process dumped (``{path, trigger, ts}``; null when none).  The
    ``health`` sub-object carries the last-seen chain diagnostics plus —
    since PR 15 — ``health.warnings``: the statistical-health
    observatory's active warnings (``stark_tpu.health`` taxonomy; latest
    occurrence per warning type, keyed by name, with severity /
    measured value / threshold / remediation hint; absent until a
    warning fires, cleared on a fresh ``run_start``).  Additive within
    the existing ``health`` key, so the schema version is unchanged.
    Since PR 16 the snapshot also carries ``comms`` — the mesh
    communication observatory's live rollup (``parallel.primitives``
    ``comm`` events): cumulative accounted collective ``calls`` /
    predicted ``wire_bytes`` / ``host_blocked_s``, the latest
    primitive, and — on STARK_FLEET_MESH runs — the latest block's
    straggler attribution (``straggler_shard``, ``straggler_ratio``,
    ``shards_timed``).  Empty ``{}`` under STARK_COMM_TELEMETRY=0 or
    on runs that never dispatch an accounted collective; additive, so
    the schema version is again unchanged.

  * ``/posterior/<id>/summary``, ``/posterior/<id>/predict``,
    ``/posterior/<id>/draws`` — the posterior READ plane
    (`stark_tpu.serving`), live once a `serving.PosteriorStore` is
    attached (``attach_serving`` or ``STARK_SERVE_ROOT``; 503 with a
    JSON reason otherwise).  GET summary returns the tenant's
    ``.summary.json`` sidecar (or an in-memory computed fallback); GET
    draws returns the last ``?n=`` draws off the zero-copy mmap; POST
    predict evaluates the batched posterior-predictive (body
    ``{"x": [[...]], "link": ...}``, or no ``x`` to serve the
    registered — possibly int8-packed — design).  Request accounting
    (``serve_request`` events) feeds the ``stark_serve_*`` metrics and
    ``/status``'s ``serving`` sub-object; see the README "Posterior
    serving" section for the full JSON contracts.

  * ``GET /jobs`` / ``GET /jobs/<job_id>`` — the tenant lineage
    observatory (`stark_tpu.lineage`): per-job rollups folded LIVE by
    the record annotator as events are emitted (no trace rescan).
    ``/jobs`` lists every job this process has observed, oldest first
    (``{"schema": INDEX_SCHEMA, "enabled": ..., "jobs": [...]}``);
    ``/jobs/<job_id>`` returns one record — lifecycle state, event
    counts, block/restart/shard-loss/checkpoint tallies, latest SLO
    burn fractions, convergence status, and serving hit counts — or
    404 for an unknown id.  With ``STARK_LINEAGE=0`` the index is
    never fed, so ``/jobs`` answers with an empty list and
    ``enabled: false``.

Probe contract: ``python -m stark_tpu status --json`` prints ONE
machine-parseable line ``{"endpoint", "code", "body"}`` for any of the
three endpoints (body parsed when the response was JSON).

The server is **process-scoped, not attempt-scoped**: `supervise` may
restart the run many times, the daemon (and the monotone counters behind
it) survives every attempt.  It observes the run through the telemetry
event-listener fan-out, so it works with ``--trace`` (file + live view)
or without (an in-memory `RunTrace(None)` bus is installed by the CLI
when only the port is given).

Probe from a shell::

    python -m stark_tpu status --port 8998              # /status, pretty
    python -m stark_tpu status --port 8998 --healthz    # exit 0/1 = 200/503
    curl -s localhost:8998/metrics | grep stark_draws_total
"""

from __future__ import annotations

import json
import logging
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from .metrics import MetricsRegistry, RunHealth, TraceCollector

log = logging.getLogger("stark_tpu.statusd")

__all__ = [
    "ROUTES",
    "SERVE_ROOT_ENV",
    "STATUS_PORT_ENV",
    "StatusServer",
    "get_server",
    "maybe_start_from_env",
    "start_status_server",
    "stop_status_server",
]

STATUS_PORT_ENV = "STARK_STATUS_PORT"

#: posterior read plane: when set, `maybe_start_from_env` attaches a
#: `serving.PosteriorStore` over this fleet draw-store root, enabling
#: the ``/posterior/*`` endpoints on the same daemon
SERVE_ROOT_ENV = "STARK_SERVE_ROOT"

#: the DECLARED endpoint contract: every route this daemon serves, in
#: the exact spelling the README endpoint table and the contract tests
#: must carry (tools/lint_endpoints.py closes the loop statically).
#: ``<id>`` segments are path parameters.
ROUTES = (
    "/metrics",
    "/healthz",
    "/status",
    "/posterior/<id>/summary",
    "/posterior/<id>/predict",
    "/posterior/<id>/draws",
    "/jobs",
    "/jobs/<job_id>",
)

#: bind address: loopback by default — the endpoints expose run metadata
#: (git SHA, toolchain versions, device inventory) with no auth, so
#: reaching them from another host is an explicit operator decision
#: (STARK_STATUS_HOST=0.0.0.0 for a real Prometheus scrape target)
STATUS_HOST_ENV = "STARK_STATUS_HOST"
DEFAULT_HOST = "127.0.0.1"

#: Prometheus text exposition content type
_METRICS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


class _Handler(BaseHTTPRequestHandler):
    """Request handler bound to one StatusServer via ``server.statusd``."""

    server_version = "stark-statusd/1"

    def _send(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, obj: Any) -> None:
        body = (json.dumps(obj, default=str) + "\n").encode()
        self._send(code, body, "application/json")

    def _posterior_route(self, path: str):
        """``/posterior/<id>/<verb>`` -> (problem_id, verb) or None."""
        parts = path.strip("/").split("/")
        if len(parts) == 3 and parts[0] == "posterior" and parts[1]:
            return parts[1], parts[2]
        return None

    def _query(self) -> Dict[str, str]:
        from urllib.parse import parse_qsl

        raw = self.path.split("?", 1)
        return dict(parse_qsl(raw[1])) if len(raw) == 2 else {}

    def do_GET(self) -> None:  # noqa: N802 — BaseHTTPRequestHandler API
        sd: "StatusServer" = self.server.statusd  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            if path == "/metrics":
                self._send(
                    200, sd.registry.render().encode(), _METRICS_CONTENT_TYPE
                )
            elif path == "/healthz":
                healthy, detail = sd.health.check()
                body = (
                    b"ok\n"
                    if healthy
                    else (json.dumps(detail) + "\n").encode()
                )
                self._send(
                    200 if healthy else 503,
                    body,
                    "text/plain; charset=utf-8"
                    if healthy
                    else "application/json",
                )
            elif path in ("/status", "/"):
                body = (
                    json.dumps(sd.collector.status(), indent=1, default=str)
                    + "\n"
                ).encode()
                self._send(200, body, "application/json")
            elif self._posterior_route(path) is not None:
                self._serve_posterior_get(sd, *self._posterior_route(path))
            elif path == "/jobs":
                # tenant lineage observatory (stark_tpu.lineage): the
                # live per-job rollups this process's annotator folded —
                # no trace rescan, oldest job first
                from . import lineage

                self._send_json(200, {
                    "schema": lineage.INDEX_SCHEMA,
                    "enabled": lineage.enabled(),
                    "jobs": lineage.GLOBAL_INDEX.jobs(),
                })
            elif path.startswith("/jobs/"):
                from . import lineage

                jid = path[len("/jobs/"):]
                rec = lineage.GLOBAL_INDEX.job(jid)
                if rec is None:
                    self._send_json(
                        404, {"error": f"unknown job {jid!r}"}
                    )
                else:
                    self._send_json(200, rec)
            else:
                self._send(404, b"not found\n", "text/plain; charset=utf-8")
        except Exception as e:  # noqa: BLE001 — a scrape must never kill the daemon
            try:
                self._send(
                    500,
                    f"internal error: {type(e).__name__}\n".encode(),
                    "text/plain; charset=utf-8",
                )
            except Exception:  # noqa: BLE001 — client already gone
                pass

    def _serve_posterior_get(
        self, sd: "StatusServer", pid: str, verb: str
    ) -> None:
        """GET half of the read plane: ``/posterior/<id>/summary`` (the
        sidecar or a computed fallback) and ``/posterior/<id>/draws``
        (the LAST ``n`` draws — ``?n=``, default 100, JSON rows read
        straight off the zero-copy mmap)."""
        store = sd.serving
        if store is None:
            self._send_json(
                503, {"error": "no posterior store attached "
                      f"(set {SERVE_ROOT_ENV} or attach_serving)"}
            )
            return
        try:
            if verb == "summary":
                self._send_json(200, store.summary(pid))
            elif verb == "draws":
                draws = store.draws(pid)
                try:
                    n = max(0, int(self._query().get("n", "100")))
                except ValueError:
                    n = 100
                tail = draws[max(0, draws.shape[0] - n):]
                self._send_json(200, {
                    "problem_id": pid,
                    "n_draws": int(draws.shape[0]),
                    "chains": int(draws.shape[1]),
                    "dim": int(draws.shape[2]),
                    "returned": int(tail.shape[0]),
                    "draws": tail.tolist(),
                })
            else:
                self._send_json(404, {"error": f"unknown verb {verb!r}"})
        except KeyError as e:
            self._send_json(404, {"error": str(e)})

    def do_POST(self) -> None:  # noqa: N802 — BaseHTTPRequestHandler API
        """``POST /posterior/<id>/predict`` — body
        ``{"x": [[...]], "link": "identity"|"logistic"}`` (``x`` omitted
        serves the tenant's registered — possibly packed — design);
        response: ``{problem_id, link, draws_used, mean, quantile_probs,
        quantiles, cache}`` from the batched evaluator."""
        sd: "StatusServer" = self.server.statusd  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            route = self._posterior_route(path)
            if route is None or route[1] != "predict":
                self._send_json(404, {"error": "not found"})
                return
            store = sd.serving
            if store is None:
                self._send_json(
                    503, {"error": "no posterior store attached "
                          f"(set {SERVE_ROOT_ENV} or attach_serving)"}
                )
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError):
                self._send_json(400, {"error": "malformed JSON body"})
                return
            from .serving import PredictRequest

            try:
                import numpy as np

                x = body.get("x")
                req = PredictRequest(
                    route[0],
                    None if x is None else np.asarray(x, np.float32),
                    link=body.get("link", "identity"),
                )
                out = store.predict([req])
                self._send_json(200, out[0])
            except KeyError as e:
                self._send_json(404, {"error": str(e)})
            except ValueError as e:
                self._send_json(400, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — a request must never kill the daemon
            try:
                self._send(
                    500,
                    f"internal error: {type(e).__name__}\n".encode(),
                    "text/plain; charset=utf-8",
                )
            except Exception:  # noqa: BLE001 — client already gone
                pass

    def log_message(self, fmt: str, *args: Any) -> None:
        # scrapes arrive every few seconds: route to the module logger at
        # DEBUG instead of BaseHTTPRequestHandler's bare stderr writes
        log.debug("%s %s", self.address_string(), fmt % args)


class StatusServer:
    """One daemon-thread HTTP server over a collector/registry/health
    triple.  ``start()`` binds and spawns the thread; ``port`` reflects
    the ACTUAL bound port (pass 0 for an ephemeral one — tests do)."""

    def __init__(
        self,
        port: int,
        *,
        host: str = DEFAULT_HOST,
        collector: Optional[TraceCollector] = None,
    ):
        self.collector = (
            collector if collector is not None else TraceCollector()
        )
        self.registry: MetricsRegistry = self.collector.registry
        self.health: RunHealth = self.collector.health
        self._requested = (host, int(port))
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        #: the attached posterior read plane (serving.PosteriorStore);
        #: None -> the /posterior/* endpoints answer 503
        self.serving: Optional[Any] = None

    def attach_serving(self, store: Any) -> "StatusServer":
        """Attach a `serving.PosteriorStore`, enabling ``/posterior/*``.

        The store is shared across handler threads (it locks
        internally); re-attaching replaces the previous plane."""
        self.serving = store
        return self

    @property
    def port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd else None

    def start(self) -> "StatusServer":
        if self._httpd is not None:
            raise RuntimeError("status server already started")
        self._httpd = ThreadingHTTPServer(self._requested, _Handler)
        self._httpd.daemon_threads = True
        self._httpd.statusd = self  # type: ignore[attr-defined]
        self.collector.install()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"stark-statusd-{self.port}",
            daemon=True,
        )
        self._thread.start()
        log.info(
            "status endpoints on :%d (/metrics /healthz /status)", self.port
        )
        return self

    def stop(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is not None:
            self.collector.uninstall()
            httpd.shutdown()
            httpd.server_close()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def __enter__(self) -> "StatusServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# process singleton: entry points call start_status_server once; a second
# call (e.g. bench.py under the CLI) reuses the running daemon instead of
# fighting over the port
_SERVER: Optional[StatusServer] = None
_SERVER_LOCK = threading.Lock()


def get_server() -> Optional[StatusServer]:
    return _SERVER


def start_status_server(
    port: int, *, host: Optional[str] = None
) -> StatusServer:
    """Start (or return the already-running) process status server.

    ``host`` default: ``STARK_STATUS_HOST`` if set, else loopback."""
    global _SERVER
    if host is None:
        host = os.environ.get(STATUS_HOST_ENV, "").strip() or DEFAULT_HOST
    with _SERVER_LOCK:
        if _SERVER is not None:
            return _SERVER
        _SERVER = StatusServer(port, host=host).start()
        return _SERVER


def stop_status_server() -> None:
    global _SERVER
    with _SERVER_LOCK:
        srv, _SERVER = _SERVER, None
    if srv is not None:
        srv.stop()


def resolve_port(cli_port: Optional[int] = None) -> Optional[int]:
    """The effective status port: CLI flag wins, then STARK_STATUS_PORT;
    None/unset/empty/invalid → no server (the default-off contract).

    ``STARK_STATUS_PORT=0`` DISABLES the exporter — the repo-wide
    ``=0 opts out`` env convention (STARK_PERF_LEDGER,
    STARK_STREAM_DIAG), and the opt-out a nested job needs when CI
    exports a port globally.  An explicit CLI ``--status-port 0`` still
    requests an ephemeral bind (a deliberate flag, not an inherited
    environment)."""
    if cli_port is not None:
        return cli_port
    raw = os.environ.get(STATUS_PORT_ENV, "").strip()
    if not raw or raw == "0":
        return None
    try:
        return int(raw)
    except ValueError:
        log.warning("ignoring non-integer %s=%r", STATUS_PORT_ENV, raw)
        return None


def maybe_start_from_env(
    cli_port: Optional[int] = None,
) -> Optional[StatusServer]:
    """Start the exporter iff a port was configured; None otherwise.

    Never raises into the caller: a bind failure (port taken) logs and
    returns None — observability must not kill the run it observes.
    """
    port = resolve_port(cli_port)
    if port is None:
        return None
    try:
        srv = start_status_server(port)
    except Exception as e:  # noqa: BLE001 — exporter startup is best-effort
        log.warning(
            "status server on port %s failed to start (%s: %s) — "
            "continuing without live endpoints",
            port, type(e).__name__, e,
        )
        return None
    serve_root = os.environ.get("STARK_SERVE_ROOT", "").strip()
    if serve_root and srv.serving is None:
        # posterior read plane over an existing fleet store root; a bad
        # root degrades to 503s on /posterior/*, never a failed start
        try:
            from .serving import PosteriorStore

            srv.attach_serving(PosteriorStore(serve_root))
        except Exception as e:  # noqa: BLE001 — attach is best-effort
            log.warning(
                "posterior store at %s=%r failed to attach (%s: %s)",
                SERVE_ROOT_ENV, serve_root, type(e).__name__, e,
            )
    return srv
