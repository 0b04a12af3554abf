"""REST server for the TF Serving HTTP API.

Reference equivalent: pkg/tfservingproxy/tfservingproxy.go:36-129 — the same
URL contract, kept bug-for-bug compatible on the *success-path* semantics
only (the reference's failure counter increments on every request,
tfservingproxy.go:62-66 — fixed here, SURVEY.md §7):

  - case-insensitive match of ``/v1/models/<name>[/versions/<version>]``
    (tfservingproxy.go:24);
  - no match       -> 404 ``{"Status": "Error", "Message": "Not found"}``;
  - missing version-> 400 ``{"Status": "Error", "Message": "Model version must be provided"}``
    (tfservingproxy.go:99-124).

Verb suffixes (``:predict`` etc.), GET status, and GET metadata are parsed
here and handed to the backend; the reference forwarded them opaquely to
TF Serving, which no longer exists.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import threading
import time

from aiohttp import web

from tfservingcache_tpu.cluster.status import STATUS_HEADER, STATUS_WANT_HEADER
from tfservingcache_tpu.protocol.backend import BackendError, RestResponse, ServingBackend
from tfservingcache_tpu.utils.accounting import LEDGER
from tfservingcache_tpu.utils.bring_up import ACCOUNT
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.logging import get_logger
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.tracing import (
    TRACER,
    parse_traceparent,
    remote_parent,
    serialize_span,
)

# response header carrying this node's completed span subtree back to the
# router that forwarded the request (see utils/tracing.serialize_span)
TRACE_SUBTREE_HEADER = "x-tpusc-trace"

log = get_logger("rest")

# reference regex (tfservingproxy.go:24) extended with the /labels/<label>
# alternative TF Serving's own REST API accepts — the reference proxies the
# URL through verbatim and TF Serving resolves the label, so label parity
# needs first-class parsing here
URL_RE = re.compile(
    r"^/v1/models/(?P<name>[^/]+?)"
    r"(/versions/(?P<version>[0-9]+)|/labels/(?P<label>[^/]+?))?$",
    re.I,
)

# "generate" is a tpusc extension verb (KV-cached autoregressive decoding);
# the reference protocol verbs are predict/classify/regress
VERBS = ("predict", "classify", "regress", "generate")


def _error_body(message: str) -> bytes:
    # exact reference shape (tfservingproxy.go:102-108)
    return json.dumps({"Status": "Error", "Message": message}).encode()


def parse_model_url(
    path: str,
) -> tuple[str, int | None, str | None, str | None] | None:
    """-> (model_name, version|None, verb|None, label|None), or None when
    unroutable.

    ``verb`` is ``predict``/``classify``/``regress``/``metadata`` or None
    (bare GET = status probe). ``version`` and ``label`` are mutually
    exclusive by the URL grammar.
    """
    verb: str | None = None
    if ":" in path:
        path, _, v = path.rpartition(":")
        if v.lower() not in VERBS:
            return None
        verb = v.lower()
    elif path.lower().endswith("/metadata"):
        path = path[: -len("/metadata")]
        verb = "metadata"
    m = URL_RE.match(path)
    if not m:
        return None
    version = m.group("version")
    return (
        m.group("name"),
        (int(version) if version is not None else None),
        verb,
        m.group("label"),
    )


class RestServingServer:
    def __init__(
        self,
        backend: ServingBackend,
        metrics: Metrics | None = None,
        require_version: bool = True,
        metrics_path: str | None = None,
        max_body_bytes: int = 256 << 20,
        metrics_scrape_targets: list[str] | None = None,
        metrics_sum_counters: bool = False,
    ) -> None:
        self.backend = backend
        self.metrics = metrics
        # The reference 400s when the URL has no version (tfservingproxy.go:112);
        # on the cache node the router always sends versioned URLs.
        self.require_version = require_version
        self.metrics_path = metrics_path
        # extra text-format exporters folded into /metrics (reference
        # MetricsHandler scrape-merge, pkg/taskhandler/metrics.go:16-53)
        self.metrics_scrape_targets = metrics_scrape_targets or []
        # series-level counter summing across merge sources (per-tenant
        # fleet aggregation; config metrics.scrape_sum_counters)
        self.metrics_sum_counters = bool(metrics_sum_counters)
        self.app = web.Application(client_max_size=max_body_bytes)
        self.app.router.add_route("*", "/{tail:.*}", self._dispatch)
        self._runner: web.AppRunner | None = None
        self.port: int | None = None
        # fleet status plane (cluster/status.py), attached post-construction
        # by CacheNode/Router when the exchange is on: the collector serves
        # GET /monitoring/status and the piggyback response header; the
        # FleetView (router's REST server only) serves /monitoring/cluster
        self.status_collector = None
        self.fleet = None
        self._profile_lock = threading.Lock()  # one JAX profile capture at a time
        self.profiler_base_dir = os.environ.get(
            "TPUSC_PROFILER_DIR", "/tmp/tpusc_profile"
        )

    async def _dispatch(self, request: web.Request) -> web.StreamResponse:
        path = request.path
        if self.metrics_path and path == self.metrics_path and self.metrics is not None:
            # mirror the tenant ledger into the tpusc_tenant_* families at
            # scrape time (delta-inc; no-op unless model_labels is on) so
            # the engine hot path never touches prometheus
            LEDGER.publish(self.metrics)
            body = self.metrics.render()
            if self.metrics_scrape_targets:
                from tfservingcache_tpu.utils.metrics import scrape_and_merge

                body = await scrape_and_merge(
                    body, self.metrics_scrape_targets, metrics=self.metrics,
                    sum_counters=self.metrics_sum_counters,
                )
            return web.Response(body=body, content_type="text/plain")
        if path == "/healthz":
            return web.json_response({"status": "ok"})
        if path == "/monitoring/traces":
            try:
                n = int(request.query.get("n", "50"))
                min_ms = (
                    float(request.query["min_ms"])
                    if "min_ms" in request.query else None
                )
            except ValueError:
                return web.json_response(
                    {"error": "n must be an integer and min_ms a number"}, status=400
                )
            # n<=0 means "none", not "everything" (negative slices would
            # truncate from the wrong end of the ring buffer)
            traces = TRACER.query(
                n=n,
                min_duration_s=min_ms / 1000.0 if min_ms is not None else None,
                trace_id=request.query.get("trace_id"),
            ) if n > 0 else []
            return web.json_response({"traces": traces})
        if path == "/monitoring/engine":
            try:
                n = int(request.query.get("n", "64"))
                reset = request.query.get("reset", "1").lower() in (
                    "1", "true", "yes", "on",
                )
            except ValueError:
                return web.json_response(
                    {"error": "n must be an integer"}, status=400
                )
            # reset-on-scrape watermarks: each GET reports the peak since the
            # previous GET and zeroes the marks; reset=0 peeks without
            # consuming (OBSERVABILITY.md documents the contract).
            # ?model=name@version restricts the per-model sections to one
            # tenant (unknown model -> empty sections, not 404: the filter
            # is a view, the resource exists)
            snap = RECORDER.snapshot(
                tail=max(0, n), reset_watermarks=reset,
                model=request.query.get("model"),
            )
            snap["dumps"] = RECORDER.list_dumps()
            # mesh topology stamp (ISSUE 20): engine numbers from a sharded
            # arena are unreadable without the mesh that shaped them — same
            # structural-stamp rule as kernel_active/platform in bench rows
            rt = getattr(
                getattr(self.backend, "manager", None), "runtime", None
            )
            topo_fn = getattr(rt, "mesh_topology", None)
            if topo_fn is not None:
                topo = topo_fn()
                if topo is not None:
                    snap["mesh"] = topo
            # the bring-up account (utils/bring_up.py): beside the recorder's
            # program and stage records, the device bytes the runtime answers
            # for and what the listeners themselves have cost (build events
            # seen, the seconds the booking ones took)
            account = snap["bring_up"]
            account["listener"] = {
                "events": ACCOUNT.events,
                "seconds": round(ACCOUNT.listener_s, 6)}
            owned_fn = getattr(rt, "owned_device_bytes", None)
            if owned_fn is not None:
                account["owned"] = owned_fn()
            memory_fn = getattr(rt, "program_memory", None)
            if memory_fn is not None and request.query.get(
                    "programs", "0").lower() in ("1", "true", "yes", "on"):
                # on demand only: each kept program is lowered and compiled
                # again for its memory_analysis() (seconds; off the event loop)
                account["program_memory"] = await asyncio.get_running_loop(
                ).run_in_executor(None, memory_fn)
            return web.json_response(snap)
        if path == "/monitoring/tenants":
            # per-tenant cost ledger (utils/accounting.py): ?top=k keeps the
            # k most expensive tenants by ?dim= (any DIMENSIONS name;
            # default dominant share), ?model=name@version filters to one
            # tenant (model_found marks a typo vs an idle tenant), and
            # ?reset=1 consumes the reset-on-scrape marks so each scrape
            # interval reads its own window (default peek, unlike
            # /monitoring/engine: cost integrals are primarily cumulative)
            try:
                top = int(request.query.get("top", "0"))
            except ValueError:
                return web.json_response(
                    {"error": "top must be an integer"}, status=400
                )
            reset = request.query.get("reset", "0").lower() in (
                "1", "true", "yes", "on",
            )
            return web.json_response(LEDGER.snapshot(
                top=max(0, top),
                dim=request.query.get("dim"),
                model=request.query.get("model"),
                reset=reset,
            ))
        if path == "/monitoring/status":
            if self.status_collector is None:
                return web.json_response(
                    {"error": "status exchange not enabled on this server"},
                    status=404,
                )
            return web.json_response(self.status_collector.collect().to_dict())
        if path == "/monitoring/cluster":
            if self.fleet is None:
                return web.json_response(
                    {"error": "no fleet view on this server (router only)"},
                    status=404,
                )
            return web.json_response(self.fleet.snapshot())
        if path == "/monitoring/profiler" and request.method == "POST":
            return await self._capture_profile(request)

        # model-API surface from here down: counted, timed, in-flight-gauged
        if self.metrics is not None:
            self.metrics.request_count.labels("rest").inc()
            self.metrics.requests_in_flight.labels("rest").inc()
        t0 = time.monotonic()
        response: web.Response | None = None
        sp = None
        verb_label = "invalid"
        try:
            response, sp, verb_label = await self._serve_model_request(request, path)
            return response
        finally:
            if self.metrics is not None:
                self.metrics.requests_in_flight.labels("rest").dec()
                outcome = "ok" if response is not None and response.status < 400 else "error"
                route = (sp.attrs.get("route") if sp is not None else None) or "local"
                self.metrics.request_duration.labels(
                    "rest", verb_label, outcome, route
                ).observe(time.monotonic() - t0)

    async def _serve_model_request(
        self, request: web.Request, path: str
    ) -> tuple[web.Response, object | None, str]:
        """-> (response, completed root span | None, verb label). The span is
        the request's root; its ``route`` attr (annotated by the backend) and
        duration feed the SLO histogram in the dispatcher above."""
        parsed = parse_model_url(path)
        if parsed is None:
            return self._fail(web.Response(
                status=404, body=_error_body("Not found"), content_type="application/json"
            )), None, "invalid"
        name, version, verb, label = parsed
        verb_label = verb or ("status" if request.method == "GET" else "invalid")
        if version is None and label is None and self.require_version:
            return self._fail(web.Response(
                status=400,
                body=_error_body("Model version must be provided"),
                content_type="application/json",
            )), None, verb_label
        body = await request.read()
        # inbound W3C context (router hop): the root span joins the caller's
        # trace instead of starting a fresh one
        remote_ctx = parse_traceparent(request.headers.get("traceparent"))
        sp = None
        streaming = False
        try:
            with remote_parent(remote_ctx), \
                    TRACER.span("rest", path=path, method=request.method) as sp:
                resp: RestResponse = await self.backend.handle_rest(
                    request.method, name, version, verb, body, label=label,
                    query=dict(request.query),
                )
                streaming = getattr(resp, "token_stream", None) is not None
                if streaming:
                    # streaming generate (ISSUE 19): the root stays open
                    # until the stream ended (EOF, error frame or client
                    # gone), and the stream's pool job starts from the drain
                    # below, inside it: pool_wait, ensure_servable/load and
                    # the engine's phase attrs sit on the one root whose
                    # duration is what the client saw
                    return await self._stream_rest(
                        request, resp, sp, remote_ctx
                    ), sp, verb_label
        except BackendError as e:
            if streaming:
                raise  # the status line has shipped: nothing left to answer
            response = self._fail(web.Response(
                status=e.http_status,
                body=json.dumps({"error": str(e)}).encode(),
                content_type="application/json",
            ))
        except Exception as e:  # noqa: BLE001
            if streaming:
                raise
            log.exception("unhandled REST error for %s", path)
            response = self._fail(web.Response(
                status=500,
                body=json.dumps({"error": f"{type(e).__name__}: {e}"}).encode(),
                content_type="application/json",
            ))
        else:
            if resp.status >= 400 and self.metrics is not None:
                self.metrics.request_failures.labels("rest").inc()
            response = web.Response(
                status=resp.status,
                body=resp.body,
                content_type=resp.content_type,
                headers=resp.headers,
            )
        if remote_ctx is not None and sp is not None:
            # the caller is a router stitching a distributed trace: ship our
            # completed subtree back inline (span closed above, duration set)
            response.headers[TRACE_SUBTREE_HEADER] = serialize_span(sp)
        if (
            self.status_collector is not None
            and request.headers.get(STATUS_WANT_HEADER)
        ):
            # routed hop from a status-exchanging router: piggyback this
            # node's (cached, byte-capped) status on the response — errors
            # included; a failing response still proves the peer is up
            blob = self.status_collector.encoded()
            if blob:
                response.headers[STATUS_HEADER] = blob
        return response, sp, verb_label

    async def _stream_rest(
        self, request: web.Request, resp: RestResponse, sp, remote_ctx
    ) -> web.StreamResponse:
        """Drain a backend ``token_stream`` over chunked transfer (SSE).

        The 200 + headers are committed at ``prepare()`` — before the first
        token exists — which is why the backend front-loads every validation
        before returning a streaming response. A client disconnect stops the
        drain without error: the generate itself keeps finishing in the
        backend's pool."""
        headers = dict(resp.headers)
        headers["Content-Type"] = resp.content_type
        stream = web.StreamResponse(status=resp.status, headers=headers)
        if remote_ctx is not None and sp is not None:
            # headers ship at prepare(), so the piggybacked subtree is the
            # root as it stands NOW (still open; the whole stream is in
            # /monitoring/traces once it ended)
            sp.duration_s = time.monotonic() - sp.t0
            stream.headers[TRACE_SUBTREE_HEADER] = serialize_span(sp)
        if (
            self.status_collector is not None
            and request.headers.get(STATUS_WANT_HEADER)
        ):
            blob = self.status_collector.encoded()
            if blob:
                stream.headers[STATUS_HEADER] = blob
        await stream.prepare(request)
        try:
            async for frame in resp.token_stream:
                await stream.write(frame)
            await stream.write_eof()
        except (ConnectionResetError, ConnectionError):
            log.info("generate stream client disconnected mid-stream")
        finally:
            aclose = getattr(resp.token_stream, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:  # noqa: BLE001 - already answered/gone
                    pass
        return stream

    async def _capture_profile(self, request: web.Request) -> web.Response:
        """Capture a JAX/XLA device profile for ``duration_s`` into ``dir``
        (TensorBoard-loadable). The reference exposes nothing comparable
        (SURVEY.md §5 tracing: none)."""
        try:
            duration_s = min(float(request.query.get("duration_s", "2")), 60.0)
        except ValueError:
            return web.json_response({"error": "duration_s must be a number"}, status=400)
        # Captures are confined under a fixed base dir; the client picks only
        # a simple label — never a path — so the unauthenticated serving port
        # can't be used to write profile trees to arbitrary locations.
        label = request.query.get("label", "default")
        if not re.fullmatch(r"[A-Za-z0-9._-]{1,64}", label) or label.startswith("."):
            return web.json_response(
                {"error": "label must be [A-Za-z0-9._-]{1,64} and not start with '.'"},
                status=400,
            )
        log_dir = os.path.join(self.profiler_base_dir, label)
        if not self._profile_lock.acquire(blocking=False):
            return web.json_response({"error": "profile capture in progress"}, status=409)
        try:
            import jax

            jax.profiler.start_trace(log_dir)
            try:
                await asyncio.sleep(duration_s)
            finally:
                # stop even on client-disconnect cancellation: a dangling
                # global profiler would fail every future start_trace
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": f"{type(e).__name__}: {e}"}, status=500)
        finally:
            self._profile_lock.release()
        return web.json_response({"status": "ok", "dir": log_dir, "duration_s": duration_s})

    def _fail(self, response: web.Response) -> web.Response:
        if self.metrics is not None:
            self.metrics.request_failures.labels("rest").inc()
        return response

    async def start(self, port: int, host: str = "0.0.0.0") -> int:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # resolves port 0
        log.info("REST server listening on %s:%d", host, self.port)
        return self.port

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
