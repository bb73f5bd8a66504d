"""The asyncio network front door over :class:`EnsembleServer`.

One :class:`Gateway` owns three concerns, each on its own thread so the
compiled serving loop never waits on a socket:

* the **HTTP/WebSocket front end** — an aiohttp application on a
  private asyncio event loop (``jaxstream-gateway-http`` thread):
  ``POST /v1/requests`` admits a scenario request and streams its
  per-segment progress + final result back as NDJSON on the same
  connection; ``GET /v1/ws`` speaks the identical events over a
  WebSocket (one in-flight request per connection); ``/v1/health``,
  ``/v1/ready`` and ``/v1/stats`` expose liveness, admission readiness
  and the serving/occupancy/autoscale telemetry.
* the **serving thread** (``jaxstream-gateway-serve``) — runs
  :meth:`EnsembleServer.serve_forever`: pack → masked segments →
  refill, forever, with the autoscale tick evaluated at segment
  boundaries.
* the **result writer** — the server's own background writer thread,
  unchanged; the gateway only subscribes to its ``on_result`` callback.

**One writer per connection** (docs/DESIGN.md "Gateway"): every
connection's events flow through a per-request ``asyncio.Queue``; the
handler coroutine that owns the connection is the ONLY code that
writes to its transport.  Server threads never touch a socket — they
enqueue events with ``loop.call_soon_threadsafe``, which preserves
cross-thread call order, and the server emits a request's segment
events strictly before queueing its finalization, so a stream can
never see ``result`` before its last ``segment``.

**Typed overload**: admission failures map to fixed statuses
(:data:`..gateway.protocol.ERROR_STATUS`) — ``QueueFull`` -> 429,
draining / ``AdmissionRefused`` -> 503 — so shedding under saturation
is a tested contract, not an accident.  Admission control itself stays
in ``jaxstream.serve`` (the queue bound and the health-event budget);
the gateway only *translates* refusals, which is what keeps a direct
``EnsembleServer`` submission and a gateway submission behaviorally
identical (the byte-parity satellite).

**Graceful drain**: :meth:`begin_drain` stops admissions instantly
(new submits get 503 ``draining``); in-flight members run to their own
final step, their streams complete normally, sinks flush, and nothing
is re-queued.  ``close()`` drains by default; ``scripts/gateway.py``
wires SIGTERM to it.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Dict, Optional

from ..config import Config, load_config
from ..obs import flight
from ..obs import trace as obs_trace
from ..obs.registry import CONTENT_TYPE as _PROM_CONTENT_TYPE
from ..obs.sink import TelemetrySink, run_manifest
from ..utils import jax_compat
from ..serve.queue import AdmissionRefused, QueueFull, ServerDraining
from ..serve.request import RequestResult, ScenarioRequest
from ..serve.server import EnsembleServer
from ..utils.logging import get_logger
from . import protocol

__all__ = ["Gateway", "GATEWAY_HTTP_THREAD_NAME",
           "GATEWAY_SERVE_THREAD_NAME"]

log = get_logger(__name__)

GATEWAY_HTTP_THREAD_NAME = "jaxstream-gateway-http"
GATEWAY_SERVE_THREAD_NAME = "jaxstream-gateway-serve"

#: Sentinel closing every live stream when the loop is torn down
#: without a result (hard shutdown).
_SHUTDOWN_EVENT = protocol.error_event(
    "shutdown", "gateway shut down before the request completed")


def _require_aiohttp():
    try:
        from aiohttp import web  # noqa: F401

        return web
    except Exception as e:  # pragma: no cover - image always has it
        raise RuntimeError(
            "the network gateway needs aiohttp (HTTP/WebSocket front "
            "end); it is unavailable in this environment: "
            f"{type(e).__name__}: {e}") from e


class Gateway:
    """Asyncio HTTP/WebSocket front end over one :class:`EnsembleServer`.

    ``config`` is the standard config surface (the server's own
    ``serve:`` block included).  ``host`` must stay loopback for tests
    (check_tiers rule 9).  ``port=0`` binds an ephemeral port,
    published as :attr:`port` once :meth:`start` returns.

    ``autoscale`` is an optional callable ``tick(server)`` evaluated by
    the serving loop at segment boundaries — the
    :class:`jaxstream.loadgen.autoscale.AutoscaleController` protocol.
    ``sink`` names a JSONL telemetry file for per-request ``gateway``
    records (admissions, sheds, completions); autoscale resize events
    land in the *server's* sink (``serve.sink``) because the resize
    happens there.

    Use as a context manager, or call :meth:`close` when done.
    """

    def __init__(self, config=None, *, host: str = "127.0.0.1",
                 port: int = 0, autoscale=None, warm: bool = True,
                 sink: str = "", idle_wait: float = 0.005,
                 profile_dir: str = ""):
        _require_aiohttp()
        self.config: Config = load_config(config)
        self._host = host
        self._requested_port = int(port)
        self.port: Optional[int] = None
        self._idle_wait = float(idle_wait)
        self._autoscale = autoscale
        #: Round 17: on-demand profiler capture (``POST /v1/profile``)
        #: writes ``jax.profiler`` traces under this directory; ''
        #: disables the endpoint with a typed 501.
        self._profile_dir = profile_dir
        self._profiling = False
        self._profile_lock = threading.Lock()
        self.server = EnsembleServer(self.config,
                                     on_result=self._on_result,
                                     on_segment=self._on_segments)
        #: The server's scrapeable registry (``GET /v1/metrics``); the
        #: gateway adds its own shed counters to the same surface.
        self.metrics = self.server.metrics
        self._trace_on = bool(self.config.serve.trace)
        if warm:
            self.server.warmup()
        if autoscale is not None:
            autoscale.attach(self.server)
        #: compile count after warmup — the zero-steady-state-recompile
        #: assertion surface for the whole gateway (resizes included).
        self.warm_compiles = self.server.compile_count()
        self.stats = {"submitted": 0, "completed": 0, "evicted": 0,
                      "shed_queue_full": 0, "shed_draining": 0,
                      "shed_admission": 0, "bad_requests": 0,
                      "ws_connections": 0}
        self._streams: Dict[str, asyncio.Queue] = {}
        self._streams_lock = threading.Lock()
        self._sink = None
        self._sink_lock = threading.Lock()
        if sink:
            self._sink = TelemetrySink(sink, run_manifest(config={
                "gateway": True, "host": host,
                "grid_n": self.config.grid.n,
                "buckets": list(self.server.buckets),
                "queue_capacity": self.config.serve.queue_capacity,
            }))
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._loop_stop: Optional[asyncio.Event] = None
        self._ready = threading.Event()
        self._boot_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._http_thread: Optional[threading.Thread] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._started = False
        self._closed = False
        self._t0 = time.perf_counter()

    # ---------------------------------------------------------- lifecycle
    def start(self, serve: bool = True) -> "Gateway":
        """Bind the HTTP endpoint (and start the serving loop).

        ``serve=False`` binds the front end without draining the queue
        — the deterministic way to test admission backpressure (the
        queue fills; nothing competes with the 429 contract).
        """
        if self._started:
            raise RuntimeError("Gateway.start() called twice")
        self._started = True
        self._http_thread = threading.Thread(
            target=self._run_http, name=GATEWAY_HTTP_THREAD_NAME,
            daemon=True)
        self._http_thread.start()
        if not self._ready.wait(timeout=60):
            raise RuntimeError("gateway HTTP endpoint failed to bind "
                               "within 60s")
        if self._boot_error is not None:
            raise RuntimeError(
                "gateway HTTP endpoint failed to start"
            ) from self._boot_error
        if serve:
            self._serve_thread = threading.Thread(
                target=self._run_serve, name=GATEWAY_SERVE_THREAD_NAME,
                daemon=True)
            self._serve_thread.start()
        return self

    def __enter__(self):
        if not self._started:
            self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def url(self) -> str:
        return f"http://{self._host}:{self.port}"

    @property
    def draining(self) -> bool:
        return self.server.draining

    def begin_drain(self) -> None:
        """Stop admissions NOW (new submits -> 503 ``draining``); the
        serving loop keeps running until every already-admitted request
        reaches its own final step, then exits."""
        self.server.begin_drain()

    def drain(self, timeout: Optional[float] = 120) -> None:
        """:meth:`begin_drain`, then wait for in-flight work to finish
        and the result writer + sinks to flush."""
        self.begin_drain()
        if self._serve_thread is not None:
            self._serve_thread.join(timeout)
            if self._serve_thread.is_alive():
                raise RuntimeError(
                    f"gateway drain did not complete within {timeout}s")

    def close(self, drain: bool = True) -> None:
        """Drain (by default), stop the serving loop, tear down the
        HTTP endpoint, close the server and the gateway sink."""
        if self._closed:
            return
        self._closed = True
        try:
            if drain and self._serve_thread is not None:
                self.drain()
        finally:
            self._stop.set()
            if self._serve_thread is not None:
                self._serve_thread.join(30)
            # Terminate any stream still waiting (hard shutdown).
            with self._streams_lock:
                pending = list(self._streams)
            for rid in pending:
                self._post(rid, dict(_SHUTDOWN_EVENT, id=rid))
            if self._loop is not None and self._loop.is_running():
                self._loop.call_soon_threadsafe(self._loop_stop.set)
            if self._http_thread is not None:
                self._http_thread.join(30)
            self.server.close()
            if self._sink is not None:
                self._sink.close()

    # ------------------------------------------------------ event plumbing
    def _post(self, rid: str, event: dict) -> None:
        """Enqueue one event onto a request's stream, from any thread."""
        with self._streams_lock:
            q = self._streams.get(rid)
        loop = self._loop
        if q is None or loop is None or not loop.is_running():
            return
        loop.call_soon_threadsafe(q.put_nowait, event)

    def _on_segments(self, events) -> None:
        """Serving thread: the server's per-segment progress events."""
        for ev in events:
            self._post(ev["id"], protocol.segment_event(ev))

    def _on_result(self, res: RequestResult) -> None:
        """Writer thread: a request reached its final state."""
        self.stats["completed" if res.ok else "evicted"] += 1
        rec = {"kind": "gateway", "id": res.id, "ic": res.ic,
               "status": res.status,
               "latency_s": round(res.latency_s, 6),
               "steps_run": res.steps_run,
               "nsteps": res.nsteps}
        if self._trace_on:
            tid = obs_trace.trace_id_for(res.id)
            rec.update(trace_id=tid,
                       span_id=obs_trace.root_span_id(tid),
                       parent_id=None)
        self._record(rec)
        # Encode (ascontiguousarray + tobytes + base64 per field) only
        # when a connection is still subscribed: this runs on the
        # writer thread whose job is overlapping d2h with the next
        # segment, and a disconnected client must not slow live ones.
        with self._streams_lock:
            subscribed = res.id in self._streams
        if subscribed:
            t_eg = time.perf_counter()
            self._post(res.id, protocol.result_event(res))
            if self._trace_on:
                # Stream egress: result encode + handoff to the
                # connection's writer coroutine.  Sits just past the
                # root interval (the result was already 'ready') —
                # part of the span-sum epsilon, by design.
                tid = obs_trace.trace_id_for(res.id)
                self._record({
                    "kind": "span", "trace_id": tid,
                    "span_id": obs_trace.span_id_for(
                        tid, obs_trace.GATEWAY_EGRESS, 0),
                    "parent_id": obs_trace.root_span_id(tid),
                    "id": res.id, "name": obs_trace.GATEWAY_EGRESS,
                    "seq": 0,
                    "start_s": round(res.latency_s, 6),
                    "duration_s": round(
                        time.perf_counter() - t_eg, 6)})

    def _record(self, rec: dict) -> None:
        if self._sink is None:
            return
        with self._sink_lock:
            try:
                self._sink.write(rec)
            except Exception as e:  # telemetry must never kill serving
                log.warning("gateway sink write failed (%s: %s)",
                            type(e).__name__, e)

    # ---------------------------------------------------------- admission
    def submit(self, req: ScenarioRequest) -> None:
        """Admit one request (the network handlers' shared path).

        Raises the typed serve exceptions; the HTTP/WS layers translate
        them through :data:`protocol.ERROR_STATUS`.
        """
        t = self._serve_thread
        if t is not None and not t.is_alive() and not self._closed:
            # A dead serving loop must refuse traffic, not accept
            # requests that can never run (untyped client hangs).
            raise AdmissionRefused(
                f"gateway refused {req.id!r}: the serving loop has "
                "stopped; this deployment cannot serve new traffic")
        self.server.submit(req)
        self.stats["submitted"] += 1

    def _shed(self, req_id: str, code: str, message: str,
              started_at: Optional[float] = None) -> dict:
        key = protocol.SHED_STATUS.get(code)
        if key is not None:
            flight.record("gateway.shed", id=req_id, code=code)
            self.stats[key] += 1
            self.metrics.counter_inc("jaxstream_requests_shed_total",
                                     status=key)
            rec = {"kind": "gateway", "id": req_id, "ic": "",
                   "status": key, "latency_s": 0.0, "error": code}
            if self._trace_on:
                tid = obs_trace.trace_id_for(req_id)
                rec.update(trace_id=tid,
                           span_id=obs_trace.root_span_id(tid),
                           parent_id=None)
            self._record(rec)
            if self._trace_on:
                # Typed sheds carry a terminal root span: a trace
                # query answers 'what happened to request X' even when
                # the answer is 'the gateway refused it'.
                self._record(obs_trace.terminal_span(
                    req_id, key,
                    duration_s=(time.perf_counter() - started_at
                                if started_at is not None else 0.0)))
        return protocol.error_event(code, message, rid=req_id)

    # --------------------------------------------------------- HTTP layer
    def _run_http(self) -> None:
        try:
            asyncio.run(self._amain())
        except BaseException as e:  # pragma: no cover - boot failures
            self._boot_error = e
            self._ready.set()

    async def _amain(self) -> None:
        from aiohttp import web

        self._loop = asyncio.get_running_loop()
        self._loop_stop = asyncio.Event()
        app = web.Application()
        app.router.add_post("/v1/requests", self._handle_submit)
        app.router.add_get("/v1/ws", self._handle_ws)
        app.router.add_get("/v1/health", self._handle_health)
        app.router.add_get("/v1/ready", self._handle_ready)
        app.router.add_get("/v1/stats", self._handle_stats)
        app.router.add_get("/v1/metrics", self._handle_metrics)
        app.router.add_post("/v1/profile", self._handle_profile)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, self._host, self._requested_port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        self._ready.set()
        log.info("gateway: listening on %s", self.url)
        try:
            await self._loop_stop.wait()
        finally:
            await runner.cleanup()

    def _run_serve(self) -> None:
        try:
            self.server.serve_forever(stop=self._stop,
                                      idle_wait=self._idle_wait,
                                      tick=self._autoscale)
        except BaseException:
            log.exception("gateway serving loop died")
        finally:
            # Once this loop exits no segment or result can ever
            # arrive (serve_forever flushed the writer on its way
            # out): terminate any stream still waiting so its client
            # gets a typed error, not a hang.  After a normal drain
            # the result events are already queued ahead of this one,
            # so a completed stream is unaffected.
            with self._streams_lock:
                pending = list(self._streams)
            for rid in pending:
                self._post(rid, protocol.error_event(
                    "internal", "serving loop stopped before the "
                    "request completed", rid=rid))

    def _json(self, payload: dict, status: int = 200):
        from aiohttp import web

        return web.json_response(payload, status=status)

    def _admit_or_error(self, body, started_at: Optional[float] = None):
        """Parse + admit; returns (req, None) or (None, (event, status)).

        ``started_at`` is the connection handler's ingress stamp
        (request body in hand) — the start of the ``gateway.ingress``
        span and the shed terminal span's duration anchor.
        """
        t_in0 = time.perf_counter() if started_at is None else started_at
        try:
            req = protocol.request_from_json(body)
        except ValueError as e:
            self.stats["bad_requests"] += 1
            return None, (protocol.error_event("bad_request", str(e)),
                          400)
        with self._streams_lock:
            if req.id in self._streams:
                self.stats["bad_requests"] += 1
                return None, (protocol.error_event(
                    "duplicate_id",
                    f"request id {req.id!r} is already in flight on "
                    "this gateway", rid=req.id), 409)
            self._streams[req.id] = asyncio.Queue()
        try:
            self.submit(req)
        except QueueFull as e:
            self._drop_stream(req.id)
            return None, (self._shed(req.id, "queue_full", str(e),
                                     t_in0), 429)
        except ServerDraining as e:
            self._drop_stream(req.id)
            return None, (self._shed(req.id, "draining", str(e),
                                     t_in0), 503)
        except AdmissionRefused as e:
            self._drop_stream(req.id)
            return None, (self._shed(req.id, "admission_refused",
                                     str(e), t_in0), 503)
        except ValueError as e:
            # Deployment-level request validation (round 18: an
            # ic 'array' state whose shape/dtype does not match the
            # serving grid) — a caller bug, typed 400 like the codec's
            # own rejections, never an untyped 500.
            self._drop_stream(req.id)
            self.stats["bad_requests"] += 1
            return None, (protocol.error_event(
                "bad_request", str(e), rid=req.id), 400)
        except Exception as e:
            # Anything unexpected (e.g. the server closed under the
            # still-bound endpoint) must not leak the stream entry —
            # a leaked id turns every retry into a 409.
            self._drop_stream(req.id)
            log.warning("gateway: submit of %r failed (%s: %s)",
                        req.id, type(e).__name__, e)
            return None, (protocol.error_event(
                "internal", f"{type(e).__name__}: {e}", rid=req.id),
                500)
        if self._trace_on:
            # gateway.ingress: decode + admission, parented to the
            # root the server's trace will emit.  start_s is relative
            # to the root start (submitted_wall — same perf_counter
            # clock), so it renders just LEFT of the root interval.
            tid = obs_trace.trace_id_for(req.id)
            t_adm = time.perf_counter()
            self._record({
                "kind": "span", "trace_id": tid,
                "span_id": obs_trace.span_id_for(
                    tid, obs_trace.GATEWAY_INGRESS, 0),
                "parent_id": obs_trace.root_span_id(tid),
                "id": req.id, "name": obs_trace.GATEWAY_INGRESS,
                "seq": 0,
                "start_s": round(t_in0 - req.submitted_wall, 6),
                "duration_s": round(t_adm - t_in0, 6)})
        return req, None

    def _drop_stream(self, rid: str) -> None:
        with self._streams_lock:
            self._streams.pop(rid, None)

    async def _handle_submit(self, request):
        """POST /v1/requests: admit, then stream NDJSON events until the
        final result.  This coroutine is the connection's one writer."""
        from aiohttp import web

        t_in0 = time.perf_counter()
        try:
            body = await request.json()
        except Exception as e:
            self.stats["bad_requests"] += 1
            return self._json(protocol.error_event(
                "bad_request", f"body is not JSON: {e}"), status=400)
        req, err = self._admit_or_error(body, started_at=t_in0)
        if err is not None:
            return self._json(err[0], status=err[1])
        with self._streams_lock:
            q = self._streams[req.id]
        resp = web.StreamResponse()
        resp.content_type = "application/x-ndjson"
        try:
            # prepare() can raise on an already-gone client; it must
            # sit inside this try or the stream entry leaks and the id
            # answers 409 forever.
            await resp.prepare(request)
            await self._write_nd(resp, protocol.accepted_event(req.id))
            while True:
                ev = await q.get()
                await self._write_nd(resp, ev)
                if ev["event"] in ("result", "error"):
                    break
            await resp.write_eof()
        finally:
            self._drop_stream(req.id)
        return resp

    @staticmethod
    async def _write_nd(resp, ev: dict) -> None:
        await resp.write((json.dumps(ev) + "\n").encode("utf-8"))

    async def _handle_ws(self, request):
        """GET /v1/ws: the same protocol over a WebSocket.  One
        in-flight request per connection at a time (the next submission
        is read only after the previous stream's final event) — the
        same one-writer invariant, with the connection's reader loop as
        the single writer."""
        from aiohttp import web

        ws = web.WebSocketResponse()
        await ws.prepare(request)
        self.stats["ws_connections"] += 1
        async for msg in ws:
            if msg.type != web.WSMsgType.TEXT:
                break
            t_in0 = time.perf_counter()
            try:
                body = json.loads(msg.data)
            except json.JSONDecodeError as e:
                await ws.send_json(protocol.error_event(
                    "bad_request", f"message is not JSON: {e}"))
                continue
            req, err = self._admit_or_error(body, started_at=t_in0)
            if err is not None:
                await ws.send_json(err[0])
                continue
            with self._streams_lock:
                q = self._streams[req.id]
            try:
                await ws.send_json(protocol.accepted_event(req.id))
                while True:
                    ev = await q.get()
                    await ws.send_json(ev)
                    if ev["event"] in ("result", "error"):
                        break
            finally:
                self._drop_stream(req.id)
        return ws

    async def _handle_health(self, request):
        """Liveness: the process is up and the serving thread (when
        started) has not died."""
        serving = (self._serve_thread is not None
                   and self._serve_thread.is_alive())
        ok = self._serve_thread is None or serving
        return self._json({
            "status": "ok" if ok else "serving_thread_dead",
            "serving_thread_alive": serving,
            "uptime_s": round(time.perf_counter() - self._t0, 3),
        }, status=200 if ok else 503)

    async def _handle_ready(self, request):
        """Readiness: would a submission be admitted right now?  503
        with the refusal reasons otherwise.  The admission reasons
        come from :meth:`EnsembleServer.refusal_reasons` — the SAME
        predicate ``submit`` enforces, so the probe cannot diverge
        from admission control; the gateway adds only its own
        serving-thread liveness."""
        srv = self.server
        reasons = srv.refusal_reasons()
        if (self._serve_thread is not None
                and not self._serve_thread.is_alive()):
            reasons.append("serving_thread_dead")
        return self._json(
            {"ready": not reasons, "reasons": reasons,
             "queue_depth": len(srv.queue),
             "queue_capacity": srv.queue.capacity},
            status=200 if not reasons else 503)

    async def _handle_stats(self, request):
        """Serving/occupancy/autoscale telemetry for operators and the
        loadgen harness's closed loop."""
        return self._json(self.snapshot())

    async def _handle_metrics(self, request):
        """GET /v1/metrics: Prometheus text exposition of the server's
        registry (jaxstream.obs.registry) — counters by typed status,
        queue/occupancy/bucket-cap/per-chip gauges, latency/wall/
        host-wait histograms.  Snapshot-on-scrape: the render copies
        the registry under its creation lock and formats outside it,
        so a slow scrape never blocks a segment boundary."""
        from aiohttp import web

        return web.Response(text=self.metrics.render(),
                            headers={"Content-Type":
                                     _PROM_CONTENT_TYPE})

    async def _handle_profile(self, request):
        """POST /v1/profile: start/stop an on-demand ``jax.profiler``
        trace capture into the gateway's ``profile_dir``.

        Body: ``{"action": "start"|"stop"}``.  Typed failures: 501
        ``profiler_unavailable`` when the jax build has no profiler or
        the gateway was started without ``profile_dir``; 409
        ``profile_conflict`` on start-while-running / stop-while-idle.
        The capture covers whatever the serving loop runs between the
        two calls — the compiled segments carry ``serve.segment``
        named-scope annotations, so the profile regions line up with
        the sink span names (docs/USAGE.md "Operator view")."""
        try:
            body = await request.json()
        except Exception as e:
            return self._json(protocol.error_event(
                "bad_request", f"body is not JSON: {e}"), status=400)
        action = body.get("action") if isinstance(body, dict) else None
        if action not in ("start", "stop"):
            return self._json(protocol.error_event(
                "bad_request",
                f"action must be 'start' or 'stop', got {action!r}"),
                status=400)
        if not self._profile_dir:
            return self._json(protocol.error_event(
                "profiler_unavailable",
                "this gateway was started without profile_dir; "
                "restart with Gateway(profile_dir=...) or "
                "scripts/gateway.py --profile-dir"), status=501)
        if not jax_compat.profiler_available():
            return self._json(protocol.error_event(
                "profiler_unavailable",
                "jax.profiler.start_trace is unavailable in this jax "
                "build"), status=501)
        with self._profile_lock:
            if action == "start":
                if self._profiling:
                    return self._json(protocol.error_event(
                        "profile_conflict",
                        "a profiler capture is already running; POST "
                        "{'action': 'stop'} first"), status=409)
                try:
                    jax_compat.start_profiler_trace(self._profile_dir)
                except RuntimeError as e:
                    return self._json(protocol.error_event(
                        "profiler_unavailable", str(e)), status=501)
                self._profiling = True
            else:
                if not self._profiling:
                    return self._json(protocol.error_event(
                        "profile_conflict",
                        "no profiler capture is running"), status=409)
                try:
                    jax_compat.stop_profiler_trace()
                except RuntimeError as e:
                    return self._json(protocol.error_event(
                        "profiler_unavailable", str(e)), status=501)
                finally:
                    self._profiling = False
        return self._json({"profiling": self._profiling,
                           "dir": self._profile_dir})

    def snapshot(self) -> dict:
        """The stats payload, also callable in-process (no HTTP)."""
        srv = self.server
        snap = {
            "gateway": dict(self.stats),
            "server": dict(srv.stats),
            "queue_depth": len(srv.queue),
            "queue_capacity": srv.queue.capacity,
            "draining": self.draining,
            "buckets": list(srv.buckets),
            "active_buckets": list(srv.active_buckets),
            "occupancy_mean": round(srv.occupancy_mean, 4),
            "utilization_mean": round(srv.utilization_mean, 4),
            "last_occupancy": srv.stats.get("last_occupancy", 0.0),
            "warm_compiles": self.warm_compiles,
            "compile_count": srv.compile_count(),
            "guard_events": (len(srv.monitor.events)
                             if srv.monitor is not None else 0),
        }
        placement = srv.placement_summary()
        if placement is not None:
            snap["placement"] = placement
        if self._autoscale is not None:
            snap["autoscale"] = self._autoscale.summary()
        # Round 19 (performance observatory): per-bucket cost stamps
        # (footprint bytes, flops-vs-analytic ratio, compile seconds,
        # advisory headroom) and the live device-memory snapshot when
        # serve.memory_watch is polling.
        snap["bucket_costs"] = srv.bucket_costs()
        memory = srv.memory_snapshot()
        if memory is not None:
            snap["memory"] = memory
        # Round 21 (warm pools): hit/miss/rung counters and the
        # speculative compiler's build log — only
        # stamped when a pool is configured, so a pool-less
        # deployment's stats payload stays byte-identical to round 20.
        warm = srv.warmpool_summary()
        if warm is not None:
            snap["warm_pool"] = warm
        return snap
