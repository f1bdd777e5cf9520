"""HTTP-JSON front end for the serving engine.

Thin by design: the stdlib ``ThreadingHTTPServer`` + the shared
``utils.httpjson`` framing, one background thread running the engine
loop. Handler threads block on the request's ``done`` event and return
the finished stream — a synchronous completion API — or, with
``"stream": true``, hold the connection open and relay tokens as SSE
frames straight off the engine's async readback (tokens are already
host-side per horizon; streaming adds zero device syncs).

Multi-tenant mode: when the engine carries a
:class:`~deeplearning4j_tpu.serving.tenancy.TenantRegistry`, every
POST resolves its API key (``X-API-Key`` header, or ``Authorization:
Bearer <key>``) to a tenant — unknown keys get 401, a missing key maps
to the registry's anonymous tenant if one exists. The tenant supplies
scheduling priority, the default LoRA adapter, and the token-rate
quota whose exhaustion surfaces as 429 (``QuotaExceeded`` subclasses
``Backpressure``, so the shed-load path is shared).

The engine thread is SUPERVISED: an exception escaping
``engine.step()`` (an ``EngineCrash`` from the fault layer, or any
bug) is caught, recorded as ``last_error``, and the engine state is
rebuilt by deterministic replay (``engine.recover``). After
``max_restarts`` CONSECUTIVE failed recoveries the engine is declared
dead: every in-flight and queued request is failed (so no handler
blocks forever) and ``/healthz`` flips to 503 — which is how an
orchestrator is told to replace the process.

Endpoints:

- ``POST /v1/generate`` — body ``{"prompt": [ints] | "text",
  "max_new": int, "priority"?: int, "eos_token"?: int,
  "deadline_s"?: float, "adapter"?: int, "stream"?: bool}`` plus —
  on engines built with ``sampling_surface=True`` — the per-request
  sampling surface: ``"temperature"?: float, "top_k"?: int,
  "top_p"?: float, "stop"?: str | [str | [ints]],
  "logit_bias"?: {token_id: float}, "logprobs"?: bool,
  "top_logprobs"?: int, "response_format"?: {"type": "json_schema",
  "json_schema": {...}} | {"type": "regex", "regex": "..."}``
  (grammar-constrained decoding; requires ``eos_token``). Returns
  ``{"id", "tokens", "text"?, "timing"?, "logprobs"?}`` where
  ``timing`` is
  ``{"ttft_s", "decode_s"}`` — engine-local time to first token and
  wall time after it (end-to-end TTFT = request wall - ``decode_s``,
  which counts queueing and any disagg prefill/transfer leg). 429 on
  queue backpressure or tenant
  quota, 400 on a request that can never fit a slot (or an adapter
  index outside the loaded LoRA bank), 401 on an unknown API key, 503
  while draining/stopped, 408 when ``deadline_s`` expired, 500 when
  the request was failed by the fault layer, 504 on handler timeout
  (the request IS cancelled in the engine — its KV slot frees within
  one step, it does not keep decoding for a gone client). With
  ``"stream": true`` the response is ``text/event-stream``: one
  ``data: {"token": t}`` frame per generated token, then a final
  ``data: {"done": true, ...}`` frame carrying the terminal status;
  the concatenated streamed tokens are byte-identical to the
  non-streaming ``tokens`` tail, and a client disconnect mid-stream
  cancels the request in the engine.
- ``POST /v1/embeddings`` — body ``{"words": ["w", ...],
  "model"?: "word2vec"|"glove"}``; returns ``{"id", "model",
  "vectors": {word: [floats] | null}}`` (null = out-of-vocabulary).
  Embedding lookups ride the same scheduler/quota/metrics/drain
  machinery as generation but are served host-side without a KV slot.
- ``GET /metrics`` — Prometheus text exposition (version 0.0.4) of the
  engine's metrics registry: request outcomes, retries, restarts,
  backpressure, queue depth, KV occupancy/churn, TTFT/TPOT and
  per-phase latency histograms (see :mod:`..serving.metrics` and
  :mod:`..obs.registry`). Also served standalone on ``metrics_port``
  when one is configured — a scrape sidecar that keeps working while
  the main port is saturated with generate traffic.
- ``GET /metrics.json`` — ``ServingMetrics.summary()`` + live engine
  state (the human-readable aggregate view).
- ``POST /profile?s=N`` — arm an XLA profiler capture of the next N
  engine steps (requires the engine to be wired with a
  ``ProfileTrigger``; 409 while a capture is already armed). Returns
  the directory the capture will land in.
- ``GET /profile/report`` — the last finished capture reduced to
  numbers (:func:`..obs.capture.loop_report`): the device's idle
  seconds by the loop phase they lie under, the seconds between decode
  steps by the program that ran in them. 404 before the first capture,
  409 while one is armed or running.
- ``GET /healthz`` — liveness: 200 while the engine thread is alive
  (or recovering), 503 once it is dead OR HUNG; payload carries
  ``engine_alive``, ``last_error``, the restart count, and the
  watchdog fields. A thread can be alive but wedged — blocked forever
  inside a device call the fault layer never sees — so the loop
  maintains a heartbeat (stamped each iteration) and ``/healthz``
  reports ``hung`` when the engine has non-idle work but the heartbeat
  is older than ``hang_threshold_s``. An idle engine beats too (the
  sleep poll), so a quiet server never trips the watchdog.
- ``GET /readyz`` — readiness: 200 only when healthy AND not
  draining; load balancers should route on this one.

``stop(drain_s)`` drains gracefully: admission stops first (new
submits get 503), in-flight requests get up to ``drain_s`` seconds to
finish. Stragglers still decoding AT the deadline are PREEMPTED —
``engine.preempt_all()`` cancels every live and queued request, and
the loop gets a short grace window to retire them as CANCELLED
(partial streams stored, ``done`` set, HTTP 499) — before the loop and
listener shut down. Hard stop (``drain_s=0``) skips the wait and fails
leftovers instead.

Text prompts/completions use the repo's byte-level convention
(latin-1 per byte) and are only offered when ``vocab_size <= 256``.
"""

from __future__ import annotations

import http.client
import json
import logging
import os
import queue
import signal
import threading
import time

import numpy as np

from http.server import ThreadingHTTPServer
from pathlib import Path
from urllib.parse import parse_qs, urlparse

from deeplearning4j_tpu.obs import capture
from deeplearning4j_tpu.obs.logs import log_event
from deeplearning4j_tpu.obs.trace import (
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)
from deeplearning4j_tpu.serving.disagg import (
    WireError,
    decode_segment,
    encode_segment,
)
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.serving.rpc import (
    DEADLINE_HEADER,
    IDEMPOTENCY_HEADER,
    Deadline,
    IdempotencyRegistry,
)
from deeplearning4j_tpu.serving.scheduler import (
    AdmissionError,
    Backpressure,
    EmbeddingRequest,
    KVExportRequest,
    KVIngestRequest,
    KVSessionRequest,
    Request,
    RequestStatus,
)
from deeplearning4j_tpu.utils.httpjson import (
    QuietHandler,
    read_json_body,
    send_body,
    send_json,
)

_log = logging.getLogger(__name__)

#: Prometheus text exposition format version served at /metrics
PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

#: HTTP status for each non-FINISHED terminal request state
_STATUS_HTTP = {
    RequestStatus.FAILED: 500,
    RequestStatus.EXPIRED: 408,
    RequestStatus.CANCELLED: 499,  # nginx-style: client gone
}

#: sentinel from ``_resolve_tenant`` for an API key the registry does
#: not know (distinct from None = server running without tenancy)
_UNKNOWN_KEY = object()


class ServingServer:
    """Engine + HTTP front end; ``start()`` is non-blocking."""

    def __init__(self, engine: ServingEngine, host: str = "127.0.0.1",
                 port: int = 0, request_timeout_s: float = 300.0,
                 max_restarts: int = 5, hang_threshold_s: float = 120.0,
                 metrics_port: int | None = None,
                 flight_dir: str | None = None,
                 migrate_targets: tuple[str, ...] = ()):
        self.engine = engine
        self.request_timeout_s = request_timeout_s
        self.max_restarts = max_restarts
        self.hang_threshold_s = hang_threshold_s
        # default destinations for live session migration: tried in
        # order by ``stop()`` at the drain deadline (and by POST
        # /migrate with no body) before falling back to preemption
        self.migrate_targets = tuple(migrate_targets)
        # receiver-side dedup for hedged/retried seat+ingest legs: a
        # duplicate X-Idempotency-Key is declined with 409, never
        # seated twice
        self._idem = IdempotencyRegistry()
        # one-slot mailbox for the engine loop: the migrate path posts
        # {"evt": Event} here and the loop (the only thread allowed to
        # touch device/slot state) fills in "sessions" between steps
        self._migrate_box: dict | None = None
        self._migrate_lock = threading.Lock()
        # postmortem bundle directory (crash / watchdog / SIGTERM
        # dumps); DL4J_TPU_FLIGHT_DIR supplies a default for wiring
        # sites that don't thread the kwarg (the CI chaos lane sets it)
        self.flight_dir = (
            flight_dir if flight_dir is not None
            else os.environ.get("DL4J_TPU_FLIGHT_DIR") or None
        )
        self._hang_dumped = False
        self._stop = threading.Event()
        self._draining = threading.Event()
        # admission pause via POST /drain — distinct from _draining
        # (stop()'s terminal drain makes the engine loop EXIT once
        # idle; a paused server keeps its loop and caches alive and
        # resumes on /undrain — the rolling-restart primitive)
        self._paused = threading.Event()
        self._engine_dead = threading.Event()
        self._last_error: str | None = None
        # watchdog heartbeat: stamped at the top of every engine-loop
        # iteration, so a loop wedged INSIDE step() (e.g. a device call
        # that never returns) stops beating while its thread stays alive
        self._last_beat: float | None = None
        # server-level gauges on the engine's registry, so one scrape
        # carries engine AND supervisor state
        reg = engine.metrics.registry
        reg.gauge(
            "serve_engine_alive",
            "1 while the supervised engine loop is considered live.",
        ).set_function(lambda: float(self._health_payload()["ok"]))
        reg.gauge(
            "serve_draining", "1 while the server is draining.",
        ).set_function(lambda: float(
            self._draining.is_set() or self._paused.is_set()
        ))
        server = self

        class Handler(QuietHandler):
            def do_GET(self):
                if not server._common_get(self):
                    send_json(self, 404, {"error": "not found"})

            def do_POST(self):
                path = urlparse(self.path).path
                if path == "/profile":
                    server._handle_profile(self)
                    return
                if path in ("/drain", "/undrain"):
                    # reachable while paused by design: the controller
                    # must be able to undrain a replica it drained
                    server._handle_drain(self, path == "/drain")
                    return
                if path == "/migrate":
                    # also reachable while paused: the controller drains
                    # a replica FIRST, then asks it to migrate leftovers
                    server._handle_migrate(self)
                    return
                if path not in ("/v1/generate", "/v1/embeddings",
                                "/v1/kv_segment", "/v1/prefill",
                                "/v1/kv_session"):
                    send_json(self, 404, {"error": "not found"})
                    return
                if (server._draining.is_set() or server._paused.is_set()
                        or server._stop.is_set()):
                    send_json(self, 503, {"error": "draining"})
                    return
                if server._engine_dead.is_set():
                    send_json(self, 503, {
                        "error": "engine dead",
                        "last_error": server._last_error,
                    })
                    return
                tenant = server._resolve_tenant(self)
                if tenant is _UNKNOWN_KEY:
                    send_json(self, 401, {"error": "unknown API key"})
                    return
                if path == "/v1/kv_segment":
                    # binary wire frame, not JSON
                    server._handle_kv_segment(self, tenant)
                    return
                if path == "/v1/kv_session":
                    # binary wire frame with live-session state
                    server._handle_kv_session(self, tenant)
                    return
                body = read_json_body(self)
                if body is None:
                    send_json(self, 400, {"error": "malformed JSON"})
                    return
                if path == "/v1/embeddings":
                    server._handle_embeddings(self, body, tenant)
                elif path == "/v1/prefill":
                    server._handle_prefill(self, body, tenant)
                else:
                    server._handle_generate(self, body, tenant)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        # fleet identity: what the access log reports as served_by
        # when the router's X-Served-By header is absent (direct hits)
        self.name = "%s:%d" % self._httpd.server_address[:2]
        # named threads: sanitizer reports (and py-spy dumps)
        # attribute races/locks to "engine-loop" vs "http-serve"
        self._engine_thread = threading.Thread(
            target=self._engine_loop, daemon=True, name="engine-loop"
        )
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True, name="http-serve"
        )

        # optional scrape sidecar: /metrics (+ health) on its own port,
        # isolated from generate traffic saturating the main listener
        self._metrics_httpd = None
        self._metrics_thread = None
        if metrics_port is not None:

            class MetricsHandler(QuietHandler):
                def do_GET(self):
                    if not server._common_get(self):
                        send_json(self, 404, {"error": "not found"})

            self._metrics_httpd = ThreadingHTTPServer(
                (host, metrics_port), MetricsHandler
            )
            self._metrics_thread = threading.Thread(
                target=self._metrics_httpd.serve_forever, daemon=True,
                name="metrics-serve",
            )

    @property
    def address(self) -> tuple[str, int]:
        return self._httpd.server_address[:2]

    @property
    def metrics_address(self) -> tuple[str, int] | None:
        """(host, port) of the metrics sidecar, or None when not
        configured."""
        if self._metrics_httpd is None:
            return None
        return self._metrics_httpd.server_address[:2]

    def _common_get(self, handler) -> bool:
        """Serve the observability GET endpoints (shared between the
        main listener and the metrics sidecar). Returns False for an
        unknown path."""
        path = urlparse(handler.path).path
        if path == "/healthz":
            payload = self._health_payload()
            send_json(handler, 200 if payload["ok"] else 503, payload)
        elif path == "/readyz":
            payload = self._health_payload()
            ready = payload["ok"] and not payload["draining"]
            payload["ready"] = ready
            send_json(handler, 200 if ready else 503, payload)
        elif path == "/metrics":
            send_body(
                handler, 200,
                self.engine.metrics.render_prometheus().encode(),
                PROM_CONTENT_TYPE,
            )
        elif path == "/metrics.json":
            send_json(handler, 200, self._metrics_payload())
        elif path == "/debug/dump":
            send_json(handler, 200, self.flight_bundle("debug_dump"))
        elif path == "/profile/report":
            self._handle_profile_report(handler)
        else:
            return False
        return True

    def flight_bundle(self, reason: str) -> dict:
        """The crash flight recorder's redacted postmortem bundle:
        recent engine events + metrics snapshot + trace tail (see
        :mod:`deeplearning4j_tpu.obs.flight`)."""
        return self.engine.flight.dump(
            reason,
            metrics=self.engine.metrics,
            tracer=self.engine.tracer,
            extra={"server": self.name, "health": self._health_payload()},
        )

    def _dump_flight(self, reason: str) -> None:
        """Best-effort postmortem write to ``flight_dir`` (no-op when
        unconfigured; never raises — this runs on crash paths)."""
        if not self.flight_dir:
            return
        try:
            path = Path(self.flight_dir) / (
                "flight-%s-%s-%d.json"
                % (self.name.replace(":", "-"), reason,
                   int(time.time() * 1000))
            )
            self.engine.flight.dump_to(
                path, reason,
                metrics=self.engine.metrics,
                tracer=self.engine.tracer,
                extra={"server": self.name,
                       "last_error": self._last_error},
            )
            log_event(_log, "flight_dump", reason=reason,
                      path=str(path))
        except Exception as e:
            log_event(_log, "flight_dump_failed", reason=reason,
                      error=repr(e), level=logging.ERROR)

    def _handle_profile(self, handler) -> None:
        """``POST /profile?s=N``: arm an XLA capture of the next N
        engine steps."""
        trigger = self.engine.profile
        if trigger is None:
            send_json(handler, 503, {
                "error": "no ProfileTrigger configured "
                         "(start the server with profiling wired)",
            })
            return
        qs = parse_qs(urlparse(handler.path).query)
        try:
            n = int(qs.get("s", ["1"])[0])
            if n < 1:
                raise ValueError
        except ValueError:
            send_json(handler, 400, {"error": "s must be an int >= 1"})
            return
        try:
            capture_dir = trigger.arm(n)
        except RuntimeError as e:  # already armed
            send_json(handler, 409, {"error": str(e)})
            return
        log_event(_log, "profile_armed", steps=n, dir=str(capture_dir))
        send_json(handler, 200, {"armed": n, "dir": str(capture_dir)})

    def _handle_profile_report(self, handler) -> None:
        """``GET /profile/report``: the last finished capture reduced
        by :func:`~deeplearning4j_tpu.obs.capture.loop_report`, on this
        handler's thread and never on the loop's."""
        trigger = self.engine.profile
        try:
            done = trigger.finished_capture() if trigger else None
        except RuntimeError as e:  # armed or running
            send_json(handler, 409, {"error": str(e)})
            return
        path = capture.find_xplane(done) if done is not None else None
        if path is None:
            send_json(handler, 404, {
                "error": "no finished capture (POST /profile?s=N first)",
            })
            return
        send_json(handler, 200, dict(
            capture.loop_report(path), dir=str(done),
        ))

    def _byte_vocab(self) -> bool:
        return self.engine.cfg.vocab_size <= 256

    def _resolve_tenant(self, handler):
        """TenantConfig for the request's API key (``X-API-Key``
        header, or ``Authorization: Bearer <key>``). None when the
        server runs without tenancy; the ``_UNKNOWN_KEY`` sentinel for
        a key the registry does not know (the caller answers 401 —
        which an anonymous-less registry also gives keyless requests)."""
        tenancy = self.engine.tenancy
        if tenancy is None:
            return None
        key = handler.headers.get("X-API-Key")
        if not key:
            auth = handler.headers.get("Authorization", "")
            if auth.startswith("Bearer "):
                key = auth[len("Bearer "):]
        t = tenancy.resolve_key(key)
        return _UNKNOWN_KEY if t is None else t

    def _parse_request(self, body: dict, tenant=None) -> Request:
        prompt = body.get("prompt")
        if isinstance(prompt, str):
            if not self._byte_vocab():
                raise ValueError(
                    "text prompts need a byte-level model (vocab <= 256)"
                )
            prompt = list(prompt.encode("latin-1", errors="replace"))
        if not isinstance(prompt, list):
            raise ValueError("'prompt' must be a token list or a string")
        stop = body.get("stop")
        if stop is not None:
            if isinstance(stop, str):
                stop = [stop]
            if not isinstance(stop, list):
                raise ValueError(
                    "'stop' must be a string or a list of "
                    "strings/token lists"
                )
            stops = []
            for s in stop:
                if isinstance(s, str):
                    if not self._byte_vocab():
                        raise ValueError(
                            "string stop sequences need a byte-level "
                            "model (vocab <= 256)"
                        )
                    s = list(s.encode("latin-1", errors="replace"))
                if not isinstance(s, list) or not s:
                    raise ValueError(
                        "each stop sequence must be a non-empty "
                        "string or token list"
                    )
                stops.append([int(t) for t in s])
            stop = stops
        # the tenant supplies scheduling priority and the LoRA adapter
        # unless the body names its own
        return Request(
            prompt=prompt,
            max_new=int(body.get("max_new", 16)),
            temperature=(float(body["temperature"])
                         if "temperature" in body else None),
            top_k=int(body["top_k"]) if "top_k" in body else None,
            top_p=float(body["top_p"]) if "top_p" in body else None,
            stop=stop,
            logit_bias=body.get("logit_bias"),
            logprobs=bool(body.get("logprobs", False)),
            top_logprobs=int(body.get("top_logprobs", 0)),
            response_format=body.get("response_format"),
            priority=int(body.get(
                "priority", tenant.priority if tenant is not None else 1
            )),
            eos_token=(
                int(body["eos_token"]) if "eos_token" in body else None
            ),
            deadline_s=(
                float(body["deadline_s"]) if "deadline_s" in body else None
            ),
            adapter=int(body.get(
                "adapter",
                tenant.default_adapter if tenant is not None else 0,
            )),
            tenant_id=tenant.tenant_id if tenant is not None else "",
            stream=queue.Queue() if body.get("stream") else None,
            done=threading.Event(),
        )

    @staticmethod
    def _resolve_trace(handler, req: Request) -> None:
        """W3C trace context: adopt the caller's ``traceparent``
        (trace id + the caller's span as our parent — the router's
        dispatch span, when routed) or start a fresh trace. Every
        request gets a trace id, so the access log and the engine's
        admission span always correlate."""
        ctx = parse_traceparent(handler.headers.get("traceparent"))
        if ctx is not None:
            req.trace_id, req.parent_span_id = ctx
        else:
            req.trace_id = new_trace_id()

    def _deadline(self, handler) -> Deadline:
        """Per-request deadline budget: honor the caller's
        ``X-Deadline-Ms`` header (router/controller shrink it on every
        hop) and fall back to the server's own request timeout. Every
        blocking wait and outbound leg below derives its timeout from
        this budget, so a request never outlives what the first hop
        promised the client."""
        return Deadline.from_header(
            handler.headers.get(DEADLINE_HEADER),
            default_s=self.request_timeout_s,
        )

    def _access_log(self, handler, req, http: int, status: str,
                    **fields) -> None:
        """The one structured access-log line per request: resolved
        trace context, tenant, and which replica served it (the
        router's ``X-Served-By`` injection names this process in the
        router's vocabulary; direct hits fall back to host:port)."""
        log_event(
            _log, "access", req_id=req.id, http=http, status=status,
            trace_id=req.trace_id or None,
            parent_span_id=req.parent_span_id or None,
            tenant=req.tenant_id or None,
            served_by=handler.headers.get("X-Served-By") or self.name,
            **fields,
        )

    def _handle_generate(self, handler, body: dict, tenant) -> None:
        try:
            req = self._parse_request(body, tenant)
        except (AdmissionError, ValueError, TypeError) as e:
            send_json(handler, 400, {"error": str(e)})
            return
        self._resolve_trace(handler, req)
        dl = self._deadline(handler)
        if req.deadline_s is None and handler.headers.get(DEADLINE_HEADER):
            # mirror the wire budget into engine-side expiry so a
            # queued request whose budget lapsed retires EXPIRED
            # instead of decoding for a caller that already gave up
            req.deadline_s = dl.remaining_s()
        try:
            self.engine.submit(req)
        except Backpressure as e:
            self._access_log(handler, req, 429, "backpressure")
            send_json(handler, 429, {"error": str(e)})
            return
        except AdmissionError as e:
            self._access_log(handler, req, 400, "admission_error")
            send_json(handler, 400, {"error": str(e)})
            return
        if req.stream is not None:
            self._stream_generate(
                handler, req,
                wait_s=dl.timeout(self.request_timeout_s, floor=0.0),
            )
            return
        if not req.done.wait(dl.timeout(self.request_timeout_s, floor=0.0)):
            # cancel in the engine so the slot stops decoding
            # for a client that is about to get a timeout
            req.cancel()
            log_event(_log, "request_completed", req_id=req.id,
                      http=504, status="timeout",
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, 504, "timeout")
            send_json(handler, 504, {"error": "generation timed out"})
            return
        if req.status is not RequestStatus.FINISHED:
            code = _STATUS_HTTP.get(req.status, 500)
            self.engine.pop_result(req.id)  # drop partial stream
            log_event(_log, "request_completed", req_id=req.id,
                      http=code, status=req.status.value,
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, code, req.status.value)
            send_json(handler, code, {
                "id": req.id,
                "status": req.status.value,
                "error": req.error or req.status.value,
            })
            return
        toks = self.engine.pop_result(req.id).tolist()
        n_new = len(toks) - len(req.prompt)
        log_event(_log, "request_completed", req_id=req.id,
                  http=200, status="finished", n_tokens=n_new,
                  trace_id=req.trace_id or None)
        self._access_log(handler, req, 200, "finished", n_tokens=n_new)
        out = {"id": req.id, "tokens": toks}
        timing = getattr(req, "timing", None)
        if timing is not None:
            out["timing"] = {k: round(float(v), 6)
                             for k, v in timing.items()}
        if req.logprobs and req.logprobs_out is not None:
            out["logprobs"] = req.logprobs_out
        if self._byte_vocab():
            out["text"] = bytes(
                t % 256 for t in toks
            ).decode("latin-1")
        send_json(handler, 200, out)

    @staticmethod
    def _sse(handler, payload: dict) -> None:
        """One SSE ``data:`` frame, flushed (per-token latency is the
        point of streaming)."""
        handler.wfile.write(b"data: " + json.dumps(payload).encode()
                            + b"\n\n")
        handler.wfile.flush()

    def _stream_generate(self, handler, req: Request,
                         wait_s: float | None = None) -> None:
        """SSE relay: one frame per generated token as each horizon's
        readback lands on ``req.stream``, then a final frame with the
        terminal status. The engine sets the terminal status BEFORE
        putting the end-of-stream sentinel, so reading the sentinel
        here orders correctly with ``req.status``. A client disconnect
        mid-stream cancels the request in the engine (its KV slot
        frees within one horizon — no decoding for a gone client)."""
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.send_header("Connection", "close")
        handler.end_headers()
        deadline = time.monotonic() + (
            self.request_timeout_s if wait_s is None else wait_s
        )
        byte_vocab = self._byte_vocab()
        n = 0
        try:
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    req.cancel()
                    log_event(_log, "request_completed", req_id=req.id,
                              http=504, status="timeout", stream=True,
                              trace_id=req.trace_id or None)
                    self._access_log(handler, req, 504, "timeout",
                                     stream=True)
                    self._sse(handler, {"error": "generation timed out",
                                        "done": True})
                    return
                try:
                    tok = req.stream.get(timeout=min(remaining, 1.0))
                except queue.Empty:
                    continue  # still decoding; re-check the deadline
                if tok is None:
                    break  # end-of-stream sentinel
                n += 1
                frame = {"token": int(tok)}
                if byte_vocab:
                    frame["text"] = chr(tok % 256)
                self._sse(handler, frame)
            final = {"id": req.id, "status": req.status.value,
                     "n_tokens": n, "done": True}
            if req.status is not RequestStatus.FINISHED and req.error:
                final["error"] = req.error
            if req.logprobs and req.logprobs_out is not None:
                # per-token logprobs ride the final frame (the engine
                # attaches them at retire, before the sentinel)
                final["logprobs"] = req.logprobs_out
            self._sse(handler, final)
            log_event(_log, "request_completed", req_id=req.id, http=200,
                      status=req.status.value, n_tokens=n, stream=True,
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, 200, req.status.value,
                             n_tokens=n, stream=True)
        except (BrokenPipeError, ConnectionResetError):
            req.cancel()
            log_event(_log, "request_completed", req_id=req.id, http=499,
                      status="client_gone", n_tokens=n, stream=True,
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, 499, "client_gone",
                             n_tokens=n, stream=True)
        finally:
            # the stream already delivered the tokens; drop the stored
            # copy so streaming traffic doesn't grow the results dict
            self.engine.pop_result(req.id)

    def _handle_embeddings(self, handler, body: dict, tenant) -> None:
        words = body.get("words")
        if isinstance(words, str):
            words = words.split()
        if (not isinstance(words, list) or not words
                or not all(isinstance(w, str) for w in words)):
            send_json(handler, 400, {
                "error": "'words' must be a non-empty list of strings",
            })
            return
        if not self.engine.embedders:
            send_json(handler, 503, {"error": "no embedding models loaded"})
            return
        req = EmbeddingRequest(
            words=tuple(words),
            model=str(body.get("model", "word2vec")),
            priority=int(body.get(
                "priority", tenant.priority if tenant is not None else 1
            )),
            tenant_id=tenant.tenant_id if tenant is not None else "",
            done=threading.Event(),
        )
        self._resolve_trace(handler, req)
        dl = self._deadline(handler)
        try:
            self.engine.submit(req)
        except Backpressure as e:
            self._access_log(handler, req, 429, "backpressure",
                             kind="embedding")
            send_json(handler, 429, {"error": str(e)})
            return
        except AdmissionError as e:
            self._access_log(handler, req, 400, "admission_error",
                             kind="embedding")
            send_json(handler, 400, {"error": str(e)})
            return
        if not req.done.wait(dl.timeout(self.request_timeout_s, floor=0.0)):
            req.cancel()
            log_event(_log, "request_completed", req_id=req.id,
                      http=504, status="timeout", kind="embedding",
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, 504, "timeout",
                             kind="embedding")
            send_json(handler, 504, {"error": "embedding timed out"})
            return
        if req.status is not RequestStatus.FINISHED:
            code = _STATUS_HTTP.get(req.status, 500)
            log_event(_log, "request_completed", req_id=req.id,
                      http=code, status=req.status.value, kind="embedding",
                      trace_id=req.trace_id or None)
            self._access_log(handler, req, code, req.status.value,
                             kind="embedding")
            send_json(handler, code, {
                "id": req.id,
                "status": req.status.value,
                "error": req.error or req.status.value,
            })
            return
        vectors = {
            w: (None if v is None else [float(x) for x in v])
            for w, v in req.result.items()
        }
        log_event(_log, "request_completed", req_id=req.id, http=200,
                  status="finished", kind="embedding", n_words=len(words),
                  trace_id=req.trace_id or None)
        self._access_log(handler, req, 200, "finished", kind="embedding",
                         n_words=len(words))
        send_json(handler, 200, {
            "id": req.id, "model": req.model, "vectors": vectors,
        })

    # -- disaggregated prefill/decode ---------------------------------

    def _handle_drain(self, handler, draining: bool) -> None:
        """``POST /drain`` / ``POST /undrain``: pause or resume
        admission without stopping the engine loop. ``/readyz`` flips
        to 503 so routers stop dispatching; in-flight and queued work
        still finishes (the loop keeps stepping — only NEW submits get
        503); ``/undrain`` restores readiness. Idempotent both ways."""
        if draining:
            self._paused.set()
        else:
            self._paused.clear()
        log_event(_log, "drain" if draining else "undrain",
                  in_flight=self.engine.pool.n_active,
                  queued=len(self.engine.scheduler))
        send_json(handler, 200, {
            "draining": self._paused.is_set(),
            "in_flight": self.engine.pool.n_active,
            "queued": len(self.engine.scheduler),
        })

    def _handle_kv_segment(self, handler, tenant) -> None:
        """``POST /v1/kv_segment``: ingest one binary KV-segment frame
        (see :mod:`..serving.disagg`) and seat it in the prefix cache
        through the engine's admission loop. 400/409 come straight from
        ``WireError.status``; otherwise 200 with ``{"stored": bool,
        "reason"}`` — a decline (cache full, no prefix cache) is
        not an error, the sender just forfeits the transfer win. A
        repeated ``X-Idempotency-Key`` (a hedged retransmit of a frame
        already being seated) is declined with 409 so the frame is
        never ingested twice."""
        dl = self._deadline(handler)
        idem = handler.headers.get(IDEMPOTENCY_HEADER, "")
        if not self._idem.first_seen(idem):
            log_event(_log, "kv_segment_duplicate", idem_key=idem)
            send_json(handler, 409, {"error": "duplicate frame",
                                     "duplicate": True, "stored": False})
            return
        try:
            length = int(handler.headers.get("Content-Length", "0"))
            data = handler.rfile.read(length)
        except (ValueError, OSError):
            send_json(handler, 400, {"error": "unreadable body"})
            return
        try:
            seg = decode_segment(data, expect_hash=self.engine.config_hash)
        except WireError as e:
            log_event(_log, "kv_segment_rejected", error=str(e),
                      http=e.status, nbytes=len(data))
            send_json(handler, e.status, {"error": str(e)})
            return
        req = KVIngestRequest(
            segment=seg,
            priority=tenant.priority if tenant is not None else 1,
            tenant_id=tenant.tenant_id if tenant is not None else "",
            done=threading.Event(),
        )
        self._resolve_trace(handler, req)
        try:
            self.engine.submit(req)
        except Backpressure as e:
            self._access_log(handler, req, 429, "backpressure",
                             kind="kv_ingest")
            send_json(handler, 429, {"error": str(e)})
            return
        except AdmissionError as e:
            self._access_log(handler, req, 400, "admission_error",
                             kind="kv_ingest")
            send_json(handler, 400, {"error": str(e)})
            return
        if not req.done.wait(dl.timeout(self.request_timeout_s, floor=0.0)):
            req.cancel()
            self._access_log(handler, req, 504, "timeout",
                             kind="kv_ingest")
            send_json(handler, 504, {"error": "kv ingest timed out"})
            return
        if req.status is not RequestStatus.FINISHED:
            code = _STATUS_HTTP.get(req.status, 500)
            self._access_log(handler, req, code, req.status.value,
                             kind="kv_ingest")
            send_json(handler, code, {
                "id": req.id,
                "status": req.status.value,
                "error": req.error or req.status.value,
            })
            return
        self._access_log(handler, req, 200, "finished", kind="kv_ingest",
                         stored=bool(req.result.get("stored")))
        send_json(handler, 200, {"id": req.id, **req.result})

    def _handle_kv_session(self, handler, tenant) -> None:
        """``POST /v1/kv_session``: seat one LIVE migrated session — a
        KV-segment frame whose ``gen`` header block carries the source
        slot's generation state (tokens so far, sampling key, budget) —
        and decode it to completion here. 200 answers with the FULL
        final token sequence; any seating decline is a soft 409 (the
        sender keeps the session and falls back to its preempt path);
        a repeated idempotency key (a hedged retransmit) is 409 with
        ``"duplicate": true``. Never 200-with-wrong-bytes: the engine
        declines anything it cannot continue byte-identically."""
        dl = self._deadline(handler)
        idem = handler.headers.get(IDEMPOTENCY_HEADER, "")
        if not self._idem.first_seen(idem):
            log_event(_log, "kv_session_duplicate", idem_key=idem)
            send_json(handler, 409, {"error": "duplicate session frame",
                                     "duplicate": True})
            return
        try:
            length = int(handler.headers.get("Content-Length", "0"))
            data = handler.rfile.read(length)
        except (ValueError, OSError):
            send_json(handler, 400, {"error": "unreadable body"})
            return
        try:
            seg = decode_segment(data, expect_hash=self.engine.config_hash)
        except WireError as e:
            log_event(_log, "kv_session_rejected", error=str(e),
                      http=e.status, nbytes=len(data))
            send_json(handler, e.status, {"error": str(e)})
            return
        gen = seg.get("gen")
        if not isinstance(gen, dict):
            send_json(handler, 400, {
                "error": "frame carries no session state ('gen' header)",
            })
            return
        try:
            n_prompt = int(gen["n_prompt"])
            req = KVSessionRequest(
                prompt=[int(t) for t in seg["tokens"][:n_prompt]],
                max_new=int(gen["max_new"]),
                eos_token=(None if gen.get("eos_token") is None
                           else int(gen["eos_token"])),
                adapter=int(gen.get("adapter", 0)),
                priority=tenant.priority if tenant is not None else 1,
                tenant_id=tenant.tenant_id if tenant is not None else "",
                segment=seg,
                gen_tokens=tuple(int(t) for t in gen.get("tokens", ())),
                key_data=np.asarray(gen.get("key_data", ()), np.uint32),
                done=threading.Event(),
            )
        except (AdmissionError, KeyError, TypeError, ValueError) as e:
            send_json(handler, 400, {
                "error": f"bad session state: {type(e).__name__}: {e}",
            })
            return
        self._resolve_trace(handler, req)
        try:
            self.engine.submit(req)
        except Backpressure as e:
            self._access_log(handler, req, 429, "backpressure",
                             kind="kv_session")
            send_json(handler, 429, {"error": str(e)})
            return
        except AdmissionError as e:
            self._access_log(handler, req, 400, "admission_error",
                             kind="kv_session")
            send_json(handler, 400, {"error": str(e)})
            return
        if not req.done.wait(dl.timeout(self.request_timeout_s, floor=0.0)):
            req.cancel()
            self._access_log(handler, req, 504, "timeout",
                             kind="kv_session")
            send_json(handler, 504, {"error": "session seat timed out"})
            return
        if (req.status is RequestStatus.FAILED
                and isinstance(req.result, dict)
                and not req.result.get("seated", True)):
            # soft decline: the engine could not guarantee byte-exact
            # continuation (hash/shape/parity mismatch); 409 tells the
            # sender to keep the session on its own fallback path
            self._access_log(handler, req, 409, "declined",
                             kind="kv_session",
                             reason=req.result.get("reason"))
            send_json(handler, 409, {
                "id": req.id, "seated": False,
                "reason": req.result.get("reason"),
                "error": req.error or "session declined",
            })
            return
        if req.status is not RequestStatus.FINISHED:
            code = _STATUS_HTTP.get(req.status, 500)
            self.engine.pop_result(req.id)
            self._access_log(handler, req, code, req.status.value,
                             kind="kv_session")
            send_json(handler, code, {
                "id": req.id,
                "status": req.status.value,
                "error": req.error or req.status.value,
            })
            return
        toks = self.engine.pop_result(req.id).tolist()
        self._access_log(handler, req, 200, "finished", kind="kv_session",
                         n_tokens=len(toks) - len(req.prompt))
        send_json(handler, 200, {
            "id": req.id, "status": "finished", "tokens": toks,
            "n_generated": len(toks) - len(req.prompt),
        })

    def _handle_prefill(self, handler, body: dict, tenant) -> None:
        """``POST /v1/prefill``: prefill-only — compute the prompt's KV
        rows, frame them for the wire, and (with ``"push_to":
        "host:port"``) push the frame to a decode replica's
        ``/v1/kv_segment``. Returns frame metadata, never the frame
        itself; a failed push answers 200 with ``"pushed": false`` so
        the caller (the fleet controller) falls back to local prefill
        on the decode side — same bytes, just slower."""
        prompt = body.get("prompt")
        if isinstance(prompt, str):
            if not self._byte_vocab():
                send_json(handler, 400, {
                    "error": "text prompts need a byte-level model "
                             "(vocab <= 256)",
                })
                return
            prompt = list(prompt.encode("latin-1", errors="replace"))
        if not isinstance(prompt, list) or not prompt:
            send_json(handler, 400, {
                "error": "'prompt' must be a non-empty token list "
                         "or a string",
            })
            return
        req = KVExportRequest(
            prompt=prompt,
            priority=int(body.get(
                "priority", tenant.priority if tenant is not None else 1
            )),
            adapter=int(body.get(
                "adapter",
                tenant.default_adapter if tenant is not None else 0,
            )),
            tenant_id=tenant.tenant_id if tenant is not None else "",
            done=threading.Event(),
        )
        self._resolve_trace(handler, req)
        dl = self._deadline(handler)
        try:
            self.engine.submit(req)
        except Backpressure as e:
            self._access_log(handler, req, 429, "backpressure",
                             kind="kv_export")
            send_json(handler, 429, {"error": str(e)})
            return
        except AdmissionError as e:
            self._access_log(handler, req, 400, "admission_error",
                             kind="kv_export")
            send_json(handler, 400, {"error": str(e)})
            return
        if not req.done.wait(dl.timeout(self.request_timeout_s, floor=0.0)):
            req.cancel()
            self._access_log(handler, req, 504, "timeout",
                             kind="kv_export")
            send_json(handler, 504, {"error": "prefill timed out"})
            return
        if req.status is not RequestStatus.FINISHED:
            code = _STATUS_HTTP.get(req.status, 500)
            self._access_log(handler, req, code, req.status.value,
                             kind="kv_export")
            send_json(handler, code, {
                "id": req.id,
                "status": req.status.value,
                "error": req.error or req.status.value,
            })
            return
        res = req.result
        frame = encode_segment(
            config_hash=res["config_hash"], tokens=res["tokens"],
            leaves=res["leaves"], logits=res["logits"],
            layout=res["layout"], block_size=res["block_size"],
        )
        out = {"id": req.id, "n_tokens": len(req.prompt),
               "nbytes": len(frame), "config_hash": res["config_hash"]}
        push_to = body.get("push_to")
        if push_to:
            pushed, info = self._push_segment(
                str(push_to), frame, req, res.get("span_id"),
                idem_key=str(body.get("idem_key") or ""), deadline=dl,
            )
            out["pushed"] = pushed
            if info:
                out["ingest"] = info
        self._access_log(handler, req, 200, "finished", kind="kv_export",
                         n_tokens=len(req.prompt), nbytes=len(frame))
        send_json(handler, 200, out)

    def _push_segment(self, target: str, frame: bytes, req,
                      parent_span: str | None, *, idem_key: str = "",
                      deadline: Deadline | None = None) -> tuple[bool, dict]:
        """POST the frame to ``target``'s ``/v1/kv_segment``; returns
        ``(ok, ingest response)``. Emits a real "transfer" span — the
        flow anchor chaining prefill -> transfer -> decode ingest in
        the merged fleet trace (the outgoing ``traceparent`` names this
        span as the ingest's parent) — and records transfer
        bytes/latency either way: failed pushes are a first-class
        fleet signal, not silence."""
        host, _, port = target.rpartition(":")
        t0 = time.perf_counter()
        span_id = new_span_id()
        info: dict = {}
        ok = False
        err = None
        try:
            # the push leg's socket timeout comes from the request's
            # remaining deadline budget, not a fixed constant, so a
            # shrunken budget can't be blown waiting on one transfer
            conn = http.client.HTTPConnection(
                host or "127.0.0.1", int(port),
                timeout=(deadline.timeout(self.request_timeout_s)
                         if deadline is not None
                         else min(30.0, self.request_timeout_s)),
            )
            headers = {"Content-Type": "application/octet-stream"}
            if idem_key:
                # hedged transfers share this key; the decode replica
                # seats the first copy and 409s the loser
                headers[IDEMPOTENCY_HEADER] = idem_key
            if deadline is not None:
                headers[DEADLINE_HEADER] = deadline.header_value()
            if req.trace_id:
                headers["traceparent"] = format_traceparent(
                    req.trace_id, span_id
                )
            conn.request("POST", "/v1/kv_segment", body=frame,
                         headers=headers)
            resp = conn.getresponse()
            raw = resp.read()
            conn.close()
            try:
                info = json.loads(raw.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                info = {}
            ok = resp.status == 200 and bool(info.get("stored"))
            if resp.status != 200:
                err = "http %d: %s" % (resp.status, info.get("error"))
        except (OSError, ValueError) as e:
            err = repr(e)
        dt = time.perf_counter() - t0
        self.engine.metrics.record_transfer(len(frame), dt, ok=ok)
        tctx = {}
        if self.engine.tracer.enabled and req.trace_id:
            tctx = {"trace_id": req.trace_id, "span_id": span_id}
            if parent_span:
                tctx["parent_span_id"] = parent_span
        self.engine.tracer.span(
            "transfer", "transfer", t0, dt, target=target,
            nbytes=len(frame), ok=ok, **tctx,
        )
        log_event(_log, "kv_transfer", target=target, nbytes=len(frame),
                  ok=ok, seconds=round(dt, 6), error=err,
                  stored=bool(info.get("stored")))
        if err:
            info = dict(info)
            info["error"] = err
        return ok, info

    # -- live session migration ----------------------------------------

    def _handle_migrate(self, handler) -> None:
        """``POST /migrate``: export every live generation session and
        re-seat each on one of the target replicas (body ``{"targets":
        ["host:port", ...]}``, falling back to the configured
        ``migrate_targets``), completing the original client requests
        with the destination's bytes. Sessions that cannot be moved
        stay on the ordinary drain/preempt path — migration is
        strictly best-effort on top of it, never a new failure mode."""
        body = read_json_body(handler)
        if body is None:
            body = {}
        targets = body.get("targets") or list(self.migrate_targets)
        if not isinstance(targets, (list, tuple)):
            send_json(handler, 400, {"error": "'targets' must be a list"})
            return
        res = self._migrate_sessions(
            [str(t) for t in targets], self._deadline(handler)
        )
        send_json(handler, 200 if "error" not in res else 503, res)

    def _migrate_sessions(self, targets: list[str],
                          deadline: Deadline | None = None) -> dict:
        """Export every live generation session from the engine loop
        (see ``ServingEngine.export_sessions``) and push each to the
        first target that seats AND completes it. Completed sessions
        answer their original blocked clients with the destination's
        bytes; push failures retire the session through the ordinary
        cancelled-drain path with its partial tokens. Serialized under
        a lock: concurrent ``/migrate`` posts and the ``stop()`` path
        share one export mailbox."""
        targets = [t for t in targets if t]
        out = {"targets": list(targets), "exported": 0,
               "migrated": 0, "failed": 0}
        if not targets:
            out["error"] = "no migration targets"
            return out
        with self._migrate_lock:
            if (not self._engine_thread.is_alive()
                    or self._engine_dead.is_set()):
                out["error"] = "engine not running"
                return out
            evt = threading.Event()
            box: dict = {"evt": evt}
            self._migrate_box = box
            wait_s = (deadline.timeout(30.0) if deadline is not None
                      else 30.0)
            t_end = time.monotonic() + wait_s
            # the loop exits once drained-and-idle, so poll aliveness
            # rather than block the full window against a gone thread
            while not evt.is_set() and time.monotonic() < t_end:
                if (not self._engine_thread.is_alive()
                        or self._engine_dead.is_set()):
                    break
                evt.wait(0.05)
            if not evt.is_set():
                self._migrate_box = None
                out["error"] = "engine loop unavailable for export"
                return out
            if "error" in box:
                out["error"] = box["error"]
                return out
            sessions = box.get("sessions") or []
            out["exported"] = len(sessions)
            for sess in sessions:
                ok, info = self._push_session(sess, targets, deadline)
                if ok:
                    self.engine.complete_migrated(
                        sess["req"], info["tokens"],
                        n_streamed=sess["n_streamed"],
                    )
                    out["migrated"] += 1
                else:
                    self.engine.fail_migrated(
                        sess["req"],
                        info.get("error") or "migration push failed",
                        partial=sess["gen"]["tokens"],
                    )
                    out["failed"] += 1
        log_event(_log, "migrate",
                  exported=out["exported"], migrated=out["migrated"],
                  failed=out["failed"], n_targets=len(targets),
                  error=out.get("error"))
        return out

    def _push_session(self, sess: dict, targets: list[str],
                      deadline: Deadline | None = None,
                      ) -> tuple[bool, dict]:
        """POST one exported session frame to each target's
        ``/v1/kv_session`` until one seats and completes it. The
        idempotency key is derived from the request id, so a retry
        racing a slow-but-successful earlier attempt to the same
        replica is declined (409) instead of double-seated. Returns
        ``(ok, response)``; a successful response carries the full
        final token list."""
        req = sess["req"]
        frame = encode_segment(
            config_hash=sess["config_hash"], tokens=sess["tokens"],
            leaves=sess["leaves"], logits=sess["logits"],
            layout=sess["layout"], block_size=sess["block_size"],
            gen=sess["gen"],
        )
        last: dict = {}
        for target in targets:
            host, _, port = target.rpartition(":")
            t0 = time.perf_counter()
            span_id = new_span_id()
            err = None
            info: dict = {}
            status = 0
            try:
                conn = http.client.HTTPConnection(
                    host or "127.0.0.1", int(port),
                    timeout=(deadline.timeout(self.request_timeout_s)
                             if deadline is not None
                             else self.request_timeout_s),
                )
                headers = {
                    "Content-Type": "application/octet-stream",
                    IDEMPOTENCY_HEADER: "mig-" + req.id,
                }
                if deadline is not None:
                    headers[DEADLINE_HEADER] = deadline.header_value()
                if req.trace_id:
                    headers["traceparent"] = format_traceparent(
                        req.trace_id, span_id
                    )
                conn.request("POST", "/v1/kv_session", body=frame,
                             headers=headers)
                resp = conn.getresponse()
                raw = resp.read()
                conn.close()
                status = resp.status
                try:
                    info = json.loads(raw.decode("utf-8"))
                except (ValueError, UnicodeDecodeError):
                    info = {}
            except (OSError, ValueError) as e:
                err = repr(e)
            dt = time.perf_counter() - t0
            ok = (err is None and status == 200
                  and info.get("status") == "finished"
                  and isinstance(info.get("tokens"), list))
            if err is None and not ok:
                err = "http %d: %s" % (
                    status, info.get("reason") or info.get("error"),
                )
            if self.engine.tracer.enabled and req.trace_id:
                self.engine.tracer.span(
                    "migrate_push", "transfer", t0, dt, target=target,
                    nbytes=len(frame), ok=ok, trace_id=req.trace_id,
                    span_id=span_id,
                )
            self.engine.flight.record(
                "migrate_push", req_id=req.id, target=target, ok=ok,
                http=status or None, error=err,
            )
            log_event(_log, "session_migrate_push", req_id=req.id,
                      target=target, nbytes=len(frame), ok=ok,
                      seconds=round(dt, 6), error=err)
            if ok:
                return True, info
            last = dict(info)
            last["error"] = err
        return False, last

    def _hung(self, now: float | None = None) -> tuple[bool, float | None]:
        """(hung?, beat_age_s). Hung = the loop thread is alive but its
        heartbeat is older than ``hang_threshold_s`` while the engine
        has work (an idle loop beats every sleep poll, so silence there
        means wedged, not quiet — but we gate on non-idle anyway to be
        robust to a paused host clock)."""
        if self._last_beat is None:
            return False, None
        age = (now if now is not None else time.monotonic()) - self._last_beat
        hung = (age > self.hang_threshold_s
                and self._engine_thread.is_alive()
                and not self._stop.is_set()
                and not self.engine.idle)
        return hung, age

    def _health_payload(self) -> dict:
        alive = (self._engine_thread.is_alive()
                 and not self._engine_dead.is_set())
        # before start() the thread hasn't run yet; report configured
        # state rather than dead
        if not self._engine_thread.ident and not self._engine_dead.is_set():
            alive = True
        hung, beat_age = self._hung()
        if hung:
            alive = False  # wedged-in-device-call counts as not live
            if not self._hang_dumped:
                # one-shot postmortem on the first observed watchdog
                # trip: the wedged loop can't dump itself, so the
                # health probe that detects it does
                self._hang_dumped = True
                self._dump_flight("watchdog_hang")
        return {
            "ok": alive,
            "engine_alive": alive,
            "hung": hung,
            "beat_age_s": beat_age,
            "hang_threshold_s": self.hang_threshold_s,
            "draining": self._draining.is_set() or self._paused.is_set(),
            "last_error": self._last_error,
            "restarts": self.engine.metrics.n_restarts,
            # fleet fields: the controller routes on these (a restarted
            # replica with a different checkpoint shows a new hash)
            "config_hash": self.engine.config_hash,
            "queue_depth": len(self.engine.scheduler),
            "idle": self.engine.idle,
        }

    def _metrics_payload(self) -> dict:
        eng = self.engine
        out = eng.metrics.summary()
        out.update(
            n_slots=eng.n_slots,
            slots_active=eng.pool.n_active,
            queue_depth=len(eng.scheduler),
            draining=self._draining.is_set() or self._paused.is_set(),
            engine_alive=self._engine_thread.is_alive()
            and not self._engine_dead.is_set(),
            last_error=self._last_error,
        )
        if eng.prefix_cache is not None:
            out["prefix_cache"] = eng.prefix_cache.stats()
        if eng.tenancy is not None:
            buckets = {}
            for tid in eng.tenancy.tenant_ids():
                lvl = eng.tenancy.bucket_level(tid)
                if lvl is not None:
                    buckets[tid] = round(lvl, 1)
            out["tenancy"] = {
                "n_tenants": len(eng.tenancy),
                "bucket_levels": buckets,
            }
        return out

    def _engine_loop(self) -> None:
        consecutive = 0
        while not self._stop.is_set():
            self._last_beat = time.monotonic()
            box = self._migrate_box
            if box is not None:
                # session export runs HERE because slot/device state is
                # owned by this thread: between steps every slot is
                # quiescent, so the snapshot is exact by construction
                self._migrate_box = None
                try:
                    box["sessions"] = self.engine.export_sessions()
                except Exception as e:
                    box["error"] = f"{type(e).__name__}: {e}"
                box["evt"].set()
            try:
                progressed = self.engine.step()
                consecutive = 0
            except Exception as e:  # EngineCrash or an engine bug
                self._last_error = f"{type(e).__name__}: {e}"
                consecutive += 1
                # dump BEFORE recover(): recovery rebuilds engine state,
                # so this is the last look at the crashed configuration
                self._dump_flight("engine_crash")
                if consecutive > self.max_restarts:
                    self._die()
                    return
                try:
                    self.engine.recover()
                except Exception as e2:  # recovery itself is broken
                    self._last_error = (
                        f"recover failed: {type(e2).__name__}: {e2}"
                    )
                    self._die()
                    return
                continue
            if not progressed:
                if self._draining.is_set():
                    return  # drained: nothing queued, nothing decoding
                time.sleep(0.002)

    def _die(self) -> None:
        """Unrecoverable: mark dead and unblock every waiting caller."""
        self._engine_dead.set()
        self._dump_flight("engine_dead")
        try:
            self.engine.fail_all(f"engine dead: {self._last_error}")
        except Exception:
            pass  # state may be arbitrarily corrupt; handlers time out

    def start(self) -> "ServingServer":
        self._engine_thread.start()
        self._http_thread.start()
        if self._metrics_thread is not None:
            self._metrics_thread.start()
        return self

    def stop(self, drain_s: float = 0.0) -> None:
        """Shut down; with ``drain_s > 0`` drain first: admission stops
        immediately (new submits 503) and in-flight/queued work gets up
        to ``drain_s`` seconds to finish. Requests still running AT the
        drain deadline are live-migrated to ``migrate_targets`` when
        configured (their clients get full completions from the
        destination replica); leftovers are preempted (cancelled
        through the engine, so each straggler retires as CANCELLED with
        its partial stream and its handler answers 499) rather than
        decoded to completion."""
        self._draining.set()
        if drain_s > 0:
            deadline = time.monotonic() + drain_s
            while (time.monotonic() < deadline
                   and self._engine_thread.is_alive()
                   and not self._engine_dead.is_set()
                   and not self.engine.idle):
                time.sleep(0.005)
            if (self._engine_thread.is_alive()
                    and not self._engine_dead.is_set()
                    and not self.engine.idle
                    and self.migrate_targets):
                # drain deadline hit with live sessions: move them to a
                # healthy replica first — preemption below only gets
                # whatever migration could not seat
                try:
                    self._migrate_sessions(list(self.migrate_targets))
                except Exception as e:
                    log_event(_log, "migrate_on_stop_failed",
                              error=f"{type(e).__name__}: {e}")
            if (self._engine_thread.is_alive()
                    and not self._engine_dead.is_set()
                    and not self.engine.idle):
                # deadline hit with stragglers: cancel everything and
                # give the loop a short bounded grace to retire them
                # cleanly (one horizon each) before the hard stop below
                self.engine.preempt_all()
                grace = time.monotonic() + max(1.0, 0.1 * drain_s)
                while (time.monotonic() < grace
                       and self._engine_thread.is_alive()
                       and not self._engine_dead.is_set()
                       and not self.engine.idle):
                    time.sleep(0.005)
        self._stop.set()
        if self._engine_thread.ident:
            self._engine_thread.join(timeout=10)
        # anything that missed the drain window (still queued or
        # decoding) is failed NOW, so its blocked handler answers
        # immediately instead of hanging until the request timeout
        if not self._engine_dead.is_set() and not self.engine.idle:
            try:
                self.engine.fail_all("server stopped before completion")
            except Exception:
                pass
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._metrics_httpd is not None:
            self._metrics_httpd.shutdown()
            self._metrics_httpd.server_close()

    def serve_forever(self, drain_s: float = 0.0) -> None:
        """Blocking convenience for the CLI; Ctrl-C and SIGTERM both
        drain for ``drain_s`` seconds before exiting. SIGTERM (the
        orchestrator's kill) additionally dumps a flight bundle first —
        evictions are exactly when you want the postmortem."""
        self.start()
        done = threading.Event()

        def _on_sigterm(signum, frame):
            self._dump_flight("sigterm")
            done.set()

        try:
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            pass  # not the main thread (embedded use); Ctrl-C still works
        try:
            while not done.is_set():
                time.sleep(1)
        except KeyboardInterrupt:
            pass
        finally:
            self.stop(drain_s)
