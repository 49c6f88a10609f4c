"""App facade: build servers from config, register routes, run everything.

Parity with gofr `pkg/gofr/gofr.go`: ``App`` owns the HTTP server (with the
5-stage middleware chain), the metrics server on its own port, the gRPC server,
the pub/sub subscription manager, the cron table, and the CLI runtime — all fed
by one Container and serving handlers through one transport-neutral Context.

TPU-first: ``app.serve_model(...)`` registers a continuous-batching engine on
the container; handlers then call ``ctx.infer``/``ctx.generate``. ``run()`` adds
graceful shutdown (absent in the reference, `gofr.go:211`).
"""

from __future__ import annotations

import asyncio
import inspect
import math
import os
import signal
import threading
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

from aiohttp import web, WSMsgType

from gofr_tpu.config import DictConfig, EnvConfig
from gofr_tpu.container import Container
from gofr_tpu.fleet.chaos import fire as chaos_fire
from gofr_tpu.context import Context
from gofr_tpu import deadline
from gofr_tpu.http.errors import DeadlineExceeded, RequestTimeout
from gofr_tpu.http.middleware import (
    SPAN_KEY,
    cors_middleware,
    logging_middleware,
    metrics_middleware,
    tracer_middleware,
)
from gofr_tpu.http.request import HTTPRequest
from gofr_tpu.http.responder import respond, to_json
from gofr_tpu.http.streaming import RawStreamingResponse, StreamingResponse
from gofr_tpu.websocket import ConnectionHub, WSConnection

Handler = Callable[[Context], Any]


class App:
    def __init__(self, config_folder: str = "./configs", config=None, container: Container | None = None):
        self.config = config if config is not None else EnvConfig(folder=config_folder)
        self.container = container if container is not None else Container.create(self.config)
        self.logger = self.container.logger

        self.http_port = self.config.get_int("HTTP_PORT", 8000)
        self.metrics_port = self.config.get_int("METRICS_PORT", 2121)
        self.grpc_port = self.config.get_int("GRPC_PORT", 9000)
        self.request_timeout = self.config.get_float("REQUEST_TIMEOUT", 0.0)

        self._routes: list[tuple[str, str, Handler]] = []
        self._ws_routes: list[tuple[str, Handler]] = []
        self._static: list[tuple[str, str]] = []
        self._auth_middlewares: list[Any] = []
        self._subscriptions: dict[str, Handler] = {}
        self._grpc_services: list[Any] = []
        self._executor = ThreadPoolExecutor(
            max_workers=self.config.get_int("HANDLER_THREADS", 32), thread_name_prefix="gofr-handler"
        )
        self.ws_hub = ConnectionHub()

        from gofr_tpu.cron import Crontab

        self.cron = Crontab(self.container)
        if self.config.get_bool("QOS_ENABLED"):
            self.enable_qos()
        self._shutdown = asyncio.Event()
        self._runners: list[web.AppRunner] = []
        self._sub_threads: list[threading.Thread] = []
        self._sub_stop = threading.Event()
        self._gossip = None  # GossipReporter once enable_router_gossip runs
        self._cleanup: list[Callable[[], None]] = []
        # one /debug/profile capture at a time (409 while held): concurrent
        # jax.profiler.trace calls crash, and N stray curls must not pin N
        # handler threads for N×seconds each
        self._profile_busy = threading.Lock()

    # -- route registration (gofr.go:244-276) ----------------------------------

    def add_route(self, method: str, path: str, handler: Handler) -> None:
        self._routes.append((method.upper(), path, handler))

    def get(self, path: str, handler: Handler) -> None:
        self.add_route("GET", path, handler)

    def post(self, path: str, handler: Handler) -> None:
        self.add_route("POST", path, handler)

    def put(self, path: str, handler: Handler) -> None:
        self.add_route("PUT", path, handler)

    def patch(self, path: str, handler: Handler) -> None:
        self.add_route("PATCH", path, handler)

    def delete(self, path: str, handler: Handler) -> None:
        self.add_route("DELETE", path, handler)

    def websocket(self, path: str, handler: Handler) -> None:
        self._ws_routes.append((path, handler))

    def add_static_files(self, route: str, directory: str) -> None:
        self._static.append((route if route.startswith("/") else f"/{route}", directory))

    def add_rest_handlers(self, entity: type, table: str | None = None, path: str | None = None) -> None:
        """Reflect a dataclass into CRUD routes (gofr `crud_handlers.go`)."""
        from gofr_tpu.crud import register_crud_routes

        register_crud_routes(self, entity, table=table, path=path)

    # -- auth (gofr.go:436-507) ------------------------------------------------

    def enable_basic_auth(self, users: dict[str, str]) -> None:
        from gofr_tpu.http.middleware.auth import basic_auth_middleware

        self._auth_middlewares.append(basic_auth_middleware(users=users))

    def enable_basic_auth_with_validator(self, validator: Callable[..., bool]) -> None:
        from gofr_tpu.http.middleware.auth import basic_auth_middleware

        self._auth_middlewares.append(basic_auth_middleware(validator=validator, container=self.container))

    def enable_api_key_auth(self, *keys: str) -> None:
        from gofr_tpu.http.middleware.auth import apikey_auth_middleware

        self._auth_middlewares.append(apikey_auth_middleware(keys=list(keys)))

    def enable_api_key_auth_with_validator(self, validator: Callable[..., bool]) -> None:
        from gofr_tpu.http.middleware.auth import apikey_auth_middleware

        self._auth_middlewares.append(apikey_auth_middleware(validator=validator, container=self.container))

    def enable_oauth(self, jwks_url: str, refresh_interval: float = 300.0,
                     audience: str | None = None, issuer: str | None = None) -> None:
        from gofr_tpu.http.middleware.auth import JWKSCache, oauth_middleware

        jwks = JWKSCache(jwks_url, refresh_interval)
        jwks.start()
        self._auth_middlewares.append(oauth_middleware(jwks=jwks, audience=audience, issuer=issuer))

    def enable_jwt_hs256(self, secret: bytes | str, audience: str | None = None,
                         issuer: str | None = None) -> None:
        from gofr_tpu.http.middleware.auth import oauth_middleware

        secret_b = secret.encode() if isinstance(secret, str) else secret
        self._auth_middlewares.append(oauth_middleware(hs_secret=secret_b, audience=audience, issuer=issuer))

    # -- QoS: admission control / rate limiting / load shedding ----------------

    def enable_qos(self, policy=None, **overrides: Any):
        """Turn on the QoS subsystem (gofr_tpu.qos; also auto-enabled by
        ``QOS_ENABLED=true``): rate limits and load shedding at the HTTP
        middleware (429/503 + ``Retry-After``) and gRPC interceptor
        (``RESOURCE_EXHAUSTED``/``UNAVAILABLE``), weighted-fair priority
        scheduling and deadline-aware admission on every served engine.
        ``policy`` is a prebuilt ``QoSPolicy``; otherwise one is built from
        ``QOS_*`` config keys with ``overrides`` applied (docs/qos.md).
        Returns the AdmissionController."""
        from gofr_tpu.qos import AdmissionController, QoSPolicy

        if policy is None:
            policy = QoSPolicy.from_config(self.config, **overrides)
        controller = AdmissionController(policy, self.container.metrics, logger=self.logger)
        self.container.register_qos(controller)
        return controller

    def enable_router_gossip(self, name: str | None = None, url: str | None = None,
                             **kw: Any):
        """Make this replica visible to a data-plane router tier
        (gofr_tpu.router; docs/routing.md): a GossipReporter publishes this
        process's health/epoch/shed snapshot on the pubsub backbone every
        ``ROUTER_GOSSIP_INTERVAL_S``. Starts with ``run()`` (after the
        engines), publishes a terminal DOWN at shutdown. Returns the
        reporter, or None when no PUBSUB_BACKEND is wired."""
        if self.container.pubsub is None:
            self.logger.error("enable_router_gossip ignored: no PUBSUB_BACKEND configured")
            return None
        from gofr_tpu.router.gossip import GossipReporter

        self._gossip = GossipReporter(
            self.container, name=name,
            url=url or f"http://127.0.0.1:{self.http_port}", **kw)
        return self._gossip

    def on_cleanup(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` during graceful shutdown, before the container closes
        — how components bound to the app (the data-plane router's gossip
        subscription, custom pollers) stop with it."""
        self._cleanup.append(fn)

    # -- other entrypoints -----------------------------------------------------

    def subscribe(self, topic: str, handler: Handler) -> None:
        if self.container.pubsub is None:
            self.logger.error(f"subscribe({topic!r}) ignored: no PUBSUB_BACKEND configured")
            return
        self._subscriptions[topic] = handler

    def add_cron_job(self, schedule: str, name: str, handler: Handler) -> None:
        self.cron.add_job(schedule, name, handler)

    def register_grpc_service(self, adder: Callable[[Any], None] | Any, servicer: Any = None) -> None:
        """Register a gRPC service: either ``(add_fn, servicer)`` from generated
        code, or an object handled by the gofr_tpu.grpc server."""
        self._grpc_services.append((adder, servicer))

    def register_service(self, name: str, base_url: str, *options: Any):
        """Register an inter-service HTTP client (circuit breaker/retry/auth
        via options, gofr `service/new.go` decorator pattern)."""
        from gofr_tpu.service import new_http_service

        client = new_http_service(base_url, self.logger, self.container.metrics, *options)
        self.container.register_service(name, client)
        return client

    def migrate(self, migrations: dict[int, Any]) -> None:
        from gofr_tpu.migration import run_migrations

        run_migrations(migrations, self.container)

    # -- external datasource plugins (gofr `external_db.go:8-52` pattern) ------

    def add_mongo(self, client: Any) -> None:
        self.container.add_mongo(client)

    def add_cassandra(self, client: Any) -> None:
        self.container.add_cassandra(client)

    def add_clickhouse(self, client: Any) -> None:
        self.container.add_clickhouse(client)

    def add_kv_store(self, client: Any) -> None:
        self.container.add_kv_store(client)

    def add_file_store(self, provider: Any) -> None:
        """Swap the container's file datasource for a remote-FS provider
        (gofr ``file/file.go:69-78`` FileSystemProvider pattern): any object
        implementing the ``datasource.file.FileSystemProvider`` surface —
        S3/FTP/SFTP wrappers plug in here; handlers keep using ``ctx.file``
        unchanged."""
        self.container.add_file_store(provider)

    # -- TPU model serving (the new capability) --------------------------------

    def serve_model(self, name: str, spec: Any = None, *, engine: Any = None, **engine_kw: Any):
        """Attach a model to the app behind a continuous-batching engine.

        ``spec`` is a ModelSpec (see gofr_tpu.models); alternatively pass a
        prebuilt ``engine``. The engine starts with ``app.run()`` (or
        immediately when the app is already running) and is reachable from any
        handler via ``ctx.infer(name, ...)`` / ``ctx.generate(name, ...)``.
        """
        if engine is None:
            from gofr_tpu.tpu.engine import build_engine

            engine = build_engine(spec, self.container, **engine_kw)
        self.container.register_engine(name, engine)
        return engine

    # -- assembly --------------------------------------------------------------

    def _registered_methods(self) -> list[str]:
        methods = sorted({m for m, _, _ in self._routes} | {"OPTIONS"})
        return methods

    def _build_http_app(self) -> web.Application:
        middlewares = [
            tracer_middleware(self.container.tracer),
            logging_middleware(self.logger),
            cors_middleware(self.config, self._registered_methods),
            metrics_middleware(self.container.metrics),
        ]
        if self.container.qos is not None:
            # after metrics (rejections must show in app_http_response),
            # before auth — admission is cheaper than signature checks, so
            # shed load never pays the auth path
            from gofr_tpu.http.middleware import qos_middleware

            middlewares.append(qos_middleware(self.container.qos))
        middlewares.extend(self._auth_middlewares)
        http_app = web.Application(middlewares=middlewares, client_max_size=64 * 1024 * 1024)

        # well-known routes (gofr.go:155-163)
        http_app.router.add_get("/.well-known/health", self._health_handler)
        http_app.router.add_get("/.well-known/alive", self._alive_handler)
        http_app.router.add_get("/favicon.ico", self._favicon_handler)
        self._add_openapi_routes(http_app)
        if self._debug_env():
            # profiling tier, gated like the reference's pprof routes
            # (http_server.go:53-60): trace capture on demand, plus the
            # always-recording flight recorder's read endpoints
            http_app.router.add_get("/debug/profile", self._profile_handler)
            http_app.router.add_get("/debug/requests", self._debug_requests_handler)
            http_app.router.add_get("/debug/engine", self._debug_engine_handler)
            http_app.router.add_get("/debug/perf", self._debug_perf_handler)
            http_app.router.add_get("/debug/quality", self._debug_quality_handler)
            http_app.router.add_get("/debug/control", self._debug_control_handler)

        for method, path, handler in self._routes:
            http_app.router.add_route(method, path, self._wrap(handler))
        for path, handler in self._ws_routes:
            http_app.router.add_get(path, self._wrap_ws(handler))
        for route, directory in self._static:
            http_app.router.add_get(
                f"{route}/{{static_tail:.*}}", self._static_handler(directory))
        # catch-all 404 with the JSON envelope (gofr handler.go:95-119)
        http_app.router.add_route("*", "/{tail:.*}", self._not_found_handler)
        return http_app

    def _build_metrics_app(self) -> web.Application:
        metrics_app = web.Application()

        async def metrics_handler(_request: web.Request) -> web.Response:
            text = self.container.metrics.expose_text()
            return web.Response(text=text, content_type="text/plain", charset="utf-8")

        metrics_app.router.add_get("/metrics", metrics_handler)
        return metrics_app

    # -- request pipeline ------------------------------------------------------

    async def _materialize(self, request: web.Request) -> HTTPRequest:
        body = await request.read()
        route = request.match_info.route
        template = getattr(route.resource, "canonical", request.path) if route and route.resource else request.path
        req = HTTPRequest(
            method=request.method,
            path=request.path,
            query_string=request.rel_url.query_string,
            headers=dict(request.headers),
            body=body,
            path_params=dict(request.match_info),
            remote=request.remote or "",
            route_template=template,
        )
        auth = request.get("gofr_auth")
        if auth:
            req.context().update(auth)
        qos_class = request.get("gofr_qos_class")
        if qos_class:
            # resolved by the QoS middleware; ctx.generate/infer pick it up
            # so handlers need no QoS-awareness to schedule correctly
            req.context()["qos_class"] = qos_class
        # request-lifetime plane (docs/resilience.md): the client's absolute
        # deadline, converted once to the monotonic domain; ctx.generate
        # folds the remaining budget into the engine timeout
        deadline.set_deadline(
            req.context(),
            deadline.parse_deadline_ms(req.headers.get(deadline.DEADLINE_HEADER)))
        return req

    def _wrap(self, handler: Handler):
        is_coro = inspect.iscoroutinefunction(handler)

        async def aio_handler(request: web.Request) -> web.Response:
            req = await self._materialize(request)
            ctx = Context(req, self.container, span=request.get(SPAN_KEY))
            result, err = None, None
            # effective budget: the server-side request_timeout and the
            # client's propagated deadline, whichever is tighter. An
            # already-expired deadline is shed here, before the handler
            # (and any engine submit) runs at all.
            remaining = deadline.remaining(req.context())
            deadline_bound = False
            if remaining is not None and remaining <= 0:
                self.container.metrics.increment_counter(
                    "app_request_deadline_exceeded_total", 1, where="edge")
                err = DeadlineExceeded("request deadline already expired")
                remaining = None
            budget = self.request_timeout if self.request_timeout > 0 else None
            if remaining is not None and (budget is None or remaining < budget):
                budget, deadline_bound = remaining, True
            try:
                if err is None:
                    if is_coro:
                        coro = handler(ctx)
                    else:
                        loop = asyncio.get_running_loop()
                        coro = loop.run_in_executor(self._executor, handler, ctx)
                    if budget is not None:
                        result = await asyncio.wait_for(coro, timeout=budget)
                    else:
                        result = await coro
            except asyncio.TimeoutError:
                if deadline_bound:
                    # the CLIENT's clock ran out, not ours: 504, and any
                    # engine work this context submitted is cancelled so
                    # slots/pages stop burning for an answer nobody reads
                    self.container.metrics.increment_counter(
                        "app_request_deadline_exceeded_total", 1, where="edge")
                    ctx.cancel_inflight("deadline")
                    err = DeadlineExceeded()
                else:
                    ctx.cancel_inflight("timeout")
                    err = RequestTimeout()
            except asyncio.CancelledError:
                # client closed the socket mid-handler: propagate to every
                # engine Request this context submitted (cooperative
                # cancellation, docs/resilience.md), then let aiohttp
                # finish tearing the transport down
                ctx.cancel_inflight("client_disconnect")
                raise
            except Exception as e:  # noqa: BLE001
                err = e
                if not hasattr(e, "status_code"):
                    self.logger.log_exception(e, f"handler {request.method} {request.path}")
            if err is None and isinstance(result, RawStreamingResponse):
                return await self._stream_raw(request, result)
            if err is None and isinstance(result, StreamingResponse):
                return await self._stream_sse(request, result)
            wire = respond(result, err, request.method)
            # a header-borne Content-Type (proxy Passthrough: the replica's
            # verbatim value, parameters included) wins — aiohttp rejects
            # parameterized values in the content_type argument
            has_ct = any(k.lower() == "content-type" for k in wire.headers)
            return web.Response(
                body=wire.body,
                status=wire.status,
                content_type=None if has_ct else wire.content_type,
                headers=wire.headers,
            )

        return aio_handler

    async def _stream_sse(self, request: web.Request, stream: StreamingResponse) -> web.StreamResponse:
        """Drive a StreamingResponse as text/event-stream. Items are pulled
        on the executor (the engine's stream queue blocks); each flush makes
        the token visible to the client before generation finishes."""
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "X-Accel-Buffering": "no"},
        )
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        sentinel = object()
        try:
            while True:
                item = await loop.run_in_executor(self._executor, next, stream.iterator, sentinel)
                if item is sentinel:
                    break
                # chaos point "client.disconnect" (drop action): the storm
                # drill's deterministic mid-stream client hangup — exercises
                # the REAL disconnect path below, not a shortcut around it
                if chaos_fire("client.disconnect"):
                    raise ConnectionResetError("chaos: injected client disconnect")
                await resp.write(stream.encode_sse(item))
            await resp.write(StreamingResponse.sse_done())
        except (ConnectionResetError, ConnectionError, asyncio.CancelledError):
            # client went away mid-decode: cancel the generation so the
            # engine frees the slot/pages instead of decoding for a ghost
            self._cancel_stream(stream)
            raise
        except Exception as e:  # noqa: BLE001 - surface mid-stream failure in-band
            self.logger.log_exception(e, "sse stream")
            self._cancel_stream(stream)
            try:
                await resp.write(StreamingResponse.sse_error(str(e)))
            except Exception:  # noqa: BLE001 - client already gone
                return resp
        try:
            await resp.write_eof()
        except Exception:  # noqa: BLE001 - broken transport on eof
            pass
        return resp

    async def _stream_raw(self, request: web.Request, stream: RawStreamingResponse) -> web.StreamResponse:
        """Drive a RawStreamingResponse: write the handler's wire chunks
        through verbatim (proxy passthrough — the router's SSE hop). Chunks
        are pulled on the executor (the upstream read blocks); a client
        disconnect closes the upstream iterator so the proxied transfer is
        aborted, not drained."""
        headers = {k: v for k, v in stream.headers.items()
                   if k.lower() not in ("content-length", "transfer-encoding",
                                        "connection", "content-encoding")}
        if not any(k.lower() == "content-type" for k in headers):
            headers["Content-Type"] = stream.content_type
        resp = web.StreamResponse(status=stream.status, headers=headers)
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        sentinel = object()
        try:
            while True:
                chunk = await loop.run_in_executor(self._executor, next, stream.iterator, sentinel)
                if chunk is sentinel:
                    break
                if chaos_fire("client.disconnect"):
                    raise ConnectionResetError("chaos: injected client disconnect")
                if chunk:
                    await resp.write(chunk)
        except (ConnectionResetError, ConnectionError, asyncio.CancelledError):
            stream.close()
            raise
        except Exception as e:  # noqa: BLE001 - upstream died mid-proxy; the
            # status line is already on the wire, so all we can do is stop
            self.logger.log_exception(e, "raw stream proxy")
            stream.close()
        try:
            await resp.write_eof()
        except Exception:  # noqa: BLE001 - broken transport on eof
            pass
        return resp

    @staticmethod
    def _cancel_stream(stream: StreamingResponse) -> None:
        cancel = getattr(stream.iterator, "cancel", None)
        if callable(cancel):
            cancel()

    def _wrap_ws(self, handler: Handler):
        is_coro = inspect.iscoroutinefunction(handler)

        async def ws_handler(request: web.Request) -> web.StreamResponse:
            ws = web.WebSocketResponse()
            if not ws.can_prepare(request).ok:
                return await self._not_found_handler(request)
            await ws.prepare(request)
            # server-generated id: the Sec-WebSocket-Key header is client
            # controlled and duplicates would cross-wire hub entries
            conn_id = uuid.uuid4().hex
            self.ws_hub.add(conn_id, ws)
            loop = asyncio.get_running_loop()
            try:
                async for msg in ws:
                    if msg.type not in (WSMsgType.TEXT, WSMsgType.BINARY):
                        continue
                    conn = WSConnection(conn_id, ws, msg.data, loop)
                    ctx = Context(conn, self.container)
                    try:
                        if is_coro:
                            result = await handler(ctx)
                        else:
                            result = await loop.run_in_executor(self._executor, handler, ctx)
                    except Exception as e:  # noqa: BLE001
                        self.logger.log_exception(e, "websocket handler")
                        await ws.send_str(to_json({"error": {"message": "handler error"}}).decode())
                        continue
                    if isinstance(result, StreamingResponse):
                        # token streaming: one ws message per item, pulled on
                        # the executor (websocket.go:37-53 parity, per-token).
                        # A mid-stream engine error becomes an in-band error
                        # frame — the connection survives; a transport error
                        # cancels the generation so the slot is freed.
                        sentinel = object()
                        try:
                            while True:
                                item = await loop.run_in_executor(
                                    self._executor, next, result.iterator, sentinel)
                                if item is sentinel:
                                    break
                                await ws.send_str(result.encode_ws(item))
                            await ws.send_str(to_json({"done": True}).decode())
                        except (ConnectionResetError, ConnectionError, asyncio.CancelledError):
                            self._cancel_stream(result)
                            raise
                        except Exception as e:  # noqa: BLE001
                            self.logger.log_exception(e, "websocket token stream")
                            self._cancel_stream(result)
                            await ws.send_str(to_json(
                                {"error": {"message": str(e)}, "done": True}).decode())
                    elif result is not None:
                        payload = result if isinstance(result, str) else to_json(result).decode()
                        await ws.send_str(payload)
            finally:
                self.ws_hub.remove(conn_id)
            return ws

        return ws_handler

    # -- built-in handlers -----------------------------------------------------

    async def _health_handler(self, _request: web.Request) -> web.Response:
        health = await asyncio.get_running_loop().run_in_executor(self._executor, self.container.health)
        status = 200 if health["status"] != "DOWN" else 503
        return web.Response(body=to_json({"data": health}), status=status, content_type="application/json")

    async def _alive_handler(self, _request: web.Request) -> web.Response:
        return web.json_response({"data": {"status": "UP"}})

    async def _favicon_handler(self, _request: web.Request) -> web.Response:
        return web.Response(body=b"", content_type="image/x-icon")

    async def _not_found_handler(self, _request: web.Request) -> web.Response:
        return web.json_response({"error": {"message": "route not registered"}}, status=404)

    def _static_handler(self, directory: str):
        """Static file serving with the reference's hardening
        (`http/router.go:62-82`): ``openapi.json`` must never be fetchable
        through a static mount — the spec is served, access-controlled and
        versioned, at ``/.well-known/openapi.json`` only — so a direct
        download attempt gets 403; path traversal out of the mounted
        directory gets 404 like any other absent file."""
        import pathlib

        base = pathlib.Path(directory).resolve()

        async def handler(request: web.Request) -> web.StreamResponse:
            tail = request.match_info.get("static_tail", "")
            if pathlib.PurePosixPath(tail).name == "openapi.json":
                return web.json_response(
                    {"error": {"message": "openapi.json is not downloadable from "
                                          "static routes; use /.well-known/openapi.json"}},
                    status=403)
            try:
                target = (base / tail).resolve()
            except (OSError, ValueError):
                return await self._not_found_handler(request)
            if base not in target.parents and target != base:
                return await self._not_found_handler(request)
            if not target.is_file():
                return await self._not_found_handler(request)
            return web.FileResponse(target)

        return handler

    # -- profiling (SURVEY §5.1; reference http_server.go:53-60) ---------------

    def _debug_env(self) -> bool:
        return self.config.get_or_default("APP_ENV", "").upper() == "DEBUG"

    def _profiler_port_base(self) -> int | None:
        """Resolve PROFILER_PORT: an explicit port, ``auto`` (derived from
        the serving port, so co-hosted replicas with distinct HTTP_PORTs
        get distinct profiler ports for free), or <=0/garbage = disabled."""
        raw = str(self.config.get_or_default("PROFILER_PORT", "9999")).strip().lower()
        if raw == "auto":
            return self.http_port + 1999  # default HTTP 8000 -> classic 9999
        try:
            base = int(raw)
        except ValueError:
            self.logger.warn(f"PROFILER_PORT {raw!r} is not a port or 'auto'; "
                             "profiler server disabled")
            return None
        return base if base > 0 else None

    @staticmethod
    def _bindable_port(base: int, tries: int = 16) -> int | None:
        """First bindable port in [base, base+tries): N replicas sharing a
        host (and a PROFILER_PORT default) each walk to a free port instead
        of the second-and-later ones logging a bind failure every boot."""
        import socket

        for port in range(base, base + tries):
            try:
                with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
                    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                    s.bind(("0.0.0.0", port))
                return port
            except OSError:
                continue
        return None

    def _start_profiler_server(self) -> None:
        """jax.profiler gRPC server for live tensorboard/xprof attach, on
        PROFILER_PORT (<=0 disables, 'auto' derives from the serving port;
        a busy port retries upward). DEBUG-gated like the pprof routes."""
        base = self._profiler_port_base()
        if base is None:
            return
        port = self._bindable_port(base)
        if port is None:
            self.logger.warn(f"no free profiler port in [{base}, {base + 16}); "
                             "profiler server disabled")
            return
        try:
            import jax

            jax.profiler.start_server(port)
            self.logger.infof("jax profiler server on :%d (APP_ENV=DEBUG)", port)
        except Exception as e:  # noqa: BLE001 - profiling must never block serving
            self.logger.warn(f"profiler server failed to start: {e}")

    async def _profile_handler(self, request: web.Request) -> web.Response:
        """GET /debug/profile?seconds=N → capture an xplane trace of whatever
        the engines/handlers are doing for N seconds; returns the trace dir
        (open with tensorboard/xprof). Bounded so a stray curl can't pin the
        process or fill disk: absurd N is a 400 (sane N still clamps to
        [0.1, 60]), and only ONE capture runs at a time — 409 while busy."""
        try:
            seconds = float(request.query.get("seconds", "2"))
            if not math.isfinite(seconds):
                raise ValueError(seconds)
        except ValueError:
            return web.json_response(
                {"error": {"message": "seconds must be a finite number"}}, status=400)
        if seconds <= 0 or seconds > 300.0:
            return web.json_response(
                {"error": {"message": "seconds must be in (0, 300]"}}, status=400)
        seconds = min(max(seconds, 0.1), 60.0)
        if not self._profile_busy.acquire(blocking=False):
            return web.json_response(
                {"error": {"message": "a profile capture is already running"}},
                status=409)
        out_root = self.config.get_or_default("PROFILER_DIR", "/tmp/gofr_tpu_profile")

        def capture() -> str:
            import time as _time

            import jax

            path = os.path.join(out_root, _time.strftime("trace-%Y%m%d-%H%M%S"))
            with jax.profiler.trace(path):
                _time.sleep(seconds)
            return path

        loop = asyncio.get_running_loop()
        try:
            path = await loop.run_in_executor(self._executor, capture)
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": {"message": str(e)}}, status=500)
        finally:
            self._profile_busy.release()
        return web.json_response({"data": {"trace_dir": path, "seconds": seconds}})

    @staticmethod
    def _debug_limit(request: web.Request) -> int | None:
        try:
            n = int(request.query.get("n", "0"))
        except ValueError:
            n = 0
        return n if n > 0 else None

    async def _debug_requests_handler(self, request: web.Request) -> web.Response:
        """GET /debug/requests?n=K → the last K completed request timelines
        (newest first) from the always-on flight recorder: queue wait, TTFT,
        TPOT, e2e, slot, preemptions, trace id — incident diagnosis without
        a trace backend attached (docs/observability.md)."""
        entries = self.container.flight.requests(limit=self._debug_limit(request))
        return web.json_response({"data": {"count": len(entries), "requests": entries}})

    async def _debug_engine_handler(self, request: web.Request) -> web.Response:
        """GET /debug/engine?n=K → the last K device steps (kind, wall time,
        batch occupancy, compile signature, backlog) plus a health snapshot
        of every served engine, including which backend serves its decode
        op (``engine.autotune_report()``; docs/kernels.md)."""
        steps = self.container.flight.steps(limit=self._debug_limit(request))
        engines = {}
        for name, engine in self.container.engines.items():
            snap = engine.health_check() if hasattr(engine, "health_check") else {}
            layout = getattr(engine, "kv_layout", None)
            if layout is not None:
                # the KV-pool dimension a kv-dtype A/B flips (ENGINE_KV_DTYPE;
                # docs/kernels.md): '' quantize means the dense bf16 pool
                snap = dict(snap)
                snap["kv"] = {
                    "layout": layout,
                    "dtype": getattr(engine, "kv_quantize", "") or "bf16",
                }
            report = getattr(engine, "autotune_report", None)
            rep = report() if report is not None else None
            if rep:
                snap = dict(snap)
                snap["autotune"] = rep
            ad_stats = getattr(engine, "adapter_stats", None)
            if callable(ad_stats):
                # adapter plane occupancy + the base-weight epoch
                # (gofr_tpu.adapters; docs/serving.md)
                snap = dict(snap)
                snap["adapters"] = ad_stats()
            engines[name] = snap
        return web.json_response(
            {"data": {"count": len(steps), "steps": steps, "engines": engines}})

    async def _debug_perf_handler(self, request: web.Request) -> web.Response:
        """GET /debug/perf → the live roofline view (metrics/perf.py): per
        engine a windowed MFU/MBU snapshot per step kind, the pipeline
        bubble ratio, the page-pool waste stats, and the engine's decode op
        joined with the roofline estimate of the step kind it runs in —
        "is the kernel the bottleneck, or is the device starved?"
        answered from one endpoint (docs/observability.md)."""
        import time as _time

        now = _time.monotonic()
        engines = {}
        for name, engine in self.container.engines.items():
            plane = getattr(engine, "perf", None)
            if plane is None:
                continue
            snap = plane.snapshot(now)
            stats_fn = getattr(engine, "page_pool_stats", None)
            stats = stats_fn() if callable(stats_fn) else None
            if stats:
                snap["page_pool"] = stats
            report = getattr(engine, "autotune_report", None)
            rep = report() if report is not None else None
            if rep and rep.get("decisions"):
                # the reported op is the decode step's, so it joins the
                # "decode" kind's roofline; spec engines fold the same op
                # inside "spec" steps too
                kinds = snap.get("kinds", {})
                joined = {}
                for op, rec in rep["decisions"].items():
                    roof = {k: kinds[k] for k in ("decode", "spec")
                            if k in kinds}
                    joined[op] = {"pin": rec, "roofline": roof or None}
                snap["autotune"] = joined
            ho_fn = getattr(engine, "handoff_stats", None)
            ho = ho_fn() if callable(ho_fn) else None
            if ho and ("export" in ho or "import" in ho):
                # disaggregation transfer plane (tpu/handoff.py): mode,
                # negotiated stream count, per-stream bytes/seconds and
                # the overlap ratio join the roofline view — "is the
                # handoff hiding behind prefill compute?" from the same
                # endpoint as "is the device starved?"
                snap["handoff"] = ho
            engines[name] = snap
        totals = self.container.perf_totals()
        fleet = None
        if totals is not None:
            from gofr_tpu.metrics import perf as perf_mod

            fleet = {"totals": totals, **perf_mod.derive(totals)}
        return web.json_response({"data": {"engines": engines, "rollup": fleet}})

    async def _debug_control_handler(self, request: web.Request) -> web.Response:
        """GET /debug/control → the online step controller's live state
        (gofr_tpu.control; docs/serving.md): per engine the knob vector
        with each knob's allowed range and frozen flag, the persisted pins
        for this replica's (kv dtype, device kind, shard) context, the
        hysteresis gate internals, the in-progress trial, the last judged
        evidence window, and the bounded decision ring — "who changed what
        knob, when, and on what evidence" answered with nothing but curl.
        Engines without a controller report {enabled: false} plus their
        static knob vector so the fleet view stays uniform."""
        engines = {}
        for name, engine in self.container.engines.items():
            report = getattr(engine, "control_report", None)
            if callable(report):
                engines[name] = report()
        decisions = self.container.flight.controls(
            limit=self._debug_limit(request))
        return web.json_response(
            {"data": {"engines": engines, "decisions": decisions}})

    async def _debug_quality_handler(self, request: web.Request) -> web.Response:
        """GET /debug/quality → the numerics/quality plane joined with the
        serving state that produced it (metrics/quality.py; docs/
        observability.md): per engine the shadow-scorer totals and recent
        per-sample divergence reports keyed by decode backend, weights epoch
        and kv dtype, the per-adapter speculative-decode acceptance ratios
        (the always-on quality proxy), and each class's quality SLO windows
        — "are the tokens still right, and if not, since when and under
        which configuration" answered from one endpoint."""
        engines = {}
        for name, engine in self.container.engines.items():
            entry: dict = {}
            snap_fn = getattr(engine, "quality_snapshot", None)
            snap = snap_fn() if callable(snap_fn) else None
            if snap is not None:
                # trim replay payloads off the live view; bundles carry them
                snap = dict(snap)
                snap["recent"] = [
                    {k: v for k, v in e.items() if k not in ("prompt", "emitted")}
                    for e in snap.get("recent", [])]
                entry["shadow"] = snap
            totals_fn = getattr(engine, "spec_accept_totals", None)
            totals = totals_fn() if callable(totals_fn) else None
            if totals:
                entry["spec_accept"] = {
                    adapter: {
                        "accepted": acc, "proposed": prop,
                        "ratio": round(acc / prop, 4) if prop else None,
                    } for adapter, (acc, prop) in totals.items()}
            if entry:
                engines[name] = entry
        slo = getattr(self.container, "slo", None)
        objectives = None
        if slo is not None:
            objectives = {
                cls: {"quality": objs["quality"]}
                for cls, objs in slo.snapshot().items() if "quality" in objs}
        return web.json_response(
            {"data": {"engines": engines, "slo": objectives}})

    def _add_openapi_routes(self, http_app: web.Application) -> None:
        from gofr_tpu.swagger import openapi_handler, swagger_ui_handler

        http_app.router.add_get("/.well-known/openapi.json", openapi_handler(self))
        http_app.router.add_get("/.well-known/swagger", swagger_ui_handler(self))

    # -- subscription manager (gofr subscriber.go) -----------------------------

    def _start_subscribers(self) -> None:
        # SUBSCRIBER_WORKERS > 1 runs N consumer threads per topic — the
        # consumer-group-partition parallelism analog (subscriber.go spawns
        # one goroutine per topic). With a model engine in the handler, the
        # concurrent handlers are what lets the engine micro-batch: N
        # in-flight messages fill one device batch instead of serializing.
        workers = max(1, self.config.get_int("SUBSCRIBER_WORKERS", 1))
        for topic, handler in self._subscriptions.items():
            for w in range(workers):
                t = threading.Thread(
                    target=self._subscribe_loop, args=(topic, handler),
                    name=f"gofr-sub-{topic}-{w}", daemon=True,
                )
                t.start()
                self._sub_threads.append(t)

    def _subscribe_loop(self, topic: str, handler: Handler) -> None:
        container = self.container
        group = self.config.get_or_default("CONSUMER_GROUP", container.app_name)
        while not self._sub_stop.is_set():
            try:
                msg = container.pubsub.subscribe(topic, group=group, timeout=0.5)
            except Exception as e:  # noqa: BLE001
                container.logger.errorf("subscribe %s failed: %r", topic, e)
                self._sub_stop.wait(1.0)
                continue
            if msg is None:
                continue
            container.metrics.increment_counter("app_pubsub_subscribe_total_count", 1, topic=topic)
            # join the publisher's trace when the message carries one
            # (Context.publish stamps traceparent into the broker headers)
            span = container.tracer.start_span(
                f"subscribe {topic}", kind="CONSUMER", set_current=False,
                traceparent=msg.param("traceparent") or None)
            ctx = Context(msg, container, span=span)
            try:
                result = handler(ctx)
                if inspect.iscoroutine(result):
                    raise TypeError("subscribe handlers must be synchronous (they run on a consumer thread)")
                # chaos point "pubsub.commit": the crash-between-handler-
                # and-commit window — the at-least-once contract's hard
                # case (handler effects applied, offset not advanced, so
                # the message is redelivered; fleet/chaos.py, tested in
                # tests/test_pubsub_clients.py). Zero-cost when unarmed.
                chaos_fire("pubsub.commit", topic=topic)
                msg.commit()  # at-least-once: commit only on success (subscriber.go:54-56)
                container.metrics.increment_counter("app_pubsub_subscribe_success_count", 1, topic=topic)
                span.set_status("OK")
            except Exception as e:  # noqa: BLE001
                span.set_status("ERROR")
                container.logger.errorf("subscriber for %s failed: %r", topic, e)
            finally:
                span.finish()

    # -- run -------------------------------------------------------------------

    def run(self) -> None:
        """Start every configured server; blocks until SIGINT/SIGTERM."""
        try:
            asyncio.run(self.arun())
        except KeyboardInterrupt:
            pass

    async def arun(self, ready: asyncio.Event | None = None) -> None:
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, self._shutdown.set)
            except (NotImplementedError, RuntimeError):
                pass

        if self._debug_env():
            self._start_profiler_server()

        # engines first (device warm-up), then servers. ENGINE_WARMUP=true
        # front-loads every program compile
        # (docs/serving.md: seconds at boot instead of inside the first
        # requests' latency window; generate engines need no example).
        warm = self.config.get_or_default("ENGINE_WARMUP", "false").lower() == "true"
        for name, engine in self.container.engines.items():
            if warm and hasattr(engine, "warmup"):
                # signature-probed, NOT try/except TypeError around the call
                # — that would conflate "needs an example input" (BatchEngine;
                # app boot has none, first traffic compiles as before) with a
                # genuine TypeError from inside warmup (same rationale as
                # container._pubsub_supports_headers)
                import inspect

                try:
                    needs_example = any(
                        p.default is p.empty
                        and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                        for p in inspect.signature(engine.warmup).parameters.values())
                except (TypeError, ValueError):
                    needs_example = True
                if not needs_example:
                    # a warmup that raises stops the boot: the operator
                    # asked for every program compiled before traffic, and
                    # a program that fails to compile here would fail (or
                    # crash-loop the device thread) inside a request
                    n = engine.warmup()
                    self.logger.infof("model engine %s warmed (%d programs)", name, n)
            if hasattr(engine, "start"):
                engine.start()
                self.logger.infof("model engine %s started", name)

        metrics_runner = web.AppRunner(self._build_metrics_app())
        await metrics_runner.setup()
        await web.TCPSite(metrics_runner, host="0.0.0.0", port=self.metrics_port).start()
        self._runners.append(metrics_runner)
        self.logger.infof("metrics server on :%d/metrics", self.metrics_port)

        if self._routes or self._ws_routes or self._static or self._debug_env():
            http_runner = web.AppRunner(self._build_http_app())
            await http_runner.setup()
            await web.TCPSite(http_runner, host="0.0.0.0", port=self.http_port).start()
            self._runners.append(http_runner)
            self.logger.infof("HTTP server on :%d", self.http_port)

        grpc_server = None
        if self._grpc_services:
            from gofr_tpu.grpc.server import start_grpc_server

            grpc_server = start_grpc_server(self)
            self.logger.infof("gRPC server on :%d", self.grpc_port)

        self._start_subscribers()
        self.cron.start()
        if self._gossip is not None:
            # after the engines: the first snapshot reports real health
            self._gossip.start()

        if ready is not None:
            ready.set()
        await self._shutdown.wait()
        self.logger.info("shutting down")
        if self._gossip is not None:
            self._gossip.stop()  # terminal DOWN leaves the router ring now
        for fn in self._cleanup:
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - one hook must not block the rest
                self.logger.log_exception(e, "cleanup hook")
        self._sub_stop.set()
        self.cron.stop()
        if grpc_server is not None:
            grpc_server.stop(grace=2)
        for runner in self._runners:
            await runner.cleanup()
        self.container.close()
        self._executor.shutdown(wait=False, cancel_futures=True)

    def stop(self) -> None:
        self._shutdown.set()


def new(config_folder: str = "./configs", config=None) -> App:
    """gofr.New() analog."""
    return App(config_folder=config_folder, config=config)


def new_cmd(config_folder: str = "./configs", config=None):
    """gofr.NewCMD() analog: a CLI app sharing the container/Context model."""
    from gofr_tpu.cli import CmdApp

    cfg = config if config is not None else EnvConfig(folder=config_folder)
    return CmdApp(Container.create(cfg))


def new_testing(config: dict[str, str] | None = None) -> App:
    """App wired to a mock container for tests."""
    from gofr_tpu.container import new_mock_container

    cfg = DictConfig(config or {})
    return App(config=cfg, container=new_mock_container(config))
