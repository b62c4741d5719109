"""Serve tests (reference analogues: serve/tests/test_standalone.py,
test_batching.py, test_autoscaling_policy.py)."""
import asyncio
import urllib.error
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import AutoscalingConfig


@pytest.fixture
def serve_rt(rt):
    yield rt
    serve.shutdown()


def test_class_deployment_call(serve_rt):
    @serve.deployment
    class Greeter:
        def __init__(self, greeting):
            self.greeting = greeting

        def __call__(self, name):
            return f"{self.greeting}, {name}!"

        def shout(self, name):
            return f"{self.greeting.upper()} {name.upper()}"

    handle = serve.run(Greeter.bind("Hello"))
    assert ray_tpu.get(handle.remote("world")) == "Hello, world!"
    assert ray_tpu.get(handle.shout.remote("hi")) == "HELLO HI"


def test_function_deployment(serve_rt):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind())
    assert ray_tpu.get(handle.remote(21)) == 42


def test_multiple_replicas_round_robin(serve_rt):
    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __init__(self):
            self.id = id(self)

        def __call__(self):
            return self.id

    handle = serve.run(WhoAmI.bind())
    seen = {ray_tpu.get(handle.remote()) for _ in range(60)}
    assert len(seen) == 3   # all replicas served traffic


def test_redeploy_updates_version(serve_rt):
    @serve.deployment
    class V:
        def __init__(self, version):
            self.v = version

        def __call__(self):
            return self.v

    h = serve.run(V.bind(1))
    assert ray_tpu.get(h.remote()) == 1
    h = serve.run(V.bind(2))
    deadline = time.time() + 10
    while time.time() < deadline:
        if ray_tpu.get(h.remote()) == 2:
            break
        time.sleep(0.05)
    assert ray_tpu.get(h.remote()) == 2


def test_deployment_error_propagates(serve_rt):
    @serve.deployment
    class Bad:
        def __call__(self):
            raise ValueError("replica error")

    h = serve.run(Bad.bind())
    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(h.remote())


def test_batching(serve_rt):
    batch_sizes = []

    @serve.deployment(max_ongoing_requests=32)
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        async def __call__(self, items):
            batch_sizes.append(len(items))
            return [i * 10 for i in items]

    h = serve.run(Batched.bind())
    refs = [h.remote(i) for i in range(16)]
    assert sorted(ray_tpu.get(refs)) == [i * 10 for i in range(16)]
    # Requests actually coalesced (fewer calls than requests).
    assert max(batch_sizes) > 1


def test_autoscaling_up_and_down(serve_rt):
    @serve.deployment(
        max_ongoing_requests=2,
        autoscaling_config=AutoscalingConfig(
            min_replicas=1, max_replicas=3,
            target_ongoing_requests=1.0,
            upscale_delay_s=0.05, downscale_delay_s=0.3))
    class Slow:
        def __call__(self):
            time.sleep(0.3)
            return "ok"

    h = serve.run(Slow.bind())
    assert serve.get_deployment("Slow")["num_replicas"] == 1
    # Flood with requests -> should scale up.
    refs = [h.remote() for _ in range(24)]
    deadline = time.time() + 15
    scaled_up = False
    while time.time() < deadline:
        if serve.get_deployment("Slow")["num_replicas"] >= 2:
            scaled_up = True
            break
        time.sleep(0.05)
    assert scaled_up, "expected upscale under load"
    ray_tpu.get(refs)
    # Idle -> should scale back down to min.
    deadline = time.time() + 15
    while time.time() < deadline:
        if serve.get_deployment("Slow")["num_replicas"] == 1:
            break
        time.sleep(0.1)
    assert serve.get_deployment("Slow")["num_replicas"] == 1


def test_list_deployments(serve_rt):
    @serve.deployment
    def a():
        return 1

    @serve.deployment
    def b():
        return 2

    serve.run(a.bind())
    serve.run(b.bind())
    deps = serve.list_deployments()
    assert set(deps) >= {"a", "b"}


def test_http_proxy(serve_rt):
    import urllib.request
    import json as _json
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment
    def echo(payload):
        return {"echoed": payload}

    serve.run(echo.bind())
    proxy = start_http(port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{proxy.port}/echo", method="POST",
            data=_json.dumps({"msg": "hi"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = _json.loads(resp.read())
        assert body == {"result": {"echoed": {"msg": "hi"}}}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{proxy.port}/-/healthz", timeout=30) as resp:
            health = _json.loads(resp.read())
        assert health["status"] == "ok"
        # Unknown deployment -> 404
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{proxy.port}/missing", timeout=30)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        stop_http()


def test_http_proxy_x_replica_header(serve_rt):
    """Opt-in X-Replica: a request header asks which replica
    incarnation served the call; the proxy injects the echo flag
    into dict payloads, pops the deployment's answer into the
    response header, and keeps the JSON body identical to the
    non-opted response. No opt-in (or a deployment that ignores the
    flag) -> no header, payload untouched."""
    import urllib.request
    import json as _json
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment
    def rep(payload):
        if isinstance(payload, dict) and payload.get("echo_replica"):
            return {"ids": [1, 2], "replica": "r1:3"}
        return [1, 2]

    @serve.deployment
    def plain(payload):
        return {"echoed": payload}

    serve.run(rep.bind())
    serve.run(plain.bind())
    proxy = start_http(port=0)
    try:
        def post(path, body, replica_header):
            headers = {"Content-Type": "application/json"}
            if replica_header:
                headers["X-Replica"] = "1"
            req = urllib.request.Request(
                f"http://127.0.0.1:{proxy.port}/{path}",
                method="POST", data=_json.dumps(body).encode(),
                headers=headers)
            with urllib.request.urlopen(req, timeout=30) as resp:
                return (resp.headers.get("X-Replica"),
                        _json.loads(resp.read()))

        # opted in: header echoed, body bare (identical to no-opt)
        hdr, body = post("rep", {"prompt_ids": [0]}, True)
        assert hdr == "r1:3"
        assert body == {"result": [1, 2]}
        # not opted in: payload untouched, no header
        hdr, body = post("rep", {"prompt_ids": [0]}, False)
        assert hdr is None and body == {"result": [1, 2]}
        # opted in but the deployment ignores the flag: the proxy
        # must not invent a header
        hdr, body = post("plain", {"msg": "hi"}, True)
        assert hdr is None
        assert body["result"]["echoed"]["msg"] == "hi"
    finally:
        stop_http()


def test_http_proxy_model_generation_header(serve_rt):
    """Opt-in X-Model-Generation: mirrors X-Replica, but the tag
    names the WEIGHTS serving the call ("<generation>:<weights_id>")
    — the half of replica identity a live rollout changes. Both
    opt-ins compose on one request."""
    import urllib.request
    import json as _json
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment
    def gen(payload):
        if isinstance(payload, dict) and (payload.get("echo_replica")
                                          or payload.get(
                                              "echo_generation")):
            out = {"ids": [4, 5]}
            if payload.get("echo_replica"):
                out["replica"] = "0:1"
            if payload.get("echo_generation"):
                out["generation"] = "3:bc7332e425e8"
            return out
        return [4, 5]

    serve.run(gen.bind())
    proxy = start_http(port=0)
    try:
        def post(body, headers_in):
            headers = {"Content-Type": "application/json"}
            headers.update(headers_in)
            req = urllib.request.Request(
                f"http://127.0.0.1:{proxy.port}/gen",
                method="POST", data=_json.dumps(body).encode(),
                headers=headers)
            with urllib.request.urlopen(req, timeout=30) as resp:
                return (resp.headers.get("X-Replica"),
                        resp.headers.get("X-Model-Generation"),
                        _json.loads(resp.read()))

        # generation alone: header echoed, body bare
        rep, g, body = post({"prompt_ids": [0]},
                            {"X-Model-Generation": "1"})
        assert rep is None and g == "3:bc7332e425e8"
        assert body == {"result": [4, 5]}
        # both opt-ins on one request
        rep, g, body = post({"prompt_ids": [0]},
                            {"X-Replica": "1",
                             "X-Model-Generation": "1"})
        assert rep == "0:1" and g == "3:bc7332e425e8"
        assert body == {"result": [4, 5]}
        # no opt-in: no headers, payload untouched
        rep, g, body = post({"prompt_ids": [0]}, {})
        assert rep is None and g is None and body == {"result": [4, 5]}
    finally:
        stop_http()


def test_llama_llm_deployment(serve_rt):
    """North-star path: Llama JAX replicas behind serve (tiny config)."""
    from ray_tpu.serve.llm import LlamaDeployment

    LLM = serve.deployment(num_replicas=1)(LlamaDeployment)
    handle = serve.run(LLM.bind(max_new_tokens=4))
    out = ray_tpu.get(handle.remote([1, 2, 3]))
    assert len(out) == 7           # 3 prompt + 4 generated
    assert out[:3] == [1, 2, 3]
    # Deterministic greedy decode across requests.
    out2 = ray_tpu.get(handle.remote([1, 2, 3]))
    assert out == out2


def test_deployment_graph_composition(serve_rt):
    """Bound deployments as init args become live handles (the serve
    deployment-graph / model-composition pattern)."""
    @serve.deployment
    class Preprocessor:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            pre = ray_tpu.get(self.pre.remote(x))
            return pre + 1

    handle = serve.run(Model.bind(Preprocessor.bind()))
    assert ray_tpu.get(handle.remote(10)) == 21
    # Both deployments exist as first-class deployments.
    deps = serve.list_deployments()
    assert "Model" in deps and "Preprocessor" in deps


def test_dag_driver_routes(serve_rt):
    from ray_tpu.serve import DAGDriver

    @serve.deployment
    def double(x):
        return x * 2

    @serve.deployment
    def square(x):
        return x * x

    ingress = serve.deployment(DAGDriver).bind(
        {"/double": double.bind(), "/square": square.bind()})
    h = serve.run(ingress)
    assert ray_tpu.get(h.remote("/double", 21)) == 42
    assert ray_tpu.get(h.remote("/square", 5)) == 25
    routes = ray_tpu.get(h.routes.remote())
    assert set(routes) == {"/double", "/square"}


def test_status_and_delete(serve_rt):
    @serve.deployment(num_replicas=2)
    def f():
        return 1

    serve.run(f.bind())
    st = serve.status()
    assert st["deployments"]["f"]["status"] == "HEALTHY"
    assert st["deployments"]["f"]["num_replicas"] == 2
    serve.delete("f")
    assert "f" not in serve.list_deployments()


def test_run_no_wait_returns_immediately(serve_rt):
    """ADVICE r1: wait_for_ready=False must skip the readiness wait, not
    raise TimeoutError on the first poll."""
    @serve.deployment
    class Slow:
        def __init__(self):
            time.sleep(0.5)

        def __call__(self):
            return "up"

    h = serve.run(Slow.bind(), wait_for_ready=False)
    # Handle returned before the replica finished __init__; a call still
    # eventually succeeds once it's up.
    deadline = time.time() + 30
    while True:
        try:
            assert ray_tpu.get(h.remote(), timeout=30) == "up"
            break
        except Exception:
            if time.time() > deadline:
                raise
            time.sleep(0.1)


def test_handle_cache_one_per_deployment(serve_rt):
    """get_handle() and unpickling reuse ONE handle per deployment per
    process — each handle owns a long-poll subscriber thread + RPC
    connection, so per-call construction would leak without bound."""
    import cloudpickle

    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x

    h = serve.run(Echo.bind())
    h2 = serve.get_handle("Echo")
    h3 = serve.get_handle("Echo")
    assert h2 is h3
    assert cloudpickle.loads(cloudpickle.dumps(h2)) is h2
    assert ray_tpu.get(h.remote("hi"), timeout=10) == "hi"
    serve.shutdown()
    from ray_tpu.serve.router import _handle_cache
    assert not _handle_cache


def test_streaming_response_generator(serve_rt):
    """handle.options(stream=True) yields chunks as the replica's
    generator produces them (reference: serve streaming responses)."""
    @serve.deployment
    class Tokens:
        def __call__(self, n):
            for i in range(n):
                yield f"tok{i}"

        def evens(self, n):
            for i in range(0, n, 2):
                yield i

    h = serve.run(Tokens.bind())
    chunks = list(h.options(stream=True).remote(5))
    assert chunks == [f"tok{i}" for i in range(5)]
    # method-level streaming
    assert list(h.evens.options(stream=True).remote(6)) == [0, 2, 4]
    # non-generator methods stream as a single chunk
    @serve.deployment
    class Plain:
        def __call__(self, x):
            return x + 1
    hp = serve.run(Plain.bind())
    assert list(hp.options(stream=True).remote(41)) == [42]


def test_streaming_incremental_delivery(serve_rt):
    """First chunk arrives while the producer is still generating."""
    import time as _time

    @serve.deployment
    class Slow:
        def __call__(self, n):
            for i in range(n):
                yield i
                _time.sleep(0.15)

    h = serve.run(Slow.bind())
    t0 = _time.time()
    it = iter(h.options(stream=True).remote(4))
    first = next(it)
    t_first = _time.time() - t0
    rest = list(it)
    t_all = _time.time() - t0
    assert first == 0 and rest == [1, 2, 3]
    # 4 chunks take >= 0.45s total; the first must arrive well before
    assert t_first < t_all - 0.25, (t_first, t_all)


def test_streaming_error_propagates(serve_rt):
    @serve.deployment
    class Boom:
        def __call__(self):
            yield 1
            raise RuntimeError("mid-stream kaboom")

    h = serve.run(Boom.bind())
    it = iter(h.options(stream=True).remote())
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="kaboom"):
        list(it)


def test_streaming_releases_inflight_slot(serve_rt):
    @serve.deployment(max_ongoing_requests=1)
    class One:
        def __call__(self):
            yield "a"
            yield "b"

    h = serve.run(One.bind())
    for _ in range(3):      # would deadlock if slots leaked
        assert list(h.options(stream=True).remote()) == ["a", "b"]


def test_streaming_async_generator(serve_rt):
    @serve.deployment
    class AsyncGen:
        async def __call__(self, n):
            import asyncio as aio
            for i in range(n):
                await aio.sleep(0.01)
                yield i * 10

    h = serve.run(AsyncGen.bind())
    assert list(h.options(stream=True).remote(3)) == [0, 10, 20]


def test_http_proxy_streaming(serve_rt):
    import urllib.request

    @serve.deployment
    class Chunks:
        def __call__(self, payload):
            for i in range(int(payload["n"])):
                yield {"i": i}

    serve.run(Chunks.bind())
    from ray_tpu.serve.http_proxy import start_http, stop_http
    import json as _json
    proxy = start_http(port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{proxy.port}/Chunks?stream=1",
            data=_json.dumps({"n": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            lines = [l for l in r.read().decode().splitlines() if l]
        assert [_json.loads(l)["chunk"] for l in lines] == \
            [{"i": 0}, {"i": 1}, {"i": 2}]
    finally:
        stop_http()


def test_http_proxy_streaming_x_replica_header(serve_rt):
    """Opt-in X-Replica on a STREAMING response: the deployment
    leads with a {"replica": ...} marker chunk, the proxy lifts it
    into the response header BEFORE the stream starts and never
    emits it as a body chunk. Without the opt-in the stream is
    byte-identical to before."""
    import urllib.request

    @serve.deployment
    class Toks:
        def __call__(self, payload):
            if isinstance(payload, dict) \
                    and payload.get("echo_replica"):
                yield {"replica": "r7:2"}
            for i in range(3):
                yield i

    serve.run(Toks.bind())
    from ray_tpu.serve.http_proxy import start_http, stop_http
    import json as _json
    proxy = start_http(port=0)
    try:
        def post(replica_header):
            headers = {"Content-Type": "application/json"}
            if replica_header:
                headers["X-Replica"] = "1"
            req = urllib.request.Request(
                f"http://127.0.0.1:{proxy.port}/Toks?stream=1",
                data=_json.dumps({"n": 3}).encode(),
                headers=headers)
            with urllib.request.urlopen(req, timeout=30) as r:
                hdr = r.headers.get("X-Replica")
                lines = [l for l in r.read().decode().splitlines()
                         if l]
            return hdr, [_json.loads(l)["chunk"] for l in lines]

        hdr, chunks = post(True)
        assert hdr == "r7:2"
        assert chunks == [0, 1, 2]     # marker never leaks as a chunk
        hdr, chunks = post(False)
        assert hdr is None
        assert chunks == [0, 1, 2]
    finally:
        stop_http()


def test_http_proxy_streaming_x_trace_id_echo(serve_rt):
    """A caller-supplied X-Trace-Id comes back on the STREAMING
    response headers (set before chunked encoding commits) and rides
    the dict payload to the deployment, so cross-process stitching
    can key on the id the client already holds."""
    import urllib.request

    seen = {}

    @serve.deployment
    class TokStream:
        def __call__(self, payload):
            seen["trace_id"] = (payload or {}).get("trace_id")
            for i in range(2):
                yield i

    serve.run(TokStream.bind())
    from ray_tpu.serve.http_proxy import start_http, stop_http
    import json as _json
    proxy = start_http(port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{proxy.port}/TokStream?stream=1",
            data=_json.dumps({"n": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "t-stream-1"})
        with urllib.request.urlopen(req, timeout=30) as r:
            hdr = r.headers.get("X-Trace-Id")
            lines = [l for l in r.read().decode().splitlines() if l]
        assert hdr == "t-stream-1"
        assert [_json.loads(l)["chunk"] for l in lines] == [0, 1]
        assert seen["trace_id"] == "t-stream-1"
        # no opt-in -> no header, payload untouched
        req = urllib.request.Request(
            f"http://127.0.0.1:{proxy.port}/TokStream?stream=1",
            data=_json.dumps({"n": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("X-Trace-Id") is None
            r.read()
        assert seen["trace_id"] is None
    finally:
        stop_http()


def test_http_proxy_metrics_endpoint(serve_rt):
    """/-/metrics serves the local registry by default and the
    aggregated fleet exposition once a collector is attached."""
    import urllib.request
    from ray_tpu.serve.http_proxy import start_http, stop_http
    from ray_tpu.util import metrics

    proxy = start_http(port=0)
    try:
        g = metrics.Gauge("proxy_smoke_gauge", "smoke")
        g.set(3.0)
        url = f"http://127.0.0.1:{proxy.port}/-/metrics"
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.headers.get_content_type() == "text/plain"
            text = r.read().decode()
        assert "proxy_smoke_gauge 3.0" in text

        class FakeCollector:
            def metrics_text(self):
                return ('serve_fleet_member_up{member="a0"} 1.0\n'
                        'serve_fleet_members 2.0\n')

        proxy.attach_telemetry(FakeCollector())
        with urllib.request.urlopen(url, timeout=30) as r:
            text = r.read().decode()
        assert 'serve_fleet_member_up{member="a0"} 1.0' in text
    finally:
        stop_http()


def test_streaming_failed_start_releases_slot(serve_rt):
    """A stream that fails to start (bad method) must release the
    handle's in-flight slot, or the handle wedges permanently."""
    @serve.deployment(max_ongoing_requests=2)
    class S:
        def __call__(self):
            yield "ok"

    h = serve.run(S.bind())
    for _ in range(5):      # more failures than max_ongoing slots
        with pytest.raises(Exception):
            h.nope.options(stream=True).remote()
    assert list(h.options(stream=True).remote()) == ["ok"]


def test_streaming_plain_async_method(serve_rt):
    """options(stream=True) on a plain `async def` awaits it and
    streams the return value as one chunk."""
    @serve.deployment
    class A:
        async def __call__(self, x):
            return x + 1

    h = serve.run(A.bind())
    assert list(h.options(stream=True).remote(41)) == [42]


def test_llama_generate_batch_ragged_matches_unbatched(serve_rt):
    from ray_tpu.serve.llm import LlamaDeployment
    dep = LlamaDeployment(max_new_tokens=8)
    prompts = [[5, 6, 7], [1, 2, 3, 4, 5, 6], [9, 8, 7]]
    batched = dep.generate_batch(prompts)
    for p, got in zip(prompts, batched):
        solo = dep(p)[len(p):]
        assert got == solo, (p, got, solo)


def test_autoscaling_counts_streaming_load(serve_rt):
    """Streaming requests hold their in-flight slot for their whole
    duration, so sustained streams drive upscale and draining streams
    release it (the ongoing counter feeding autoscaling is shared with
    the streaming path)."""
    import threading

    @serve.deployment(
        max_ongoing_requests=2,
        autoscaling_config=AutoscalingConfig(
            min_replicas=1, max_replicas=3,
            target_ongoing_requests=1.0,
            upscale_delay_s=0.05, downscale_delay_s=0.3))
    class Tokens:
        def __call__(self, n):
            for i in range(n):
                time.sleep(0.02)
                yield i

    h = serve.run(Tokens.bind())
    assert serve.get_deployment("Tokens")["num_replicas"] == 1

    done = []

    def consume():
        done.append(len(list(h.options(stream=True).remote(80))))

    threads = [threading.Thread(target=consume) for _ in range(6)]
    for t in threads:
        t.start()
    deadline = time.time() + 15
    scaled_up = False
    while time.time() < deadline:
        if serve.get_deployment("Tokens")["num_replicas"] >= 2:
            scaled_up = True
            break
        time.sleep(0.05)
    for t in threads:
        t.join()
    assert scaled_up, "streaming load must register as ongoing"
    assert done == [80] * 6
    # streams finished -> ongoing drains -> back to min replicas
    deadline = time.time() + 15
    while time.time() < deadline:
        if serve.get_deployment("Tokens")["num_replicas"] == 1:
            break
        time.sleep(0.1)
    assert serve.get_deployment("Tokens")["num_replicas"] == 1


def test_llm_deployment_serves_mixtral(serve_rt):
    """The LLM deployment serves any Llama-shaped family: a Mixtral
    (sparse-MoE) replica answers batched and streaming requests."""
    from ray_tpu.models.mixtral import mixtral_tiny
    from ray_tpu.serve.llm import LlamaDeployment

    @serve.deployment
    class MoELLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=mixtral_tiny(), max_new_tokens=6,
                             decode_chunk=3)

    h = serve.run(MoELLM.bind(), timeout_s=300)
    prompt = list(range(1, 9))
    full = ray_tpu.get(h.remote(prompt), timeout=300)
    assert len(full) == len(prompt) + 6
    streamed = list(h.stream.options(stream=True).remote(prompt))
    assert streamed == full[len(prompt):]


def test_dag_driver_single_graph_with_adapter(rt):
    """Single-graph DAGDriver: the http_adapter parses the payload
    and predict() runs the bound graph (reference drivers.py shape)."""
    import json
    from ray_tpu import serve
    from ray_tpu.serve import DAGDriver, json_to_ndarray

    @serve.deployment
    class Doubler:
        def __call__(self, arr):
            return (arr * 2).tolist()

    ingress = serve.deployment(DAGDriver).bind(
        Doubler.bind(), http_adapter=json_to_ndarray)
    handle = serve.run(ingress, timeout_s=120)
    out = ray_tpu.get(handle.remote(
        json.dumps({"array": [1, 2, 3]})))
    assert out == [2, 4, 6]
    serve.shutdown()


def test_model_multiplexing(rt):
    """@serve.multiplexed LRU model loading + model-id routing
    affinity (reference: serve model multiplexing, the LoRA pattern):
    loads are cached per replica, the id reaches the replica via
    get_multiplexed_model_id, eviction respects the per-replica cap,
    and repeated requests for one model keep hitting the same replica.
    """
    import os
    from ray_tpu import serve

    @serve.deployment(num_replicas=2, max_ongoing_requests=8)
    class Multi:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id):
            self.loads.append(model_id)
            scale = int(model_id[1:]) if model_id else 0
            return {"id": model_id, "scale": scale}

        def __call__(self, x):
            mid = serve.get_multiplexed_model_id()
            model = self.get_model(mid)
            return {"pid": os.getpid(), "out": x * model["scale"],
                    "loads": list(self.loads)}

    h = serve.run(Multi.bind(), timeout_s=120)
    # same model id repeatedly: one replica, one load
    outs = [ray_tpu.get(h.options(multiplexed_model_id="m3").remote(5))
            for _ in range(6)]
    assert all(o["out"] == 15 for o in outs)
    assert len({o["pid"] for o in outs}) == 1      # affinity held
    assert outs[-1]["loads"].count("m3") == 1      # loaded once
    # a third model on one replica evicts the LRU entry (cap 2)
    for mid in ("m1", "m2", "m4", "m1"):
        ray_tpu.get(h.options(multiplexed_model_id=mid).remote(1))
    # un-multiplexed requests still work (empty model id)
    probe = ray_tpu.get(h.remote(7))
    assert probe["out"] == 0      # scale-0 default model
    serve.shutdown()


def test_multiplexed_loader_dedup_under_concurrency(rt):
    """Concurrent first requests for one model id coalesce into a
    single load (duplicate loads = N x memory + dropped copies
    skipping unload)."""
    import threading
    import time as _t
    from ray_tpu.serve.multiplex import multiplexed

    class Host:
        def __init__(self):
            self.loads = []

        @multiplexed(max_num_models_per_replica=2)
        def get_model(self, mid):
            self.loads.append(mid)
            _t.sleep(0.2)          # slow load window
            return {"id": mid}

    host = Host()
    results = []
    ts = [threading.Thread(
        target=lambda: results.append(host.get_model("m1")))
        for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(results) == 8
    assert all(r["id"] == "m1" for r in results)
    assert host.loads == ["m1"]            # exactly one load


def test_user_config_reconfigure_without_restart(serve_rt):
    """user_config updates roll reconfigure() through LIVE replicas —
    no restarts (reference: deployment user_config semantics)."""
    import time
    from ray_tpu import serve

    @serve.deployment(num_replicas=1, user_config={"threshold": 1})
    class Scorer:
        def __init__(self):
            self.pid_mark = id(self)
            self.threshold = None

        def reconfigure(self, user_config):
            self.threshold = user_config["threshold"]

        def __call__(self, x):
            return {"hit": x >= self.threshold,
                    "mark": self.pid_mark,
                    "threshold": self.threshold}

    app = Scorer.bind()
    h = serve.run(app, timeout_s=120)
    first = ray_tpu.get(h.remote(5))
    assert first == {"hit": True, "mark": first["mark"],
                     "threshold": 1}

    # redeploy with ONLY user_config changed
    h2 = serve.run(Scorer.options(user_config={"threshold": 10}).bind(),
                   timeout_s=120)
    deadline = time.time() + 10
    out = None
    while time.time() < deadline:
        out = ray_tpu.get(h2.remote(5))
        if out["threshold"] == 10:
            break
        time.sleep(0.2)
    assert out["threshold"] == 10 and out["hit"] is False
    # the SAME instance served both configs: no replica restart
    assert out["mark"] == first["mark"]


def test_unhealthy_replica_is_replaced(serve_rt):
    """Controller health checks (reference: deployment-state health
    checking): a replica whose user check_health() starts raising is
    killed and replaced; traffic recovers on the fresh replica."""
    import time
    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Flaky:
        def __init__(self):
            self.born = time.time()
            self.sick = False

        def make_sick(self):
            self.sick = True
            return True

        def check_health(self):
            if self.sick:
                raise RuntimeError("unhealthy")

        def __call__(self, _):
            return self.born

    # fast health cadence for the test
    dep = Flaky.options(name="Flaky")
    dep.config.health_check_period_s = 0.3
    h = serve.run(dep.bind(), timeout_s=120)
    born1 = ray_tpu.get(h.remote(0))
    assert ray_tpu.get(h.make_sick.remote())
    deadline = time.time() + 30
    born2 = born1
    while time.time() < deadline:
        try:
            born2 = ray_tpu.get(h.remote(0), timeout=5)
            if born2 != born1:
                break
        except Exception:
            pass
        time.sleep(0.3)
    assert born2 != born1, "sick replica was never replaced"


def test_replica_concurrency_honors_max_ongoing(serve_rt):
    """Sync user methods run via the replica loop's run_in_executor;
    the stock asyncio default executor caps at min(32, cpus + 4)
    threads, which on a small host silently limited every replica to
    ~5 concurrent requests regardless of max_ongoing_requests. The
    executor is now sized to the actor's max_concurrency: 8 parallel
    0.3s calls must overlap, not serialize."""
    import threading

    @serve.deployment(max_ongoing_requests=32)
    class Sleepy:
        def __call__(self, x):
            time.sleep(0.3)
            return x

    handle = serve.run(Sleepy.bind())
    ray_tpu.get(handle.remote(0))          # replica up + warm
    results = []
    lock = threading.Lock()

    def call():
        r = ray_tpu.get(handle.remote(1), timeout=30)
        with lock:
            results.append(r)

    t0 = time.time()
    threads = [threading.Thread(target=call) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    # every call must actually succeed (a fast failure also keeps
    # wall low) ...
    assert results == [1] * 8, results
    # ... and serial would be 2.4s; genuine overlap keeps it well
    # under half
    assert wall < 1.2, f"8 parallel 0.3s calls took {wall:.2f}s"


def test_replica_stats_user_hook(serve_rt):
    """A deployment exposing serve_stats() gets its metrics merged
    into Replica.stats() under "user" — the path autoscaler/status
    consumers read (LLM engine occupancy rides this hook)."""
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.models.llama import llama_tiny

    @serve.deployment(max_ongoing_requests=8)
    class L:
        def __init__(self):
            self.inner = LlamaDeployment(
                config=llama_tiny(), max_new_tokens=6,
                max_slots=2, page_size=8, decode_chunk=2)

        def __call__(self, p):
            return self.inner(p)

        def serve_stats(self):
            return self.inner.serve_stats()

    handle = serve.run(L.bind())
    out = ray_tpu.get(handle.remote([3, 1, 4]), timeout=120)
    assert len(out) == 9
    from ray_tpu.serve.api import get_or_create_controller
    controller = get_or_create_controller()
    reps = ray_tpu.get(controller.get_replicas.remote("L"))
    _rid, h = reps["replicas"][0]
    stats = ray_tpu.get(h.stats.remote(), timeout=30)
    eng = stats["user"]["engine"]
    assert eng["completed"] >= 1
    assert eng["slots_total"] == 2
    assert eng["pages_free"] <= eng["pages_total"]


def test_ingress_routing(serve_rt):
    """@serve.ingress + @serve.route: path templates, verbs, 404/405,
    and specificity ordering — the reference's FastAPI-ingress
    capability on the in-house router (serve/ingress.py)."""
    import urllib.request
    import urllib.error
    import json as _json
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment
    @serve.ingress
    class Store:
        def __init__(self):
            self.items = {"1": "apple"}

        @serve.route("/items/{item_id}")
        def get_item(self, payload, item_id):
            if item_id not in self.items:
                raise LookupError(f"404: no item {item_id}")
            return {"item": self.items[item_id]}

        @serve.route("/items", methods=["POST"])
        def add_item(self, payload):
            self.items[payload["id"]] = payload["name"]
            return {"count": len(self.items)}

        @serve.route("/items/special")
        def special(self, payload):
            return {"item": "unicorn"}

    serve.run(Store.bind())
    proxy = start_http(port=0)
    base = f"http://127.0.0.1:{proxy.port}/Store"
    try:
        with urllib.request.urlopen(f"{base}/items/1",
                                    timeout=30) as r:
            assert _json.loads(r.read()) == {"result":
                                             {"item": "apple"}}
        # longest-pattern-first: the literal route wins over {item_id}
        with urllib.request.urlopen(f"{base}/items/special",
                                    timeout=30) as r:
            assert _json.loads(r.read())["result"]["item"] == "unicorn"
        req = urllib.request.Request(
            f"{base}/items", method="POST",
            data=_json.dumps({"id": "2", "name": "pear"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert _json.loads(r.read()) == {"result": {"count": 2}}
        with urllib.request.urlopen(f"{base}/items/2", timeout=30) as r:
            assert _json.loads(r.read())["result"]["item"] == "pear"
        try:
            urllib.request.urlopen(f"{base}/nope", timeout=30)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        try:
            req = urllib.request.Request(f"{base}/items/1",
                                         method="DELETE")
            urllib.request.urlopen(req, timeout=30)
            assert False, "expected 405"
        except urllib.error.HTTPError as e:
            assert e.code == 405
    finally:
        stop_http()


def test_ingress_requires_routes():
    with pytest.raises(ValueError, match="no @serve.route"):
        @serve.ingress
        class Empty:
            pass


def test_ingress_error_mapping(serve_rt):
    """Subpaths on non-ingress deployments 404 cleanly; status markers
    map by FIRST occurrence (a path containing '405:' can't flip a
    404); decoration-time validation fails fast."""
    import urllib.request
    import urllib.error
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment
    def plain(payload=None):
        return "ok"

    @serve.deployment
    @serve.ingress
    class Api:
        @serve.route("/x/{v}")
        def x(self, payload, v):
            return {"v": v}

    serve.run(plain.bind())
    serve.run(Api.bind())
    proxy = start_http(port=0)
    try:
        for url, want in [
                (f"http://127.0.0.1:{proxy.port}/plain/sub/path", 404),
                (f"http://127.0.0.1:{proxy.port}/Api/a/b/c", 404)]:
            try:
                urllib.request.urlopen(url, timeout=30)
                assert False, f"expected {want} for {url}"
            except urllib.error.HTTPError as e:
                assert e.code == want, (url, e.code)
    finally:
        stop_http()

    with pytest.raises(TypeError, match="not a string"):
        serve.route("/x", methods="POST")
    with pytest.raises(ValueError, match="unknown HTTP"):
        serve.route("/x", methods=["FETCH"])
    with pytest.raises(ValueError, match="would overwrite"):
        @serve.ingress
        class Clashing:
            @serve.route("/a")
            def a(self, payload):
                return 1

            def handle_route(self):
                return 2
