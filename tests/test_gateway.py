"""Network gateway correctness: the HTTP/SSE stream must be
token-for-token identical to in-process ``engine.stream()``, concurrent
clients must interleave under continuous batching, and a mid-stream
client disconnect must cancel the request and free its pages.

All HTTP here is real sockets against a gateway running on its own
thread + event loop (``serving/gateway.serve_background``); the engine
stays on the gateway's single engine thread throughout."""

import http.client
import json
import socket
import threading
import time

import jax
import pytest

from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, SamplingParams, ServeEngine
from repro.serving.gateway import request_from_json, serve_background

_SLOW = pytest.mark.slow

_SAMPLING = dict(temperature=0.8, top_k=8, max_new=6)


def _cfg(backend):
    return smoke_config("codeqwen1.5-7b").replace(attn_backend=backend)


def _engine(cfg, **kw):
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    return ServeEngine(md, cfg, params, **kw)


def _sse_post(port, spec, timeout=300):
    """POST /v1/generate and collect every SSE event until the final one."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", body=json.dumps(spec),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    events, status = [], resp.status
    if status == 200:
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            evt = json.loads(line[6:])
            events.append(evt)
            if evt.get("finished"):
                break
    else:
        events.append(json.loads(resp.read()))
    conn.close()
    return status, events


def _wait_for(cond, timeout=60.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------------------
# SSE == engine.stream() token-for-token


@pytest.mark.parametrize("backend", [
    "dense",
    pytest.param("camformer", marks=_SLOW),
])
def test_sse_matches_engine_stream(backend):
    cfg = _cfg(backend)
    prompt = [3, 5, 8, 1, 4]
    # reference: plain in-process stream, rid pinned to the rid the
    # gateway runner will assign (0 on a fresh engine) — sampling is
    # keyed by (seed, rid, index), so the tokens must agree exactly
    ref_eng = _engine(cfg)
    want = [out.token for out in ref_eng.stream(
        Request(prompt=list(prompt), rid=0,
                sampling=SamplingParams(**_SAMPLING)))]

    handle = serve_background(_engine(cfg))
    try:
        status, events = _sse_post(handle.port, dict(_SAMPLING, prompt=prompt))
    finally:
        handle.stop()
    assert status == 200
    assert [e["token"] for e in events] == want
    assert [e["index"] for e in events] == list(range(1, len(want) + 1))
    final = events[-1]
    assert final["finished"] and final["finish_reason"] == "length"
    assert final["tokens"] == want  # full-sequence snapshot on the last event


def test_gateway_healthz_metrics_and_validation():
    handle = serve_background(_engine(_cfg("dense")))
    port = handle.port
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["status"] == "ok"
        assert health["backend"] == "dense"
        conn.close()

        # malformed bodies are rejected before reaching the engine thread
        for bad in ({"prompt": []}, {"prompt": "hi"}, {"prompt": [1], "max_new": 0},
                    {"prompt": [1], "max_new": 1000}):
            status, events = _sse_post(port, bad)
            assert status == 400, bad
            assert "error" in events[0]

        status, events = _sse_post(
            port, {"prompt": [3, 5, 8], "max_new": 3})
        assert status == 200 and events[-1]["finished"]

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/metrics")
        metrics = json.loads(conn.getresponse().read())
        conn.close()
        assert metrics["requests"]["completed"] == 1
        assert metrics["requests"]["tokens_out"] == 3
        assert metrics["ttft_ms"]["count"] == 1
        assert metrics["tpot_ms"]["count"] == 2
        assert metrics["engine"]["ticks"] > 0
        assert metrics["engine"]["preemptions"] == 0
        assert metrics["engine"]["pool_pages"] > 0
        # the engine thread's host seconds by part of the tick
        for part in ("plan_s", "dispatch_s", "ingest_s"):
            assert metrics["engine"][part] > 0, part
        # speculative-decode counters are always surfaced (0 with the
        # plain loop; nonzero acceptance books when spec_k > 0)
        assert metrics["engine"]["spec_proposed"] == 0
        assert metrics["engine"]["spec_accepted"] == 0
        assert metrics["engine"]["spec_acceptance"] == 0.0
    finally:
        handle.stop()


def test_request_from_json_validation():
    req = request_from_json(
        {"prompt": [1, 2], "max_new": 4, "temperature": 0.5, "top_k": 3,
         "top_p": 0.9, "stop": [7], "priority": 2}, max_len=32)
    assert req.prompt == [1, 2] and req.priority == 2
    assert req.sampling.stop == (7,) and req.sampling.max_new == 4
    with pytest.raises(ValueError):
        request_from_json({"prompt": [1]}, max_len=16)  # default max_new 32
    with pytest.raises(ValueError):
        request_from_json({"prompt": [True, 2], "max_new": 1})
    with pytest.raises(ValueError):
        request_from_json([1, 2])


# ---------------------------------------------------------------------------
# concurrent clients interleave under continuous batching


def test_concurrent_clients_interleave():
    handle = serve_background(_engine(_cfg("dense")))
    n_clients, results = 3, {}
    barrier = threading.Barrier(n_clients)
    # the engine-thread routing order, recorded on the runner's sink path
    routed, submit = [], handle.runner.submit

    def spy_submit(req, sink):
        def spy(out):
            if out.token is not None:
                routed.append((out.rid, out.index))
            sink(out)
        return submit(req, spy)

    handle.runner.submit = spy_submit

    def client(i):
        barrier.wait()
        status, events = _sse_post(
            handle.port,
            {"prompt": [10 + i, 3, 5], "max_new": 10, "temperature": 0.8,
             "top_k": 8})
        results[i] = (status, events)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert all(not t.is_alive() for t in threads)
        assert all(results[i][0] == 200 for i in range(n_clients))
        assert all(results[i][1][-1]["finished"] for i in range(n_clients))
        rids = {results[i][1][0]["rid"] for i in range(n_clients)}
        assert len(rids) == n_clients

        # the engine-thread routing order: decode ticks emit one token per
        # live request per tick, so concurrently-resident requests must
        # ALTERNATE in the log rather than complete one after another
        log = list(routed)
        changes = sum(a[0] != b[0] for a, b in zip(log, log[1:]))
        assert changes > n_clients, (
            f"no continuous-batching interleave in routed order: {log}")
    finally:
        handle.stop()


# ---------------------------------------------------------------------------
# mid-stream disconnect cancels and frees pages


def test_disconnect_cancels_and_frees_pages():
    eng = _engine(_cfg("dense"), max_len=64)
    handle = serve_background(eng)
    try:
        body = json.dumps({"prompt": [3, 5, 8, 1], "max_new": 50,
                           "temperature": 0.8, "top_k": 8}).encode()
        s = socket.create_connection(("127.0.0.1", handle.port), timeout=120)
        s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        buf = b""
        while buf.count(b"data: ") < 2:  # two streamed tokens, mid-flight
            chunk = s.recv(4096)
            assert chunk, f"stream ended early: {buf!r}"
            buf += chunk
        first = json.loads(
            buf.split(b"data: ", 1)[1].split(b"\n", 1)[0])
        rid = first["rid"]
        s.close()  # abrupt client disconnect

        assert _wait_for(lambda: any(
            r.rid == rid and r.finish_reason == "cancelled"
            for r in eng.done)), "disconnect did not cancel the request"
        # pages freed immediately: the whole pool is reclaimable again
        # (prefix pages may be LRU-retained; free_pages counts those)
        assert _wait_for(
            lambda: eng.kv.free_pages == eng.kv.n_pages - 1), (
            f"pages leaked after disconnect-cancel: "
            f"{eng.kv.free_pages}/{eng.kv.n_pages - 1}")
        assert _wait_for(lambda: eng.sched._inflight_total == 0)
        assert handle.runner.is_alive()

        # the engine keeps serving after the disconnect
        status, events = _sse_post(
            handle.port, {"prompt": [9, 1, 4], "max_new": 2})
        assert status == 200 and events[-1]["finished"]
    finally:
        handle.stop()
