"""HTTP completion server: SSE framing, stop/top_p end-to-end through the
wire, disconnect -> abort, backpressure 429, and route/validation errors.

Each test runs a real ``CompletionServer`` on a loopback socket (port 0)
and speaks raw HTTP/1.1 through the shared ``serving.http_client`` —
the same protocol layer a load balancer or the bench harness sees, no
test-only shortcuts.
"""
import asyncio
import json

import numpy as np
import pytest

from repro.launch.serve import build_pair
from repro.serving import (
    AsyncEngine,
    CompletionServer,
    Engine,
    EngineConfig,
    SamplingParams,
)
from repro.serving import http_client as hc


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [
        [int(t) for t in rng.randint(0, vocab, size=rng.randint(3, 7))]
        for _ in range(n)
    ]


@pytest.fixture(scope="module")
def pair():
    return build_pair(seed=0, s_max=128, quantize=False)


def _stop_cut(ref, stops):
    """Tokens a stop leaves of `ref`: scan the detokenized stream token by
    token, and at the first token where any stop text has appeared keep
    the tokens whose text ends at or before the earliest match."""
    from repro.serving.api import default_detokenize

    text, ends = "", []
    for t in ref:
        text += default_detokenize(t)
        ends.append(len(text))
        hits = [m for m in (text.find(s) for s in stops) if m >= 0]
        if hits:
            return sum(1 for e in ends if e <= min(hits))
    return len(ref)


def _sync_ref(pair, prompt, sp):
    target, draft = pair
    eng = Engine(target, draft, EngineConfig(max_batch=1, page_size=8))
    outs, _ = eng.run([np.asarray(prompt, np.int32)], sp)
    return [int(t) for t in outs[0]]


class _Served:
    """One live server, with the shared client bound to its port."""

    def __init__(self, server):
        self.server = server
        self.port = server.port

    async def request(self, method, path, payload=None):
        return await hc.request(self.port, method, path, payload)

    async def stream_raw(self, payload):
        """POST stream=true; return (status, head, raw SSE body bytes)."""
        return await self.request(
            "POST", "/v1/completions", dict(payload, stream=True)
        )


def _with_server(pair, engine_cfg=None, max_queued=8):
    """Decorator-free harness: run `fn(_Served)` inside a fresh server."""
    target, draft = pair
    cfg = engine_cfg or EngineConfig(
        max_batch=2, page_size=8, max_model_len=128
    )

    def runner(fn):
        async def scenario():
            engine = Engine(target, draft, cfg)
            server = CompletionServer(
                AsyncEngine(engine, max_queued=max_queued)
            )
            await server.start(port=0)
            task = asyncio.ensure_future(server.serve_forever())
            try:
                return await fn(_Served(server))
            finally:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                await server.stop()

        return asyncio.run(scenario())

    return runner


# ---------------------------------------------------------------------------
# SSE framing + bit-identity through the wire
# ---------------------------------------------------------------------------


def test_sse_chunk_framing_and_token_identity(pair):
    prompt = _prompts(1, seed=1)[0]
    ref = _sync_ref(pair, prompt, SamplingParams(max_tokens=10))

    async def fn(srv):
        status, head, body = await srv.stream_raw(
            {"prompt": prompt, "max_tokens": 10}
        )
        assert status == 200
        assert "text/event-stream" in head
        events = [e for e in body.decode().split("\n\n") if e.strip()]
        # framing: every event is a single `data: ` line, stream ends [DONE]
        assert all(e.startswith("data: ") and "\n" not in e for e in events)
        assert events[-1] == "data: [DONE]"
        chunks = [json.loads(e[len("data: "):]) for e in events[:-1]]
        # per-token chunks with a monotone index and exactly one final
        assert [c["index"] for c in chunks] == list(range(len(chunks)))
        assert [c["token"] for c in chunks] == ref
        reasons = [c["finish_reason"] for c in chunks]
        assert reasons[-1] == "length" and set(reasons[:-1]) == {None}
        # detokenized text rides along per chunk
        assert chunks[0]["text"] == f"{ref[0]} "

    _with_server(pair)(fn)


def test_non_streaming_completion_matches_reference(pair):
    prompt = _prompts(1, seed=2)[0]
    ref = _sync_ref(pair, prompt, SamplingParams(max_tokens=8))

    async def fn(srv):
        status, _, body = await srv.request(
            "POST", "/v1/completions", {"prompt": prompt, "max_tokens": 8}
        )
        assert status == 200
        obj = json.loads(body)
        assert obj["token_ids"] == ref
        assert obj["finish_reason"] == "length"
        assert obj["usage"] == {
            "prompt_tokens": len(prompt), "completion_tokens": len(ref),
        }
        assert obj["text"] == "".join(f"{t} " for t in ref)

    _with_server(pair)(fn)


# ---------------------------------------------------------------------------
# stop + top_p end-to-end through HTTP
# ---------------------------------------------------------------------------


def test_stop_sequence_through_http(pair):
    prompt = _prompts(1, seed=3)[0]
    ref = _sync_ref(pair, prompt, SamplingParams(max_tokens=10))
    stop_s = f"{ref[4]} "  # the 5th token's text
    cut = _stop_cut(ref, (stop_s,))  # may match before the 5th token

    async def fn(srv):
        # whole response: truncated BEFORE the stop string, reason "stop"
        status, _, body = await srv.request(
            "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 10, "stop": stop_s},
        )
        obj = json.loads(body)
        assert status == 200
        assert obj["token_ids"] == ref[:cut]
        assert obj["finish_reason"] == "stop"
        assert stop_s not in obj["text"]
        # streamed: same truncation, final chunk carries the reason
        status, _, sse = await srv.stream_raw(
            {"prompt": prompt, "max_tokens": 10, "stop": [stop_s]}
        )
        events = [e for e in sse.decode().split("\n\n") if e.strip()]
        chunks = [json.loads(e[len("data: "):]) for e in events[:-1]]
        toks = [c["token"] for c in chunks if c["token"] is not None]
        assert toks == ref[:cut]
        assert chunks[-1]["finish_reason"] == "stop"

    _with_server(pair)(fn)


def test_top_p_through_http_deterministic_and_lossless(pair):
    prompt = _prompts(1, seed=4)[0]
    greedy = _sync_ref(pair, prompt, SamplingParams(max_tokens=8))
    sp = SamplingParams(temperature=0.8, top_p=0.85, seed=21, max_tokens=8)
    ref = _sync_ref(pair, prompt, sp)

    async def fn(srv):
        payload = {
            "prompt": prompt, "max_tokens": 8,
            "temperature": 0.8, "top_p": 0.85, "seed": 21,
        }
        status, _, body = await srv.request(
            "POST", "/v1/completions", payload
        )
        assert status == 200
        # nucleus sampling through HTTP == the same SamplingParams run
        # synchronously (per-request key streams, schedule-invariant)
        assert json.loads(body)["token_ids"] == ref
        # and a tiny nucleus collapses to greedy exactly
        status, _, body = await srv.request(
            "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 8,
             "temperature": 0.8, "top_p": 1e-6, "seed": 21},
        )
        assert json.loads(body)["token_ids"] == greedy

    _with_server(pair)(fn)


# ---------------------------------------------------------------------------
# disconnect -> abort, health/stats, errors
# ---------------------------------------------------------------------------


def test_client_disconnect_aborts_and_frees_pages(pair):
    p_victim, p_survivor = _prompts(2, seed=5)
    ref = _sync_ref(pair, p_survivor, SamplingParams(max_tokens=10))

    async def fn(srv):
        # open a long streaming completion, read one chunk, hang up
        reader, writer = await hc.open_request(
            srv.port, "POST", "/v1/completions",
            {"prompt": p_victim, "max_tokens": 100, "stream": True},
        )
        await hc.read_head(reader)
        await reader.readuntil(b"\n\n")  # first token chunk is out
        writer.close()  # mid-generation disconnect
        # a healthy neighbour keeps decoding, bit-identical
        status, _, resp = await srv.request(
            "POST", "/v1/completions",
            {"prompt": p_survivor, "max_tokens": 10},
        )
        assert status == 200 and json.loads(resp)["token_ids"] == ref
        # every page returns once the abort lands
        st = {}
        for _ in range(200):
            _, _, sbody = await srv.request("GET", "/stats")
            st = json.loads(sbody)
            if st["target_pool"]["used_pages"] == 0 and st["active"] == 0:
                break
            await asyncio.sleep(0.02)
        assert st["target_pool"]["used_pages"] == 0, st["target_pool"]
        assert st["target_pool"]["reserved_pages"] == 0
        assert st["draft_pool"]["used_pages"] == 0

    _with_server(pair)(fn)


def test_healthz_stats_and_error_routes(pair):
    prompt = _prompts(1, seed=6)[0]

    async def fn(srv):
        status, _, body = await srv.request("GET", "/healthz")
        assert status == 200 and json.loads(body) == {"status": "ok"}
        status, _, body = await srv.request("GET", "/stats")
        st = json.loads(body)
        assert status == 200
        for key in ("queued", "active", "max_batch", "target_pool",
                    "draft_pool", "requests_served", "par_mode"):
            assert key in st, key
        # route + validation errors
        status, _, _ = await srv.request("GET", "/nope")
        assert status == 404
        status, _, _ = await srv.request("GET", "/v1/completions")
        assert status == 405
        status, _, body = await srv.request(
            "POST", "/v1/completions", {"prompt": "not token ids"}
        )
        assert status == 400 and "prompt" in json.loads(body)["error"]
        status, _, _ = await srv.request(
            "POST", "/v1/completions",
            {"prompt": prompt, "temperature": -1.0},
        )
        assert status == 400
        # oversized request rejected cleanly, engine stays healthy
        status, _, _ = await srv.request(
            "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 100000},
        )
        assert status == 400
        status, _, _ = await srv.request("GET", "/healthz")
        assert status == 200

    _with_server(pair)(fn)


def test_backpressure_returns_429_when_saturated(pair):
    prompts = _prompts(4, seed=7)

    async def fn(srv):
        hogs = [
            asyncio.ensure_future(srv.stream_raw(
                {"prompt": prompts[i], "max_tokens": 40, "seed": i}
            ))
            for i in range(3)  # 2 decode slots + the 1-deep queue
        ]
        got_429 = False
        for _ in range(200):
            status, _, _ = await srv.request(
                "POST", "/v1/completions",
                {"prompt": prompts[3], "max_tokens": 4, "wait": False},
            )
            if status == 429:
                got_429 = True
                break
            await asyncio.sleep(0.02)
        results = await asyncio.gather(*hogs)
        assert got_429, "saturated queue never surfaced HTTP 429"
        assert all(status == 200 for status, _, _ in results)

    _with_server(pair, max_queued=1)(fn)


def test_malformed_content_length_gets_400(pair):
    async def fn(srv):
        reader, writer = await asyncio.open_connection("127.0.0.1", srv.port)
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            b"Content-Length: abc\r\n\r\n"
        )
        await writer.drain()
        raw = await reader.read()
        writer.close()
        head = raw.partition(b"\r\n\r\n")[0]
        assert b" 400 " in head.splitlines()[0], head
        # the server survives the malformed request
        status, _, body = await srv.request("GET", "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"

    _with_server(pair)(fn)
