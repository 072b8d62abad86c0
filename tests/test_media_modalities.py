"""Non-text modalities (image gen, TTS, STT, realtime audio frames) against a
mock provider — llm-gateway PRD FRs :104-311, ADR-0003 media-via-FileStorage."""

import asyncio
import base64

import aiohttp
import pytest
from aiohttp import web

from conftest import boot_stack, stop_stack, ws_event

PNG = (b"\x89PNG\r\n\x1a\n" + b"\x00" * 16)
MP3 = b"ID3fake-mp3-bytes" * 4
MP4 = b"\x00\x00\x00 ftypisom" + b"\x00" * 24


@pytest.fixture()
def stack(fresh_registry):
    from cyberfabric_core_tpu.modkit.registry import Registration
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule
    from cyberfabric_core_tpu.modules.credstore import CredStoreModule
    from cyberfabric_core_tpu.modules.file_storage import FileStorageModule
    from cyberfabric_core_tpu.modules.llm_gateway.module import LlmGatewayModule
    from cyberfabric_core_tpu.modules.model_registry import ModelRegistryModule
    from cyberfabric_core_tpu.modules.oagw import OagwModule
    from cyberfabric_core_tpu.modules.resolvers import TenantResolverModule

    fresh_registry._REGISTRATIONS.clear()
    regs = [
        Registration("api_gateway", ApiGatewayModule, (),
                     ("rest_host", "stateful", "system")),
        Registration("tenant_resolver", TenantResolverModule, (), ("system",)),
        Registration("credstore", CredStoreModule, ("tenant_resolver",),
                     ("db", "rest")),
        Registration("oagw", OagwModule, ("credstore",), ("db", "rest")),
        Registration("model_registry", ModelRegistryModule, (), ("db", "rest")),
        Registration("file_storage", FileStorageModule, (), ("rest",)),
        Registration("llm_gateway", LlmGatewayModule, ("model_registry",),
                     ("rest", "stateful")),
    ]
    seen: list[dict] = []

    async def boot():
        mock = web.Application()

        async def images(request):
            body = await request.json()
            seen.append({"path": "images", "body": body})
            return web.json_response({"data": [
                {"b64_json": base64.b64encode(PNG).decode(),
                 "revised_prompt": "a nicer cat"}]})

        async def speech(request):
            body = await request.json()
            seen.append({"path": "speech", "body": body})
            return web.Response(body=MP3, content_type="audio/mpeg")

        async def transcriptions(request):
            post = await request.post()
            seen.append({"path": "stt",
                         "model": post["model"],
                         "bytes": len(post["file"].file.read())})
            return web.json_response({"text": "hello from audio",
                                      "language": "en"})

        video_polls: dict[str, int] = {}

        async def videos(request):
            body = await request.json()
            seen.append({"path": "videos", "body": body})
            # job-shaped create: the gateway must poll for the result
            video_polls["vid-1"] = 0
            return web.json_response({"id": "vid-1", "status": "processing"})

        async def video_status(request):
            vid = request.match_info["vid"]
            video_polls[vid] = video_polls.get(vid, 0) + 1
            seen.append({"path": "video_poll", "id": vid,
                         "n": video_polls[vid]})
            if video_polls[vid] < 2:
                return web.json_response({"id": vid, "status": "processing"})
            return web.json_response({
                "id": vid, "status": "completed",
                "data": [{"b64_json": base64.b64encode(MP4).decode(),
                          "revised_prompt": "a cinematic cat"}]})

        mock.router.add_post("/v1/images/generations", images)
        mock.router.add_post("/v1/videos/generations", videos)
        mock.router.add_get("/v1/videos/generations/{vid}", video_status)
        mock.router.add_post("/v1/audio/speech", speech)
        mock.router.add_post("/v1/audio/transcriptions", transcriptions)
        runner = web.AppRunner(mock)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        mock_port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

        rt, base = await boot_stack({"modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                       "auth_disabled": True}},
            "tenant_resolver": {}, "credstore": {}, "file_storage": {},
            "oagw": {"config": {"allow_insecure_http": True,
                                "allow_private_upstreams": True}},
            "model_registry": {"config": {
                "seed_tenant": "default",
                "models": [
                    {"provider_slug": "media-mock", "provider_model_id": "pix",
                     "approval_state": "approved", "managed": False,
                     "capabilities": {"image_generation": True}},
                    {"provider_slug": "media-mock", "provider_model_id": "vidgen",
                     "approval_state": "approved", "managed": False,
                     "capabilities": {"video_generation": True}},
                    {"provider_slug": "media-mock", "provider_model_id": "tts-1",
                     "approval_state": "approved", "managed": False,
                     "capabilities": {"tts": True}},
                    {"provider_slug": "media-mock", "provider_model_id": "whisper",
                     "approval_state": "approved", "managed": False,
                     "capabilities": {"stt": True}},
                    {"provider_slug": "local", "provider_model_id": "tiny-llama",
                     "approval_state": "approved", "managed": True,
                     "architecture": "llama",
                     "engine_options": {"model_config": "tiny-llama"}},
                ]}},
            "llm_gateway": {"config": {"video_poll_interval_s": 0.02}},
        }}, extra=regs)
        async with aiohttp.ClientSession() as s:
            await s.put(f"{base}/v1/credstore/secrets/media-key",
                        json={"value": "sk-media"})
            await s.post(f"{base}/v1/oagw/upstreams", json={
                "slug": "media-mock",
                "base_url": f"http://127.0.0.1:{mock_port}/v1",
                "auth": {"type": "bearer", "secret_ref": "media-key"}})
        return rt, runner, base

    loop = asyncio.new_event_loop()
    rt, runner, base = loop.run_until_complete(boot())
    yield loop, base, seen
    loop.run_until_complete(stop_stack(rt))
    loop.run_until_complete(runner.cleanup())
    loop.close()


def _req(loop, method, url, **kw):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, **kw) as r:
                try:
                    return r.status, await r.json(content_type=None)
                except Exception:  # noqa: BLE001
                    return r.status, await r.read()

    return loop.run_until_complete(go())


def test_image_generation_stored_via_file_storage(stack):
    loop, base, seen = stack
    status, body = _req(loop, "POST", f"{base}/v1/images/generations", json={
        "model": "media-mock::pix", "prompt": "a cat on a TPU"})
    assert status == 200, body
    assert body["model_used"] == "media-mock::pix"
    url = body["data"][0]["url"]
    assert url.startswith("/v1/files/")
    assert body["data"][0]["revised_prompt"] == "a nicer cat"
    # the stored bytes round-trip through file-storage
    status, raw = _req(loop, "GET", f"{base}{url}")
    assert status == 200 and raw == PNG
    assert seen[0]["body"]["prompt"] == "a cat on a TPU"
    assert seen[0]["body"]["model"] == "pix"


def test_video_generation_polled_and_stored(stack):
    loop, base, seen = stack
    status, body = _req(loop, "POST", f"{base}/v1/videos/generations", json={
        "model": "media-mock::vidgen", "prompt": "a TPU pod spinning",
        "duration_seconds": 4})
    assert status == 200, body
    assert body["model_used"] == "media-mock::vidgen"
    assert body["data"][0]["revised_prompt"] == "a cinematic cat"
    url = body["data"][0]["url"]
    assert url.startswith("/v1/files/")
    status, raw = _req(loop, "GET", f"{base}{url}")
    assert status == 200 and raw == MP4
    create = next(s for s in seen if s.get("path") == "videos")
    assert create["body"]["model"] == "vidgen"
    assert create["body"]["duration_seconds"] == 4
    # the job really was polled to completion (two status round trips)
    assert [s["n"] for s in seen if s.get("path") == "video_poll"] == [1, 2]


def test_video_capability_gated(stack):
    loop, base, _ = stack
    # the image model does not declare video_generation -> 409, never billed
    status, body = _req(loop, "POST", f"{base}/v1/videos/generations", json={
        "model": "media-mock::pix", "prompt": "nope"})
    assert status == 409 and body["code"] == "capability_missing"


def test_tts_audio_via_file_storage(stack):
    loop, base, seen = stack
    status, body = _req(loop, "POST", f"{base}/v1/audio/speech", json={
        "model": "media-mock::tts-1", "input": "read this aloud",
        "voice": "nova"})
    assert status == 200, body
    assert body["mime_type"] == "audio/mpeg"
    status, raw = _req(loop, "GET", f"{base}{body['url']}")
    assert status == 200 and raw == MP3
    call = [s for s in seen if s["path"] == "speech"][0]
    assert call["body"]["input"] == "read this aloud"
    assert call["body"]["voice"] == "nova"


def test_stt_transcription(stack):
    loop, base, seen = stack
    status, body = _req(
        loop, "POST",
        f"{base}/v1/audio/transcriptions?model=media-mock::whisper",
        data=b"RIFFfake-wav-bytes", headers={"Content-Type": "audio/wav"})
    assert status == 200, body
    assert body["text"] == "hello from audio"
    call = [s for s in seen if s["path"] == "stt"][0]
    assert call["model"] == "whisper"
    assert call["bytes"] == len(b"RIFFfake-wav-bytes")


def test_capability_and_managed_gating(stack):
    loop, base, _ = stack
    # model without the capability → 409
    status, body = _req(loop, "POST", f"{base}/v1/images/generations", json={
        "model": "media-mock::whisper", "prompt": "x"})
    assert status == 409 and body["code"] == "capability_missing"
    # managed model → 501
    status, body = _req(loop, "POST", f"{base}/v1/images/generations", json={
        "model": "local::tiny-llama", "prompt": "x"})
    assert status == 501 and body["code"] == "modality_not_implemented"


def test_realtime_binary_audio_frames(stack):
    loop, base, seen = stack

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.ws_connect(f"{base}/v1/realtime") as ws:
                seen_events = []
                await ws.send_bytes(b"RIFF-chunk-1")
                ack1 = await ws_event(ws, seen_events)
                await ws.send_bytes(b"-chunk-2")
                ack2 = await ws_event(ws, seen_events)
                await ws.send_json({"type": "audio.commit",
                                    "model": "media-mock::whisper",
                                    "mime_type": "audio/wav"})
                deltas = []
                ev = await ws_event(ws, seen_events)
                while ev["type"] == "transcript.delta":
                    deltas.append(ev["delta"])
                    ev = await ws_event(ws, seen_events)
                await ws.send_json({"type": "session.close"})
                return ack1, ack2, deltas, ev

    ack1, ack2, deltas, transcript = loop.run_until_complete(go())
    assert ack1 == {"type": "audio.appended", "buffered_bytes": 12}
    assert ack2["buffered_bytes"] == 20
    # incremental deltas precede and concatenate to the final transcript
    assert deltas and "".join(deltas) == "hello from audio"
    assert transcript["type"] == "transcript"
    assert transcript["text"] == "hello from audio"
    call = [s for s in seen if s["path"] == "stt"][-1]
    assert call["bytes"] == 20  # both frames committed as one buffer


def test_realtime_full_audio_loop(stack):
    """The DESIGN.md:262-271 bidirectional loop end to end over one socket:
    audio-in → transcript deltas → chat on the transcript → TTS audio OUT as
    binary frames (round-2 verdict item 8)."""
    loop, base, seen = stack

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.ws_connect(f"{base}/v1/realtime") as ws:
                events = []  # every message so far: what a failed wait reports
                # 1) audio in + commit → transcript
                await ws.send_bytes(b"RIFF" + b"\x00" * 60)
                assert (await ws_event(ws, events))["type"] == "audio.appended"
                await ws.send_json({"type": "audio.commit",
                                    "model": "media-mock::whisper"})
                ev = await ws_event(ws, events)
                deltas = []
                while ev["type"] == "transcript.delta":
                    deltas.append(ev["delta"])
                    ev = await ws_event(ws, events)
                assert ev["type"] == "transcript"
                transcript_text = ev["text"]

                # 2) chat on the transcript, asking for spoken output
                await ws.send_json({
                    "type": "chat.create", "id": "loop-1",
                    "response_audio": {"model": "media-mock::tts-1",
                                       "voice": "nova", "format": "mp3"},
                    "request": {
                        "model": "local::tiny-llama",
                        "messages": [{"role": "user", "content": [
                            {"type": "text", "text": transcript_text}]}],
                        "max_tokens": 4}})
                tokens, audio_out = [], bytearray()
                begin = done = out_done = None
                while out_done is None:
                    ev = await ws_event(ws, events)
                    if isinstance(ev, bytes):
                        audio_out.extend(ev)
                    elif ev["type"] == "token":
                        tokens.append(ev["content"])
                    elif ev["type"] == "done":
                        done = ev
                    elif ev["type"] == "audio.out.begin":
                        begin = ev
                    elif ev["type"] == "audio.out.done":
                        out_done = ev
                    elif ev["type"] == "error":
                        raise AssertionError(ev)
                await ws.send_json({"type": "session.close"})
                return deltas, tokens, done, begin, bytes(audio_out), out_done

    deltas, tokens, done, begin, audio_out, out_done = loop.run_until_complete(go())
    assert deltas, "expected at least one transcript delta"
    assert tokens, "expected streamed chat tokens"
    assert done["finish_reason"] in ("stop", "length")
    assert begin["mime_type"] == "audio/mpeg"
    assert begin["model_used"] == "media-mock::tts-1"
    assert audio_out == MP3                      # TTS bytes over the socket
    assert out_done["bytes"] == len(MP3)
    # the TTS provider was fed the CHAT REPLY, not the transcript
    tts_call = [s for s in seen if s["path"] == "speech"][-1]
    assert tts_call["body"]["voice"] == "nova"
    assert tts_call["body"]["input"] == "".join(tokens)


@pytest.mark.parametrize("ending", ["empty_reply", "handler_exception",
                                    "slow_reply"])
def test_realtime_response_audio_ends_with_one_terminal_event(
        stack, monkeypatch, ending):
    """A `chat.create` frame that asked for `response_audio` ends with exactly
    one of `audio.out.done` or `error` for its id, whatever the chat did: a
    reply with no text is `audio.out.done` with `bytes: 0` (nothing sent to
    the TTS provider), an exception that is no ProblemError is the REST
    mapping's `internal_error`, and a reply slower than the server's heartbeat
    and pong wait (here 0.4 s + 0.2 s) is not cut by them. The session
    outlives each and closes clean."""
    from cyberfabric_core_tpu.modules.llm_gateway import module as gateway_module
    from cyberfabric_core_tpu.modules.sdk import ChatStreamChunk

    async def chat_once(self, ctx, model, body, mode="chat"):
        if ending == "handler_exception":
            raise RuntimeError("boom")
        if ending == "slow_reply":
            await asyncio.sleep(1.5)
            yield ChatStreamChunk(request_id="r", text="late")
        yield ChatStreamChunk(request_id="r", finish_reason="stop",
                              usage={"input_tokens": 3, "output_tokens": 0})

    monkeypatch.setattr(gateway_module.LlmGatewayModule, "_chat_once", chat_once)
    real_response = web.WebSocketResponse
    monkeypatch.setattr(gateway_module.web, "WebSocketResponse",
                        lambda heartbeat: real_response(heartbeat=0.4))
    loop, base, seen = stack

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.ws_connect(f"{base}/v1/realtime") as ws:
                events = []
                await ws.send_json({
                    "type": "chat.create", "id": "quiet-1",
                    "response_audio": {"model": "media-mock::tts-1"},
                    "request": {
                        "model": "local::tiny-llama", "max_tokens": 4,
                        "messages": [{"role": "user", "content": [
                            {"type": "text", "text": "say nothing"}]}]}})
                # whatever follows the frame's events answers the next frame:
                # nothing else was sent for quiet-1
                await ws.send_json({"type": "bogus"})
                ev = await ws_event(ws, events)
                while isinstance(ev, bytes) or ev.get("id") == "quiet-1":
                    ev = await ws_event(ws, events)
                assert ev["error"]["code"] == "unknown_frame_type", events
                await ws.send_json({"type": "session.close"})
                closing = await ws.receive(timeout=30.0)
                return events[:-1], closing, ws.close_code

    events, closing, close_code = loop.run_until_complete(go())
    if ending == "empty_reply":
        assert [e["type"] for e in events] == ["done", "audio.out.done"], events
        assert events[-1] == {"type": "audio.out.done", "id": "quiet-1",
                              "bytes": 0}
        assert not [s for s in seen if s["path"] == "speech"]
    elif ending == "slow_reply":
        assert [e if isinstance(e, bytes) else e["type"] for e in events] == [
            "token", "done", "audio.out.begin", MP3, "audio.out.done"], events
        assert events[-1]["bytes"] == len(MP3)
    else:
        assert [e["type"] for e in events] == ["error"], events
        assert events[0]["id"] == "quiet-1"
        assert events[0]["error"]["code"] == "internal_error"
        assert events[0]["error"]["status"] == 500
    assert closing.type == aiohttp.WSMsgType.CLOSE, closing
    assert close_code == 1000


def test_media_usage_reported(stack):
    loop, base, seen = stack
    _req(loop, "POST", f"{base}/v1/images/generations", json={
        "model": "media-mock::pix", "prompt": "count me"})
    _req(loop, "POST", f"{base}/v1/audio/speech", json={
        "model": "media-mock::tts-1", "input": "count me too"})
    s, body = _req(loop, "GET", f"{base}/v1/usage")
    assert s == 200
    usage = body["usage"]
    assert usage.get("images", 0) >= 1
    assert usage.get("media_requests", 0) >= 1
    assert usage.get("tts_bytes", 0) >= 1


def test_undeclared_capabilities_denied(stack):
    """A model with an EMPTY capabilities block gets 409 on media endpoints —
    empty means chat-only, not everything (review finding)."""
    loop, base, _ = stack
    s, _ = _req(loop, "POST", f"{base}/v1/model-registry/models", json={
        "provider_slug": "media-mock", "provider_model_id": "plain-chat",
        "approval_state": "approved"})
    assert s == 201
    s, body = _req(loop, "POST", f"{base}/v1/images/generations", json={
        "model": "media-mock::plain-chat", "prompt": "x"})
    assert s == 409 and body["code"] == "capability_missing"
