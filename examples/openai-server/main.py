"""OpenAI-compatible serving surface over the continuous-batching engine.

Drop-in endpoints for clients speaking the OpenAI REST shapes:

  GET  /v1/models                      -> model listing
  POST /v1/completions                 -> text completion (+SSE streaming)
  POST /v1/chat/completions            -> chat completion (+SSE streaming)

Streaming responses emit `data: {json}` SSE chunks and terminate with
`data: [DONE]`, matching the OpenAI wire contract, so existing SDKs can
point their base_url here. The engine underneath is the same PagedLLMEngine
the native /generate endpoint uses (examples/llm-server), with every
framework feature available (kernel decode, int8 KV, speculation, drain).
"""

import json
import os
import sys
import time
import uuid

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from gofr_tpu import App, Stream  # noqa: E402
from gofr_tpu.http.errors import InvalidParam, RequestTimeout  # noqa: E402
from gofr_tpu.http.responder import Raw  # noqa: E402

import importlib.util  # noqa: E402


def _load_llm_server():
    """Import the llm-server example under a UNIQUE module name: a bare
    `import main` would collide with whatever other example's main.py is
    already cached in sys.modules (test suites load several)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "llm-server", "main.py")
    cached = sys.modules.get("example_llm_server_engine")
    if cached is not None:
        return cached
    spec = importlib.util.spec_from_file_location("example_llm_server_engine",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module   # cache: one shared instance per process
    spec.loader.exec_module(module)
    return module


_llm_server = _load_llm_server()
build_engine = _llm_server.build_engine
# engine 503 sheds (draining / stalled / breaker-open DeviceLostError) →
# ServiceUnavailable + Retry-After, shared with the native surface
_raise_for_shed = _llm_server._raise_for_shed


def _render_chat(messages) -> str:
    """Minimal chat template: role-tagged turns + assistant cue. A real
    deployment swaps this for the model family's template."""
    lines = [f"{m.get('role', 'user')}: {m.get('content', '')}"
             for m in messages]
    lines.append("assistant:")
    return "\n".join(lines)


def build_app(**kw) -> App:
    app = App(**kw)
    # sampling_controls ON by default: an OpenAI surface must honor client
    # top_p (SAMPLING_CONTROLS=false trades that for a leaner sampler)
    engine = build_engine(app, default_sampling_controls=True)
    app.engine = engine    # reachable for operators/tests (llm-server parity)
    # per-request flight recorder + /debug/requests + SLO goodput gauges
    # (llm-server parity; FLIGHT_RECORDER=false opts out)
    if app.config.get_bool("FLIGHT_RECORDER", True):
        recorder = app.enable_flight_recorder(engine)
        # uniform journey surface: GET /debug/journey[/{id}] here too
        app.enable_journey(engine)
        # replayable loadgen trace at GET /debug/trace (llm-server
        # parity; FLIGHT_TRACE_EXPORT=false opts out)
        if app.config.get_bool("FLIGHT_TRACE_EXPORT", True):
            from gofr_tpu.loadgen.capture import \
                install_recorder_trace_route

            install_recorder_trace_route(app, recorder)
    # GET /debug/engine + utilization gauges + HBM sampler (llm-server
    # parity; ENGINE_SNAPSHOT=false opts out)
    if app.config.get_bool("ENGINE_SNAPSHOT", True):
        app.enable_engine_snapshot(engine)
    # GET /debug/steps + step histograms/straggler sentinel (llm-server
    # parity; STEP_LEDGER=false opts out)
    if app.config.get_bool("STEP_LEDGER", True):
        app.enable_step_ledger(engine)
    # Perfetto trace export at GET /debug/timeline (llm-server parity;
    # TIMELINE=false opts out, TIMELINE_STEPS sets the window)
    if app.config.get_bool("TIMELINE", True):
        app.enable_timeline(engine)
    # host sampling profiler at GET /debug/hostprof (llm-server parity;
    # HOSTPROF=false or HOSTPROF_HZ<=0 opts out)
    if app.config.get_bool("HOSTPROF", True):
        app.enable_hostprof(engine)
    # incident autopsy plane: GET /debug/slo + /debug/incidents (llm-server
    # parity; INCIDENT_AUTOPSY=false opts out, SLO_BURN_*/INCIDENT_* tune)
    if app.config.get_bool("INCIDENT_AUTOPSY", True):
        burn, _ = app.enable_incident_autopsy(engine)
        app.slo_burn = burn    # llm-server parity: harnesses re-target SLOs
    # chaos plane (llm-server parity): 404s unless FAULT_INJECTION=true
    app.enable_fault_injection(engine)
    # QoS serving plane (llm-server parity): opt-IN via QOS=true —
    # classes/quotas/shed ladder/batch lane + GET /debug/qos
    if app.config.get_bool("QOS", False):
        app.enable_qos(engine)
    # capacity observatory (llm-server parity): GET /debug/capacity,
    # app_tpu_meter_* / app_tpu_capacity_*; CAPACITY=false opts out
    if app.config.get_bool("CAPACITY", True):
        app.enable_capacity(engine)
    # disaggregated pair (DISAGG_MODE=both, llm-server parity): submits go
    # through the router's prefill/decode split; GET /debug/disagg
    router = getattr(engine, "disagg_router", None)
    if router is not None:
        from gofr_tpu.tpu.disagg import install_routes as _disagg_routes

        _disagg_routes(app, router)
    submitter = router if router is not None else engine
    tokenizer = engine.tokenizer
    model_id = app.config.get_or_default("MODEL_PRESET", "debug")

    # elastic lifecycle + drain-with-migration surface (llm-server
    # parity): advertise warming/serving/draining via /stats below, land
    # peer migrations on POST /migrate, drain via POST /debug/drain
    app.enable_drain_migration(engine)
    lifecycle = engine.lifecycle

    @app.get("/stats")
    def stats(ctx):  # noqa: ARG001 - fleet probe payload (llm-server parity)
        fleet = {"lifecycle": lifecycle.state}
        qos_ctl = getattr(engine, "qos", None)
        if qos_ctl is not None:
            fleet["qos"] = {"scaleout_wanted": qos_ctl.scaleout_wanted}
        util = getattr(engine, "util", None)
        if util is not None:
            fleet["duty_cycle"] = util.window_stats()["duty_cycle"]
        return {
            "active_slots": sum(1 for s in engine.slots if s.active),
            "queue_depth": engine._pending.qsize(),
            "stall_seconds": round(engine.stall_seconds, 1),
            "fleet": fleet,
        }

    # parameters this surface cannot honor are REJECTED (400), never
    # silently ignored — a client that sent frequency_penalty=0.8 must not
    # get un-penalized text labeled as if its request was honored. The
    # no-op defaults (0 penalties, empty logit_bias, best_of=1) pass, since
    # SDKs send them unprompted.
    _UNSUPPORTED_NONDEFAULT = (
        ("presence_penalty", lambda v: float(v) != 0.0),
        ("frequency_penalty", lambda v: float(v) != 0.0),
        ("logit_bias", lambda v: bool(v)),
        ("best_of", lambda v: int(v) > 1),
        ("suffix", lambda v: bool(v)),
    )

    def _params(body: dict):
        """Parse/validate the shared generation params once (a bad type is
        a 400 parameter error, not a 500)."""
        for name, is_nondefault in _UNSUPPORTED_NONDEFAULT:
            if name in body:
                try:
                    nondefault = is_nondefault(body[name])
                except (TypeError, ValueError) as exc:
                    raise InvalidParam([name]) from exc
                if nondefault:
                    raise InvalidParam(
                        [f"{name} is not supported by this server"])
        try:
            max_tokens = int(body.get("max_tokens", 16))
            temperature = float(body.get("temperature", 1.0))
            # top_p=1.0 is the OpenAI default (no truncation) -> disabled;
            # top_k is the common extension (0 disables)
            top_p = float(body.get("top_p", 1.0))
            top_k = int(body.get("top_k", 0))
            # extension (vLLM-style): stop conditions suppressed until
            # this floor of emitted tokens
            min_tokens = int(body.get("min_tokens", 0))
        except (TypeError, ValueError) as exc:
            raise InvalidParam(["max_tokens", "temperature", "top_p",
                                "top_k", "min_tokens"]) from exc
        if max_tokens < 1:
            raise InvalidParam(["max_tokens"])
        if not 0.0 < top_p <= 1.0:
            raise InvalidParam(["top_p must be in (0, 1]"])
        if top_k < 0:
            raise InvalidParam(["top_k must be >= 0"])
        if top_p >= 1.0:
            top_p = 0.0                       # 1.0 == keep everything
        if (top_p or top_k) and not engine.sampling_controls:
            raise InvalidParam(
                ["top_p/top_k need SAMPLING_CONTROLS=true on this server"])
        if not 0 <= min_tokens <= max_tokens:
            raise InvalidParam(["min_tokens must be 0..max_tokens"])
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        if not all(isinstance(s, str) for s in stop):
            raise InvalidParam(["stop"])
        return max_tokens, temperature, stop, min_tokens, top_p, top_k

    def _encode_checked(prompt: str):
        prompt_tokens = tokenizer.encode(prompt)
        if len(prompt_tokens) > engine.admission_limit:
            # the OpenAI contract: context_length_exceeded is a 400, never
            # a silent truncation that would drop system prompts unnoticed
            raise InvalidParam(
                [f"prompt: {len(prompt_tokens)} tokens exceeds the model "
                 f"context limit ({engine.admission_limit})"])
        return prompt_tokens

    def _submit_tokens(prompt_tokens, max_tokens: int, temperature: float,
                       min_tokens: int = 0, top_p: float = 0.0,
                       top_k: int = 0, ctx=None):
        # ctx threads the caller's trace context through to the engine so
        # the flight recorder's engine child spans (queue/prefill/decode)
        # share the inbound trace id. QoS class/tenant come from the
        # request headers (the OpenAI body shape has no field for them);
        # unknown class strings 400 inside submit (tpu/qos.py)
        qos_class = (ctx.request.header("X-QoS-Class") or None
                     if ctx is not None else None)
        tenant = (str(ctx.request.header("X-Tenant") or "")
                  if ctx is not None else "")
        if lifecycle.state == "draining":
            from gofr_tpu.http.errors import ServiceUnavailable

            # new sessions belong on a peer (llm-server parity)
            raise ServiceUnavailable("replica is draining",
                                     retry_after_s=1.0)
        try:
            return submitter.submit(
                prompt_tokens, max_new_tokens=max_tokens,
                temperature=temperature,
                stop_tokens={tokenizer.EOS},
                span=ctx.span if ctx is not None else None,
                traceparent=(ctx.request.traceparent
                             if ctx is not None else None),
                min_tokens=min_tokens, top_p=top_p, top_k=top_k,
                qos_class=qos_class, tenant=tenant)
        except ValueError:
            raise
        except Exception as exc:  # noqa: BLE001 - sheds → 503 + Retry-After
            _raise_for_shed(exc)

    def _finish_reason(n_emitted: int, max_tokens: int) -> str:
        return "length" if n_emitted >= max_tokens else "stop"

    def _apply_stops(text: str, n_tokens: int, max_tokens: int, stop_strs,
                     floor_chars: int = 0):
        """Stop strings only match at offsets >= floor_chars — the text of
        the first min_tokens tokens is immune, mirroring the engine's
        min_tokens rule for stop token ids."""
        finish = _finish_reason(n_tokens, max_tokens)
        for s in stop_strs:
            idx = text.find(s, floor_chars)
            if idx >= 0:
                text = text[:idx]
                finish = "stop"
        return text, finish

    def _floor_chars(tokens, min_tokens: int) -> int:
        if min_tokens <= 0 or not tokens:
            return 0
        return len(tokenizer.decode(tokens[:min_tokens]))

    def _parse_logprobs(body: dict, chat: bool):
        """OpenAI logprobs semantics, split by surface. Returns None (off)
        or the number of top alternatives to attach (0 = chosen only).

        completions: `logprobs: 0..5` (int). chat: `logprobs: true` +
        `top_logprobs: 0..20`. Served by the teacher-forced scoring pass
        (engine.score) after generation completes — exact decode-time
        distributions, zero hot-path cost when unused."""
        if chat:
            flag = body.get("logprobs")
            if flag in (None, False):
                if body.get("top_logprobs"):
                    raise InvalidParam(["top_logprobs requires logprobs=true"])
                return None
            if flag is not True:
                raise InvalidParam(["logprobs"])
            try:
                n = int(body.get("top_logprobs", 0) or 0)
            except (TypeError, ValueError) as exc:
                raise InvalidParam(["top_logprobs"]) from exc
            if not 0 <= n <= 20:
                raise InvalidParam(["top_logprobs must be 0..20"])
            return n
        if body.get("top_logprobs"):
            raise InvalidParam(["top_logprobs is a chat parameter; "
                                "completions take logprobs=0..5"])
        v = body.get("logprobs")
        if v is None:
            return None
        if isinstance(v, bool):
            # chat-style true/false on the completions surface: OpenAI
            # 400s the non-integer rather than coercing 0/1
            raise InvalidParam(["logprobs must be an integer 0..5"])
        try:
            n = int(v)
        except (TypeError, ValueError) as exc:
            raise InvalidParam(["logprobs"]) from exc
        if not 0 <= n <= 5:
            raise InvalidParam(["logprobs must be 0..5"])
        return n

    def _check_scoreable(prompt_len: int, max_tokens: int) -> None:
        """Reject un-scoreable logprobs requests AT ADMISSION: generation
        can run past the largest scoring bucket (admission caps the prompt,
        not prompt+completion), and discovering that after paying for the
        whole generation would be a 500 instead of this 400."""
        cap = engine.prefill_buckets[-1]
        if prompt_len + max_tokens > cap:
            raise InvalidParam(
                [f"logprobs supports prompt+max_tokens up to {cap} "
                 f"tokens on this server"])

    def _token_bytes(token_id: int) -> bytes:
        tb = getattr(tokenizer, "decode_token_bytes", None)
        if tb is not None:
            return tb(token_id)
        return tokenizer.decode_token(token_id).encode("utf-8", "ignore")

    def _tokens_for_text(tokens, text: str):
        """The largest token prefix whose decoded concatenation fits the
        (possibly stop-string-truncated) returned text — logprobs must
        describe the text the client actually received, not generation the
        stop rule cut away."""
        out, acc = [], 0
        for t in tokens:
            piece = tokenizer.decode_token(int(t))
            if acc + len(piece) > len(text):
                break
            acc += len(piece)
            out.append(t)
        return out

    def _logprobs_payload(chat: bool, prompt_toks, tokens, n_top: int,
                          text=None):
        """Format engine.score output in the surface's shape. `text`
        (when given) clips the scored tokens to the returned text."""
        if text is not None:
            tokens = _tokens_for_text(tokens, text)
        if not tokens:
            return {"content": []} if chat else {
                "tokens": [], "token_logprobs": [], "top_logprobs": None,
                "text_offset": []}
        chosen, top_ids, top_lps = engine.score(prompt_toks, tokens,
                                                top=max(n_top, 1))
        if chat:
            content = []
            for t, c, irow, lrow in zip(tokens, chosen, top_ids, top_lps):
                entry = {"token": tokenizer.decode_token(int(t)),
                         "logprob": round(float(c), 6),
                         "bytes": list(_token_bytes(int(t)))}
                if n_top:
                    entry["top_logprobs"] = [
                        {"token": tokenizer.decode_token(int(i)),
                         "logprob": round(float(l), 6),
                         "bytes": list(_token_bytes(int(i)))}
                        for i, l in zip(irow[:n_top], lrow[:n_top])]
                content.append(entry)
            return {"content": content}
        token_strs = [tokenizer.decode_token(int(t)) for t in tokens]
        offsets, off = [], 0
        for s in token_strs:
            offsets.append(off)
            off += len(s)
        top = None
        if n_top:
            # keyed by decoded string (the OpenAI completions shape): with
            # a byte-level vocab two alternative ids can decode to the same
            # string — keep the best-probability one (ids arrive sorted
            # descending, so first insert wins)
            top = []
            for irow, lrow in zip(top_ids, top_lps):
                d = {}
                for i, l in zip(irow[:n_top], lrow[:n_top]):
                    d.setdefault(tokenizer.decode_token(int(i)),
                                 round(float(l), 6))
                top.append(d)
        return {"tokens": token_strs,
                "token_logprobs": [round(float(c), 6) for c in chosen],
                "top_logprobs": top, "text_offset": offsets}

    def _multi_completion(ctx, chat, prompt, n_choices, max_tokens,
                          temperature, stop_strs, min_tokens, top_p, top_k,
                          lp_n=None):
        """n > 1: fan the prompt out as n engine requests (they batch into
        the same continuous-batching slots) and collect n choices. Encode
        once; ANY failure cancels every sibling so abandoned requests
        can't keep occupying decode slots."""
        prompt_toks = _encode_checked(prompt)
        if lp_n is not None:
            _check_scoreable(len(prompt_toks), max_tokens)
        requests = []
        choices, total_out = [], 0
        try:
            for _ in range(n_choices):
                requests.append(_submit_tokens(prompt_toks, max_tokens,
                                               temperature, min_tokens,
                                               top_p, top_k, ctx=ctx))
            for idx, req in enumerate(requests):
                try:
                    tokens = req.result(timeout_s=ctx.remaining())
                except TimeoutError as exc:
                    raise RequestTimeout() from exc
                total_out += len(tokens)
                text, finish = _apply_stops(tokenizer.decode(tokens),
                                            len(tokens), max_tokens,
                                            stop_strs,
                                            _floor_chars(tokens, min_tokens))
                body = ({"message": {"role": "assistant", "content": text}}
                        if chat else {"text": text})
                lp = (_logprobs_payload(chat, prompt_toks, tokens, lp_n,
                                        text=text)
                      if lp_n is not None else None)
                choices.append(dict(index=idx, finish_reason=finish,
                                    logprobs=lp, **body))
        except BaseException:
            for req in requests:
                req.cancel()
            raise
        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        return Raw({
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()),
            "model": model_id, "choices": choices,
            "usage": {"prompt_tokens": len(prompt_toks),
                      "completion_tokens": total_out,
                      "total_tokens": len(prompt_toks) + total_out},
        })

    @app.get("/v1/models")
    def models(ctx):
        return Raw({"object": "list",
                    "data": [{"id": model_id, "object": "model",
                              "owned_by": "gofr_tpu"}]})

    def _pin_conversation(conversation_id, prompt_toks, out_tokens):
        """Resumable conversations: pin this turn's trunk pages (prompt +
        response, full pages only) through the host KV tier so the
        follow-up request restores them instead of re-prefilling. No-op
        without KV_HOST_TIER_BYTES; never fails the response."""
        pin = getattr(engine, "pin_conversation", None)
        if not conversation_id or pin is None:
            return
        try:
            pin(conversation_id, list(prompt_toks) + list(out_tokens))
        except Exception:
            pass

    def _completion(ctx, chat: bool):
        body = ctx.bind()
        if not isinstance(body, dict):
            raise InvalidParam(["body"])
        if chat:
            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                raise InvalidParam(["messages"])
            prompt = _render_chat(messages)
        else:
            prompt = body.get("prompt")
            if not isinstance(prompt, str) or not prompt:
                raise InvalidParam(["prompt"])
        (max_tokens, temperature, stop_strs, min_tokens, top_p,
         top_k) = _params(body)
        conversation_id = body.get("conversation_id")
        if conversation_id is not None and not isinstance(conversation_id,
                                                          str):
            raise InvalidParam(["conversation_id"])
        lp_n = _parse_logprobs(body, chat)
        if lp_n is not None and body.get("stream"):
            # scoring runs AFTER generation; attaching it to a stream would
            # mean holding every chunk back — reject honestly instead
            raise InvalidParam(["logprobs are not supported with "
                               "stream=true on this server"])
        try:
            n_choices = int(body.get("n", 1))
        except (TypeError, ValueError) as exc:
            raise InvalidParam(["n"]) from exc
        if not 1 <= n_choices <= max(1, engine.n_slots):
            raise InvalidParam([f"n must be 1..{engine.n_slots}"])
        if n_choices > 1:
            if body.get("stream"):
                raise InvalidParam(["n: streaming supports n=1"])
            if temperature <= 0.0:
                # greedy sampling is deterministic: n identical choices
                # would be a silent lie, match OpenAI's temperature advice
                raise InvalidParam(["n > 1 requires temperature > 0"])
            return _multi_completion(ctx, chat, prompt, n_choices,
                                     max_tokens, temperature, stop_strs,
                                     min_tokens, top_p, top_k, lp_n=lp_n)
        prompt_toks = _encode_checked(prompt)
        if lp_n is not None:
            _check_scoreable(len(prompt_toks), max_tokens)
        request = _submit_tokens(prompt_toks, max_tokens, temperature,
                                 min_tokens, top_p, top_k, ctx=ctx)
        created = int(time.time())
        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        obj = "chat.completion" if chat else "text_completion"
        chunk_obj = "chat.completion.chunk" if chat else "text_completion"

        def _chunk(text=None, finish=None, role=None):
            if chat:
                delta = {}
                if role:
                    delta["role"] = role
                if text:
                    delta["content"] = text
                choice = {"index": 0, "delta": delta, "finish_reason": finish}
            else:
                choice = {"index": 0, "text": text or "",
                          "finish_reason": finish}
            return {"id": rid, "object": chunk_obj, "created": created,
                    "model": model_id, "choices": [choice]}

        if body.get("stream"):
            def chunks():
                from gofr_tpu.models.tokenizer import StreamingDecoder

                decoder = StreamingDecoder(tokenizer)
                count = 0
                if chat:  # role announcement chunk, per the chat protocol
                    yield _chunk(role="assistant")
                # stop strings can split across token boundaries: hold back
                # the last len(longest_stop)-1 chars until more text lands
                hold = max((len(s) for s in stop_strs), default=0) - 1
                acc, sent, stopped = "", 0, False
                out_toks = []
                floor_chars = None if min_tokens else 0
                for token in request.stream():
                    count += 1
                    out_toks.append(token)
                    acc += decoder.push(token)
                    if floor_chars is None:
                        if count < min_tokens:
                            continue_scan = False
                        else:
                            floor_chars = len(acc)  # first min_tokens' text
                            continue_scan = True
                    else:
                        continue_scan = True
                    cut = min((idx for idx in
                               (acc.find(s, max(floor_chars or 0,
                                                sent - hold))
                                for s in stop_strs)
                               if idx >= 0), default=-1) if continue_scan \
                        else -1
                    if cut >= 0:
                        if cut > sent:
                            yield _chunk(text=acc[sent:cut])
                        request.cancel()
                        stopped = True
                        break
                    safe = len(acc) - max(hold, 0)
                    if safe > sent:
                        yield _chunk(text=acc[sent:safe])
                        sent = safe
                if not stopped:
                    acc += decoder.flush()
                    if floor_chars is None:
                        # stream ended (cancel/engine failure) before
                        # min_tokens arrived: everything received is inside
                        # the protected floor — no stop-string scan may
                        # truncate it (ADVICE r3)
                        floor_chars = len(acc)
                    cut = min((idx for idx in
                               (acc.find(s, max(floor_chars or 0,
                                                sent - hold))
                                for s in stop_strs)
                               if idx >= 0), default=-1)
                    end = cut if cut >= 0 else len(acc)
                    stopped = cut >= 0
                    if end > sent:
                        yield _chunk(text=acc[sent:end])
                _pin_conversation(conversation_id, prompt_toks, out_toks)
                finish = "stop" if stopped else _finish_reason(count, max_tokens)
                yield _chunk(finish=finish)
                yield "[DONE]"

            return Stream(chunks(), sse=True, on_close=request.cancel)

        try:
            tokens = request.result(timeout_s=ctx.remaining())
        except TimeoutError as exc:
            raise RequestTimeout() from exc
        _pin_conversation(conversation_id, prompt_toks, tokens)
        text, finish = _apply_stops(tokenizer.decode(tokens), len(tokens),
                                    max_tokens, stop_strs,
                                    _floor_chars(tokens, min_tokens))
        message_or_text = ({"message": {"role": "assistant", "content": text}}
                           if chat else {"text": text})
        lp = (_logprobs_payload(chat, prompt_toks, tokens, lp_n,
                                text=text)
              if lp_n is not None else None)
        return Raw({
            "id": rid, "object": obj, "created": created, "model": model_id,
            "choices": [dict(index=0, finish_reason=finish,
                             logprobs=lp, **message_or_text)],
            "usage": {"prompt_tokens": len(prompt_toks),
                      "completion_tokens": len(tokens),
                      "total_tokens": len(prompt_toks) + len(tokens)},
        })

    @app.post("/v1/completions")
    def completions(ctx):
        return _completion(ctx, chat=False)

    @app.post("/v1/chat/completions")
    def chat_completions(ctx):
        return _completion(ctx, chat=True)

    @app.post("/v1/embeddings")
    def embeddings(ctx):
        """OpenAI embeddings shape over the served model: the sequence
        embedding is the last position's final-norm hidden state
        (engine.embed — the causal summary, E5-Mistral-style pooling),
        L2-normalized per the OpenAI convention. `input` is a string or a
        list of strings; encoding_format float (default) or base64
        (little-endian float32, the OpenAI wire format)."""
        body = ctx.bind()
        if not isinstance(body, dict):
            raise InvalidParam(["body"])
        raw = body.get("input")
        inputs = [raw] if isinstance(raw, str) else raw
        if (not isinstance(inputs, list) or not inputs
                or not all(isinstance(s, str) and s for s in inputs)):
            raise InvalidParam(["input must be a non-empty string or list "
                               "of non-empty strings"])
        if len(inputs) > 256:
            # one forward per item runs on this handler: bound the batch
            # (OpenAI's own cap is 2048 items; this server sizes the bound
            # to its single-chip, request-timeout reality)
            raise InvalidParam(["input supports up to 256 items per "
                               "request on this server"])
        fmt = body.get("encoding_format", "float")
        if fmt not in ("float", "base64"):
            raise InvalidParam(["encoding_format must be float or base64"])
        cap = engine.prefill_buckets[-1]
        # validate EVERY item before paying for any forward pass — a late
        # over-cap item must 400 before the device ran the earlier ones
        token_lists = []
        for idx, text in enumerate(inputs):
            toks = tokenizer.encode(text)
            if len(toks) > cap:
                raise InvalidParam(
                    [f"input[{idx}]: {len(toks)} tokens exceeds the "
                     f"embedding limit ({cap})"])
            token_lists.append(toks)
        data, total_tokens = [], 0
        for idx, toks in enumerate(token_lists):
            total_tokens += len(toks)
            vec = engine.embed(toks)
            if fmt == "base64":
                import base64 as _b64

                emb = _b64.b64encode(
                    vec.astype("<f4").tobytes()).decode("ascii")
            else:
                # full float32 precision, same as the base64 wire format —
                # the two encodings must return the same vector
                emb = [float(x) for x in vec]
            data.append({"object": "embedding", "index": idx,
                         "embedding": emb})
        return Raw({"object": "list", "data": data, "model": model_id,
                    "usage": {"prompt_tokens": total_tokens,
                              "total_tokens": total_tokens}})

    return app


def main() -> None:
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    build_app().run()


if __name__ == "__main__":
    main()
