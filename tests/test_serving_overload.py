"""Overload-safe serving: admission control (429), per-request
deadlines (queued expiry 504 / mid-decode partial+timeout), readiness
vs liveness, graceful drain, and the serving-path FaultPlan sites.

Engine-level tests drive deadlines through an injectable clock — no
sleeping, fully deterministic; HTTP-level tests use the seeded
FaultPlan (``serving.step`` delays) so timing windows have wide,
reproducible margins."""
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine, QueueFullError
from elephas_tpu.serving_http import ServingServer
from elephas_tpu.utils.faults import FaultPlan, clear_plan, install_plan

from ._greedy import greedy_reference as _ref


@pytest.fixture(autouse=True)
def _clean_plan(monkeypatch):
    """Fault state is process-global: every test starts and ends clean."""
    monkeypatch.delenv("ELEPHAS_TPU_FAULT_PLAN", raising=False)
    clear_plan()
    yield
    clear_plan()


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=64,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as resp:
        return json.loads(resp.read())


def _http_error(fn):
    """Run ``fn``, returning ``(status_code, decoded_body)`` of the
    HTTPError it must raise."""
    with pytest.raises(urllib.error.HTTPError) as exc:
        fn()
    return exc.value.code, json.loads(exc.value.read())


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 300, n)]


def _wait_admitted(engine, timeout=60):
    """Block until some slot is occupied (first admission done)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if any(r is not None for r in engine._rid):
            return
        time.sleep(0.005)
    raise AssertionError("no request was admitted in time")


# --------------------------------------------------------------- engine
def test_engine_queue_full_sheds_deterministically(model):
    """With the backlog at max_queue, submit answers QueueFullError
    (with a retry hint) instead of queueing — and the engine keeps
    serving what it already accepted."""
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1, max_queue=1)
    r1 = eng.submit(_prompt(0, 5), 6)         # straight into the slot
    r2 = eng.submit(_prompt(1, 5), 6)         # backlog: 1/1
    with pytest.raises(QueueFullError) as exc:
        eng.submit(_prompt(2, 5), 6)
    assert exc.value.retry_after_ms >= 50
    assert eng.stats["requests_shed"] == 1
    assert eng.stats["queue_depth"] == 1
    while eng.pending:
        eng.step()
    # accepted work is unharmed by the shed
    assert eng.result(r1) == _ref(params, config, _prompt(0, 5), 6)
    assert eng.result(r2) == _ref(params, config, _prompt(1, 5), 6)


def test_engine_queued_token_bound(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1, max_queued_tokens=10)
    eng.submit(_prompt(0, 5), 4)              # admitted, not queued
    eng.submit(_prompt(1, 8), 4)              # 8 queued tokens: fits
    with pytest.raises(QueueFullError):
        eng.submit(_prompt(2, 8), 4)          # 16 > 10: shed
    assert eng.stats["queued_tokens"] == 8
    # a prompt that could NEVER fit is a permanent error, not a
    # retryable shed — a 429 + backoff would have clients retry forever
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(_prompt(4, 12), 4)
    eng.submit(_prompt(3, 2), 4)              # 10 <= 10: still fits
    while eng.pending:
        eng.step()
    assert eng.stats["queued_tokens"] == 0


def test_engine_queued_expiry_never_reaches_prefill(model):
    """A queued request whose deadline passes is shed BEFORE prefill:
    zero tokens, ``expired`` marked, and the prefill path provably
    never ran for it."""
    params, config = model
    now = [0.0]
    eng = DecodeEngine(params, config, max_slots=1, clock=lambda: now[0])
    prefills = []
    orig = eng._prefill_with_prefixes

    def counting_prefill(prompt, *a, **k):
        prefills.append(list(prompt))
        return orig(prompt, *a, **k)

    eng._prefill_with_prefixes = counting_prefill
    r1 = eng.submit(_prompt(0, 5), 30)              # occupies the slot
    doomed = _prompt(1, 6)
    r2 = eng.submit(doomed, 5, deadline_ms=100)     # queued
    now[0] += 0.2                                   # deadline passes
    eng.step()
    info = eng.result_info(r2)
    assert info == {"tokens": [], "timeout": True, "expired": True}
    assert doomed not in prefills, "expired request reached prefill"
    assert eng.stats["requests_expired"] == 1
    eng.cancel(r1)


def test_engine_mid_decode_deadline_frees_slot_returns_partial(model):
    """An over-deadline ACTIVE request retires mid-decode: the slot
    frees, and the partial output (a strict prefix of the solo greedy
    decode) is returned marked ``timeout``."""
    params, config = model
    now = [0.0]
    eng = DecodeEngine(params, config, max_slots=1, clock=lambda: now[0])
    p = _prompt(0, 5)
    rid = eng.submit(p, 30, deadline_ms=100)
    eng.step()
    eng.step()
    now[0] += 0.2                                   # deadline passes
    eng.step()                                      # enforcement point
    info = eng.result_info(rid)
    assert info["timeout"] and not info["expired"]
    ref = _ref(params, config, p, 30)
    assert 1 <= len(info["tokens"]) < 30
    assert info["tokens"] == ref[:len(info["tokens"])]
    assert all(r is None for r in eng._rid), "slot not freed"
    assert eng.stats["requests_timed_out"] == 1
    # the freed slot admits new work normally
    r2 = eng.submit(p, 4)
    while eng.pending:
        eng.step()
    assert eng.result(r2) == ref[:4]


def test_engine_deadline_validation_and_result_compat(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1)
    with pytest.raises(ValueError):
        eng.submit(_prompt(0, 4), 4, deadline_ms=0)
    with pytest.raises(ValueError):
        DecodeEngine(params, config, max_queue=0)
    # result() keeps its old list shape for non-deadline users
    rid = eng.submit(_prompt(0, 4), 3)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, _prompt(0, 4), 3)
    assert eng.result(rid) is None


def test_engine_submit_fault_site_drop_is_deterministic_shed(model):
    """A FaultPlan 'drop' at serving.submit sheds exactly the planned
    submissions — chaos-testing 429 handling without filling a queue."""
    params, config = model
    eng = DecodeEngine(params, config, max_slots=2)
    install_plan(FaultPlan([{"site": "serving.submit", "action": "drop",
                             "after": 1, "times": 1}]))
    eng.submit(_prompt(0, 4), 2)              # hit 0: clean
    with pytest.raises(QueueFullError):       # hit 1: planned shed
        eng.submit(_prompt(1, 4), 2)
    eng.submit(_prompt(2, 4), 2)              # hit 2: clean again
    assert eng.stats["requests_shed"] == 1


# ----------------------------------------------------------------- http
def test_http_queue_full_answers_429_with_retry_hint(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1, max_queue=1)
    with ServingServer(eng) as srv:
        # slow steps keep the slot occupied for a multi-second window —
        # the backlog state the assertions need must survive even a
        # GIL-contention stall of this (the asserting) thread
        install_plan(FaultPlan([{"site": "serving.step", "action": "delay",
                                 "delay": 0.05, "times": None}]))
        r1 = _post(srv.port, "/v1/submit",
                   {"prompt": _prompt(0, 5), "max_new_tokens": 55})["id"]
        _wait_admitted(eng)                   # backlog empty again
        _post(srv.port, "/v1/submit",
              {"prompt": _prompt(1, 5), "max_new_tokens": 4})
        code, body = _http_error(
            lambda: _post(srv.port, "/v1/submit",
                          {"prompt": _prompt(2, 5), "max_new_tokens": 4}))
        assert code == 429
        assert body["retry_after_ms"] >= 50
        assert "queue full" in body["error"]
        assert _get(srv.port, "/stats")["requests_shed"] == 1
        _post(srv.port, "/v1/cancel", {"id": r1})


def test_http_queued_expiry_answers_504(model):
    """A blocking generate whose deadline passes while queued gets 504
    — and /v1/result for an expired submit also answers 504."""
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1)
    with ServingServer(eng) as srv:
        # slow steps guarantee the doomed requests wait out their 1ms
        # deadlines in the queue (admission only runs between steps),
        # and keep the blocker alive across thread-scheduling stalls
        install_plan(FaultPlan([{"site": "serving.step", "action": "delay",
                                 "delay": 0.05, "times": None}]))
        blocker = _post(srv.port, "/v1/submit",
                        {"prompt": _prompt(0, 5),
                         "max_new_tokens": 55})["id"]
        _wait_admitted(eng)
        code, body = _http_error(
            lambda: _post(srv.port, "/v1/generate",
                          {"prompt": _prompt(1, 6), "max_new_tokens": 4,
                           "deadline_ms": 1}))
        assert code == 504
        assert body["status"] == "expired"
        rid = _post(srv.port, "/v1/submit",
                    {"prompt": _prompt(2, 6), "max_new_tokens": 4,
                     "deadline_ms": 1})["id"]
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                out = _get(srv.port, f"/v1/result?id={rid}")
                assert out["status"] == "pending"
                time.sleep(0.01)
            except urllib.error.HTTPError as err:
                assert err.code == 504
                assert json.loads(err.read())["status"] == "expired"
                break
        else:
            raise AssertionError("expired submit never surfaced as 504")
        assert _get(srv.port, "/stats")["requests_expired"] >= 2
        _post(srv.port, "/v1/cancel", {"id": blocker})


def test_http_mid_decode_deadline_returns_partial_with_timeout(model):
    """Server-side default deadline + slow steps (seeded FaultPlan):
    the response is a 200 with partial tokens and ``"timeout": true``,
    and the partial is a prefix of the solo greedy decode."""
    params, config = model
    p = _prompt(0, 5)
    eng = DecodeEngine(params, config, max_slots=1)
    with ServingServer(eng, default_deadline_ms=500) as srv:
        # warm the prefill/step compiles OUTSIDE the deadline window
        warm = _post(srv.port, "/v1/generate",
                     {"prompt": p, "max_new_tokens": 2,
                      "deadline_ms": 600000})
        assert warm["status"] == "done" and "timeout" not in warm
        install_plan(FaultPlan([{"site": "serving.step", "action": "delay",
                                 "delay": 0.05, "times": None}]))
        out = _post(srv.port, "/v1/generate",
                    {"prompt": p, "max_new_tokens": 40})
        assert out["status"] == "done" and out["timeout"] is True
        ref = _ref(params, config, p, 40)
        assert 1 <= len(out["tokens"]) < 40
        assert out["tokens"] == ref[:len(out["tokens"])]
        assert _get(srv.port, "/stats")["requests_timed_out"] == 1


def test_http_body_size_cap_413(model):
    params, config = model
    with ServingServer(DecodeEngine(params, config, max_slots=1),
                       max_body_bytes=512) as srv:
        code, body = _http_error(
            lambda: _post(srv.port, "/v1/submit",
                          {"prompt": [1] * 1000, "max_new_tokens": 1}))
        assert code == 413
        assert body["max_body_bytes"] == 512
        # under the cap still works
        out = _post(srv.port, "/v1/generate",
                    {"prompt": _prompt(0, 4), "max_new_tokens": 2})
        assert out["status"] == "done"


def test_http_negative_content_length_400(model):
    """A negative Content-Length is truthy AND under the byte cap — it
    must answer 400, never reach read(-1) (read-to-EOF: the unbounded
    buffering the cap exists to prevent)."""
    import http.client

    params, config = model
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=30)
        try:
            conn.putrequest("POST", "/v1/submit")
            conn.putheader("Content-Type", "application/json")
            conn.putheader("Content-Length", "-1")
            conn.endheaders()
            resp = conn.getresponse()
            assert resp.status == 400
            assert "Content-Length" in json.loads(resp.read())["error"]
        finally:
            conn.close()


def test_http_unknown_result_id_404(model):
    params, config = model
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        code, body = _http_error(
            lambda: _get(srv.port, "/v1/result?id=123"))
        assert code == 404
        assert body["status"] == "unknown"
        assert "123" in body["error"]


def test_http_engine_step_crash_flips_health_and_ready(model):
    """FaultPlan-driven engine-step crash: /health turns 500 (liveness
    lost) and /ready goes 503 with the failure — while a blocked
    generate gets an error payload instead of hanging."""
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1)
    srv = ServingServer(eng).start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if _get(srv.port, "/ready")["status"] == "ready":
                    break
            except urllib.error.HTTPError:
                pass
            time.sleep(0.01)
        assert _get(srv.port, "/health")["status"] == "ok"
        install_plan(FaultPlan([{"site": "serving.step", "action": "error",
                                 "message": "injected step crash"}]))
        out = _post(srv.port, "/v1/generate",
                    {"prompt": _prompt(0, 5), "max_new_tokens": 4})
        assert out["status"] == "error"
        assert "injected step crash" in out["error"]
        code, body = _http_error(lambda: _get(srv.port, "/health"))
        assert code == 500 and body["status"] == "error"
        code, body = _http_error(lambda: _get(srv.port, "/ready"))
        assert code == 503 and body["status"] == "failed"
        assert "injected step crash" in body["error"]
    finally:
        srv.stop()


def test_http_stream_write_fault_aborts_like_disconnect(model):
    """A FaultPlan 'error' at serving.stream_write is a deterministic
    mid-stream client disconnect: the server aborts the request and
    releases the slot instead of decoding for nobody."""
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1)
    with ServingServer(eng) as srv:
        install_plan(FaultPlan([{"site": "serving.stream_write",
                                 "action": "error", "after": 1,
                                 "times": 1}]))
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            data=json.dumps({"prompt": _prompt(0, 5),
                             "max_new_tokens": 40,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                for _ in resp:
                    pass
        except Exception:  # noqa: BLE001 — truncated stream is expected
            pass
        deadline = time.time() + 60
        while time.time() < deadline:
            with srv._cond:
                if (all(r is None for r in eng._rid)
                        and not eng._queue and not srv._streams):
                    break
            time.sleep(0.02)
        with srv._cond:
            assert all(r is None for r in eng._rid), \
                "slot still decoding after injected stream death"


def test_readiness_distinct_from_liveness_through_lifecycle(model):
    """/ready is 503 before the engine loop runs and again during
    drain; /health stays 200 throughout (the server is alive in both
    windows)."""
    params, config = model
    srv = ServingServer(DecodeEngine(params, config, max_slots=1))
    # not started: simulate the warming window by flipping the flag back
    srv.start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if _get(srv.port, "/ready")["status"] == "ready":
                    break
            except urllib.error.HTTPError:
                pass
            time.sleep(0.01)
        srv._ready = False          # the pre-first-step warming state
        code, body = _http_error(lambda: _get(srv.port, "/ready"))
        assert code == 503 and body["status"] == "warming"
        assert _get(srv.port, "/health")["status"] == "ok"
        srv._ready = True
        srv.begin_drain()
        code, body = _http_error(lambda: _get(srv.port, "/ready"))
        assert code == 503 and body["status"] == "draining"
        assert _get(srv.port, "/health")["status"] == "ok"
        assert _get(srv.port, "/stats")["draining"] is True
    finally:
        srv.stop()


# ---------------------------------------------------------------- drain
def test_drain_completes_inflight_stream_rejects_new_submits(model):
    """THE acceptance chaos scenario, deterministically seeded: with the
    queue at capacity the server sheds (429) rather than stalls; then
    stop(drain_timeout) finishes an in-flight streaming request —
    token-identical to the solo decode — while new submits answer 503."""
    params, config = model
    p = _prompt(0, 5)
    eng = DecodeEngine(params, config, max_slots=1, max_queue=1)
    srv = ServingServer(eng).start()
    stopped = False
    try:
        # warm compiles so the drained stream's duration is step-bound
        _post(srv.port, "/v1/generate", {"prompt": p, "max_new_tokens": 2})
        # slow-step plan: keeps the slot occupied through phase (1) —
        # even across a GIL-contention stall of this thread — and the
        # stream in flight across the drain in phase (2)
        install_plan(FaultPlan([{"site": "serving.step", "action": "delay",
                                 "delay": 0.05, "times": None}]))
        # (1) queue at capacity -> shed, not stall
        r1 = _post(srv.port, "/v1/submit",
                   {"prompt": p, "max_new_tokens": 55})["id"]
        _wait_admitted(eng)
        r2 = _post(srv.port, "/v1/submit",
                   {"prompt": p, "max_new_tokens": 2})["id"]
        code, _ = _http_error(
            lambda: _post(srv.port, "/v1/submit",
                          {"prompt": p, "max_new_tokens": 2}))
        assert code == 429
        _post(srv.port, "/v1/cancel", {"id": r1})
        _post(srv.port, "/v1/cancel", {"id": r2})
        box = {}

        def streamer():
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                data=json.dumps({"prompt": p, "max_new_tokens": 15,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                box["lines"] = [json.loads(raw) for raw in resp]

        th = threading.Thread(target=streamer)
        th.start()
        _wait_admitted(eng)
        srv.begin_drain()
        code, body = _http_error(
            lambda: _post(srv.port, "/v1/submit",
                          {"prompt": p, "max_new_tokens": 2}))
        assert code == 503 and body["draining"] is True
        code, body = _http_error(lambda: _get(srv.port, "/ready"))
        assert code == 503 and body["status"] == "draining"
        srv.stop(drain_timeout=60)
        stopped = True
        th.join(timeout=30)
        assert not th.is_alive()
        lines = box["lines"]
        assert lines[-1] == {"status": "done"}, \
            f"drain cut the stream short: {lines[-1]}"
        streamed = [t for ln in lines[:-1] for t in ln.get("tokens", [])]
        assert streamed == _ref(params, config, p, 15)
        assert srv._n_drained == 0      # nothing needed cancelling
    finally:
        if not stopped:
            srv.stop()


@pytest.mark.slow
def test_drain_timeout_cancels_stragglers(model):
    """A drain shorter than the in-flight work: the straggler stream is
    cancelled at the timeout with a clean terminal line (never a severed
    socket), and the cancellation is counted."""
    params, config = model
    p = _prompt(0, 5)
    eng = DecodeEngine(params, config, max_slots=1)
    srv = ServingServer(eng).start()
    stopped = False
    try:
        _post(srv.port, "/v1/generate", {"prompt": p, "max_new_tokens": 2})
        install_plan(FaultPlan([{"site": "serving.step", "action": "delay",
                                 "delay": 0.05, "times": None}]))
        box = {}

        def streamer():
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                data=json.dumps({"prompt": p, "max_new_tokens": 55,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                box["lines"] = [json.loads(raw) for raw in resp]

        th = threading.Thread(target=streamer)
        th.start()
        _wait_admitted(eng)
        srv.stop(drain_timeout=0.4)     # ~8 of 55 tokens will exist
        stopped = True
        th.join(timeout=30)
        assert not th.is_alive()
        assert box["lines"][-1]["status"] == "cancelled"
        assert srv._n_drained >= 1
    finally:
        if not stopped:
            srv.stop()
