"""HTTP serving server: concurrent requests through the engine-backed
server must return exactly each prompt's solo greedy decode; submit/
poll, cancellation, text mode, stats, and engine-validation errors all
ride the JSON wire."""
import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine
from elephas_tpu.serving_http import ServingServer
from elephas_tpu.utils.text import ByteTokenizer

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=48,
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


def test_concurrent_generate_matches_solo_decode(model):
    params, config = model
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, 300, int(n))]
               for n in (4, 7, 5, 9)]
    with ServingServer(DecodeEngine(params, config, max_slots=2)) as srv:
        assert _get(srv.port, "/health")["status"] == "ok"
        results = {}

        def call(i):
            results[i] = _post(srv.port, "/v1/generate",
                               {"prompt": prompts[i],
                                "max_new_tokens": 8})

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in enumerate(prompts):
            assert results[i]["tokens"] == _ref(params, config, p, 8)
        stats = _get(srv.port, "/stats")
        assert stats["requests_finished"] == len(prompts)


def test_submit_poll_and_cancel(model):
    params, config = model
    rng = np.random.default_rng(1)
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        p1 = [int(t) for t in rng.integers(0, 300, 5)]
        p2 = [int(t) for t in rng.integers(0, 300, 6)]
        # r2 gets a wide budget: even if r1 finishes and r2 is admitted
        # before the cancel below lands (a stall of THIS thread), r2
        # cannot have completed — the cancel still finds it live
        r1 = _post(srv.port, "/v1/submit",
                   {"prompt": p1, "max_new_tokens": 6})["id"]
        r2 = _post(srv.port, "/v1/submit",
                   {"prompt": p2, "max_new_tokens": 40})["id"]
        # r2 queues behind the single slot; cancel it before admission
        assert _post(srv.port, "/v1/cancel", {"id": r2})["cancelled"]
        while True:
            out = _get(srv.port, f"/v1/result?id={r1}")
            if out["status"] == "done":
                break
        assert out["tokens"] == _ref(params, config, p1, 6)
        # one-shot semantics after fetch; cancelled rid is unknown — and
        # an unknown id is a real 404, not a 200 payload
        for rid in (r1, r2):
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(srv.port, f"/v1/result?id={rid}")
            assert exc.value.code == 404
            assert json.loads(exc.value.read())["status"] == "unknown"


def test_text_mode_round_trip(model):
    params, config = model      # vocab 300 covers the byte alphabet
    tok = ByteTokenizer()
    with ServingServer(DecodeEngine(params, config, max_slots=2),
                       tokenizer=tok) as srv:
        out = _post(srv.port, "/v1/generate",
                    {"text": "hi", "max_new_tokens": 5})
        assert out["tokens"] == _ref(params, config, tok.encode("hi"), 5)
        assert out["text"] == tok.decode(out["tokens"])


def test_validation_errors_as_400(model):
    params, config = model
    with ServingServer(DecodeEngine(params, config, max_slots=2)) as srv:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(srv.port, "/v1/generate", {"max_new_tokens": 4})
        assert exc.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(srv.port, "/v1/generate",
                  {"text": "no tokenizer attached"})
        assert exc.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(srv.port, "/v1/generate", {"prompt": [1, 2],
                                             "max_new_tokens": 4,
                                             "top_p": 7.0})
        assert exc.value.code == 400


def test_cancel_unblocks_waiting_generate(model):
    """POST /v1/cancel against a request another client is blocking on
    in /v1/generate must release that handler with a 'cancelled' payload
    — never hang it until shutdown."""
    import time

    params, config = model
    rng = np.random.default_rng(2)
    # slots=1 and a long budget: the second generate queues behind the
    # first, giving the canceller a stable window
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        p1 = [int(t) for t in rng.integers(0, 300, 4)]
        p2 = [int(t) for t in rng.integers(0, 300, 5)]
        _post(srv.port, "/v1/submit", {"prompt": p1, "max_new_tokens": 40})
        box = {}

        def blocked():
            box["out"] = _post(srv.port, "/v1/generate",
                               {"prompt": p2, "max_new_tokens": 30})

        t = threading.Thread(target=blocked)
        t.start()
        # wait for the SECOND submission to exist (its prefill compiles
        # inside submit, so a fixed sleep could cancel p1 instead)
        deadline = time.time() + 60
        while srv.engine._next_rid < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert srv.engine._next_rid == 2
        assert _post(srv.port, "/v1/cancel", {"id": 1})["cancelled"]
        t.join(timeout=30)
        assert not t.is_alive(), "generate handler hung after cancel"
        assert box["out"]["status"] == "cancelled"


def test_result_invalid_id_is_400(model):
    params, config = model
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/v1/result?id=abc")
        assert exc.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(srv.port, "/v1/generate", {"prompt": 5})
        assert exc.value.code == 400           # wrong type -> clean 400


def test_streaming_generate(model):
    """stream:true delivers newline-delimited token chunks incrementally;
    their concatenation is exactly the solo greedy decode, terminated by
    a done line."""
    params, config = model
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, 300, 5)]
    with ServingServer(DecodeEngine(params, config, max_slots=2)) as srv:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            data=json.dumps({"prompt": prompt, "max_new_tokens": 10,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        lines = []
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.headers["Content-Type"] == "application/x-ndjson"
            for raw in resp:
                lines.append(json.loads(raw))
        assert lines[-1] == {"status": "done"}
        token_lines = [ln["tokens"] for ln in lines[:-1]]
        assert len(token_lines) >= 2          # incremental, not one blob
        streamed = [t for chunk in token_lines for t in chunk]
        assert streamed == _ref(params, config, prompt, 10)
        # streamed requests never linger in the poll store (404: the
        # result was consumed through the stream)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/v1/result?id=0")
        assert exc.value.code == 404


@pytest.mark.parametrize("paged", [None, (40, 4)],
                         ids=["contiguous", "paged"])
def test_concurrent_streams_carry_the_solo_decodes(model, paged):
    """Five streams of different budgets through two slots, with the
    engine one step ahead of what the handlers have been given: every
    stream's lines concatenate to its solo greedy decode, no token
    twice, none after the budget, each ending in a done line."""
    params, config = model
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(0, 300, 5)]
               for _ in range(5)]
    budgets = [10, 3, 7, 12, 5]
    lines = {}

    def stream(i, port):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"prompt": prompts[i], "stream": True,
                             "max_new_tokens": budgets[i]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            lines[i] = [json.loads(raw) for raw in resp]

    engine = DecodeEngine(params, config, max_slots=2, paged=paged)
    with ServingServer(engine) as srv:
        threads = [threading.Thread(target=stream, args=(i, srv.port))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = _get(srv.port, "/stats")
    for i, p in enumerate(prompts):
        assert lines[i][-1] == {"status": "done"}
        streamed = [t for ln in lines[i][:-1] for t in ln["tokens"]]
        assert streamed == _ref(params, config, p, 12)[:budgets[i]]
    assert stats["surplus_rows"] == 0
    assert stats["steps_ahead"] >= stats["steps"] - len(prompts)


def test_streaming_cancel_terminates(model):
    import time

    params, config = model
    rng = np.random.default_rng(4)
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        # slot occupied -> the streamed request queues; cancel it
        _post(srv.port, "/v1/submit",
              {"prompt": [int(t) for t in rng.integers(0, 300, 4)],
               "max_new_tokens": 40})
        box = {}

        def streamer():
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                data=json.dumps(
                    {"prompt": [int(t) for t in rng.integers(0, 300, 6)],
                     "max_new_tokens": 30, "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                box["lines"] = [json.loads(raw) for raw in resp]

        t = threading.Thread(target=streamer)
        t.start()
        deadline = time.time() + 60
        while srv.engine._next_rid < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert _post(srv.port, "/v1/cancel", {"id": 1})["cancelled"]
        t.join(timeout=30)
        assert not t.is_alive()
        assert box["lines"][-1]["status"] in ("cancelled", "done")


def test_stream_client_disconnect_cancels_request(model):
    """A client that drops mid-stream must not keep its slot decoding
    for nobody: the handler aborts the request server-side and every
    trace (slot, stream feed, stored result) is released."""
    import socket
    import time

    params, config = model
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(0, 300, 4)]
    with ServingServer(DecodeEngine(params, config, max_slots=1)) as srv:
        body = json.dumps({"prompt": prompt, "max_new_tokens": 40,
                           "stream": True}).encode()
        raw = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
        raw.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                    b"Host: x\r\nContent-Type: application/json\r\n"
                    + f"Content-Length: {len(body)}\r\n\r\n".encode()
                    + body)
        raw.recv(1)               # first byte of the response arrived
        raw.close()               # client vanishes mid-stream
        deadline = time.time() + 60
        while time.time() < deadline:
            with srv._cond:
                if (all(r is None for r in srv.engine._rid)
                        and not srv.engine._queue and not srv._streams):
                    break
            time.sleep(0.05)
        with srv._cond:
            assert all(r is None for r in srv.engine._rid), \
                "slot still decoding for a dead client"
            assert not srv._streams
        # the server still serves live clients afterwards
        out = _post(srv.port, "/v1/generate",
                    {"prompt": prompt, "max_new_tokens": 5})
        assert out["tokens"] == _ref(params, config, prompt, 5)


def test_transformer_model_serve_one_call():
    """TransformerModel.serve(): trained model -> running HTTP server in
    one call, warmed, output ≡ the model's own generate."""
    from elephas_tpu.models.transformer_model import TransformerModel

    tm = TransformerModel(TransformerConfig(
        vocab_size=300, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=48, dtype=jnp.float32))
    tm.build(seed=0)
    srv = tm.serve(warmup_lengths=(4,), max_slots=2)
    try:
        prompt = [int(t) for t in np.random.default_rng(7).integers(
            0, 300, 4)]
        out = _post(srv.port, "/v1/generate",
                    {"prompt": prompt, "max_new_tokens": 6})
        ref = [int(t) for t in tm.generate(np.asarray(prompt)[None], 6)[0]]
        assert out["tokens"] == ref
    finally:
        srv.stop()


def test_engine_failure_fails_fast_not_hangs(model):
    """ADVICE r3: a raising engine.step() must not silently kill the
    driver loop — /health turns 500, a blocked /v1/generate returns an
    error payload instead of waiting forever, polls surface the
    failure, and new submits are rejected."""
    params, config = model
    srv = ServingServer(DecodeEngine(params, config, max_slots=1))
    srv.start()
    try:
        boom = RuntimeError("injected device loss")

        def exploding_step():
            raise boom

        srv.engine.step = exploding_step
        prompt = [1, 2, 3]
        out = _post(srv.port, "/v1/generate",
                    {"prompt": prompt, "max_new_tokens": 4})
        assert out["status"] == "error"
        assert "injected device loss" in out["error"]
        # liveness now reports the failure (500 + error body)
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(srv.port, "/health")
        assert err.value.code == 500
        assert json.loads(err.value.read())["status"] == "error"
        # a poll for the dead rid explains itself
        assert _get(srv.port, "/v1/result?id=0")["status"] == "error"
        # new submissions are refused with the failure, not queued
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(srv.port, "/v1/submit", {"prompt": [1],
                                           "max_new_tokens": 1})
        assert err.value.code == 400
    finally:
        srv.stop()


def test_eviction_never_takes_a_waiters_result(model):
    """ADVICE r3: the finished-result cap must not evict a result whose
    blocking /v1/generate handler hasn't woken yet — with a cap of 1 and
    concurrent blocking clients, every client still gets its tokens."""
    params, config = model
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, 300, 4 + i)]
               for i in range(3)]
    engine = DecodeEngine(params, config, max_slots=2)
    with ServingServer(engine, max_stored_results=1) as srv:
        results = {}

        def call(i):
            results[i] = _post(srv.port, "/v1/generate",
                               {"prompt": prompts[i],
                                "max_new_tokens": 6})

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in enumerate(prompts):
            assert results[i].get("tokens") == _ref(params, config, p, 6), \
                f"client {i} lost its result to eviction: {results[i]}"


# ------------------------------------------------- per-request mailboxes
class _FakeEngine:
    """The engine surface ServingServer drives, with no device: a
    submitted request stays live until the test finishes it (posts its
    outcome to ``finished``) or cancels it; ``fail`` makes the next
    step raise."""

    def __init__(self):
        self._next = 0
        self.live = set()
        self.finished = {}
        self.fail = None

    def submit(self, prompt, max_new_tokens, admit=True, **kwargs):
        rid, self._next = self._next, self._next + 1
        self.live.add(rid)
        return rid

    @property
    def pending(self):
        return bool(self.live)

    def step(self):
        if self.fail is not None:
            raise self.fail
        import time

        time.sleep(0.001)
        return {}

    def finish(self, rid, tokens):
        self.live.discard(rid)
        self.finished[rid] = {"tokens": tokens, "timeout": False,
                              "expired": False}

    def result_info(self, rid):
        return self.finished.pop(rid, None)

    def cancel(self, rid):
        if rid in self.live:
            self.live.discard(rid)
            return True
        return False


def _wait_blocked(srv, n):
    """Until ``n`` handlers are registered and each is inside its
    mailbox wait (a Condition's ``_waiters`` holds one lock a waiter)."""
    import time

    deadline = time.time() + 30
    while time.time() < deadline:
        with srv._cond:
            boxes = [*srv._streams.values(), *srv._waiters.values()]
        if len(boxes) == n and all(b._cond._waiters for b in boxes):
            return
        time.sleep(0.002)
    raise AssertionError(f"{len(boxes)} of {n} handlers blocked")


class _Handlers:
    """Blocked handlers in threads: ``streams`` run ``_run_stream`` over
    a submitted rid, ``waiters`` run ``_generate`` (a blocking
    /v1/generate); each one's lines, payload or error is kept."""

    def __init__(self, srv, streams, waiters):
        self.lines, self.out = {}, {}
        self.stream_rids = [srv._submit({"prompt": [1, 2]}, stream=True)
                            for _ in range(streams)]
        self.threads = []
        for rid in self.stream_rids:
            self.lines[rid] = []
            self.threads.append(threading.Thread(
                target=srv._run_stream, args=(rid, self.lines[rid].append)))
        for i in range(waiters):
            self.threads.append(threading.Thread(target=self._generate,
                                                 args=(srv, i)))
        for t in self.threads:
            t.start()
        _wait_blocked(srv, streams + waiters)
        with srv._cond:
            self.waiter_rids = sorted(srv._waiters)

    def _generate(self, srv, i):
        try:
            self.out[i] = srv._generate({"prompt": [3]})
        except ValueError as exc:
            self.out[i] = exc

    def join(self):
        for t in self.threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in self.threads), \
            "a blocked handler outlived its request"


def _wakeups(srv):
    return srv._m_wakeups.value, srv._m_wakeups_empty.value


def _deliver(srv, emitted):
    """One engine-loop delivery: the harvest under the serving lock,
    then the live streams' tokens posted outside it, as the loop does."""
    with srv._cond:
        _, feeds = srv._deliver_locked(emitted)
    for box, toks in feeds:
        box.post(tokens=toks)


def test_a_step_wakes_only_the_handlers_it_has_news_for(monkeypatch):
    """K live streams, N streams still queued and W blocking waiters,
    all blocked: one delivery of K+W rows' tokens wakes the K streams
    alone (a waiter takes no token stream), the waiters' results wake
    the W waiters alone, and no wake is empty."""
    from elephas_tpu import serving_http

    monkeypatch.setattr(serving_http, "_WAIT_BACKSTOP_S", 60.0)
    K, N, W = 4, 12, 3
    srv = ServingServer(_FakeEngine(), watchdog=False)
    h = _Handlers(srv, K + N, W)
    live = h.stream_rids[:K]
    _deliver(srv, {rid: [rid, 7] for rid in live + h.waiter_rids})
    _wait_blocked(srv, K + N + W)
    assert _wakeups(srv) == (K, 0)
    for rid in h.stream_rids:
        assert h.lines[rid] == ([{"tokens": [rid, 7]}] if rid in live
                                else [])
    for rid in h.waiter_rids:
        srv.engine.finish(rid, [rid, 7, 8])
    _deliver(srv, {})
    for t in h.threads[K + N:]:
        t.join(timeout=30)
    assert _wakeups(srv) == (K + W, 0)
    assert sorted(out["tokens"] for out in h.out.values()) == \
        [[rid, 7, 8] for rid in h.waiter_rids]
    assert all(out["status"] == "done" for out in h.out.values())
    srv.stop()
    h.join()
    for rid in h.stream_rids:
        assert h.lines[rid][-1] == {"status": "cancelled"}


@pytest.mark.parametrize("ending", ["done", "stop", "drain_deadline",
                                    "cancel", "abort_stream",
                                    "engine_error"])
def test_every_ending_reaches_every_blocked_handler(ending, monkeypatch):
    """Each way a request can end wakes its blocked handler with its
    terminal line, whichever handler it is, through a running engine
    loop: finished (``done``), ``stop()``, a drain's deadline
    (``cancelled``, counted as drained), ``/v1/cancel`` and a vanished
    stream client (``cancelled``), the engine raising (``error``)."""
    from elephas_tpu import serving_http

    monkeypatch.setattr(serving_http, "_WAIT_BACKSTOP_S", 60.0)
    srv = ServingServer(_FakeEngine(), watchdog=False).start()
    stopped = False
    try:
        h = _Handlers(srv, 3, 2)
        rids = h.stream_rids + h.waiter_rids
        if ending == "done":
            with srv._cond:
                for rid in rids:
                    srv.engine.finish(rid, [rid])
        elif ending == "stop":
            srv.stop()
            stopped = True
        elif ending == "drain_deadline":
            srv.stop(drain_timeout=0.2)
            stopped = True
            assert srv._n_drained == len(rids)
        elif ending == "cancel":
            for rid in rids:
                assert srv._cancel({"id": rid}) == {"cancelled": True}
        elif ending == "abort_stream":
            for rid in rids:
                srv._abort_stream(rid)
        else:
            with srv._cond:
                srv.engine.fail = RuntimeError("injected device loss")
        h.join()
    finally:
        if not stopped:
            srv.stop()
    assert not (srv._streams or srv._waiters)
    # an abrupt stop leaves the work it abandoned in the engine
    assert bool(srv._tracked) == (ending == "stop")
    for rid in h.stream_rids:
        last = h.lines[rid][-1]
        if ending == "done":
            assert h.lines[rid] == [{"status": "done"}]
        elif ending == "engine_error":
            assert last["status"] == "error"
            assert "injected device loss" in last["error"]
        else:
            assert last == {"status": "cancelled"}
    for out in h.out.values():
        if ending == "done":
            assert out["status"] == "done" and len(out["tokens"]) == 1
        elif ending == "stop":
            assert isinstance(out, ValueError)
        elif ending == "engine_error":
            assert out["status"] == "error"
        else:
            assert out["status"] == "cancelled"
    assert _wakeups(srv)[1] == 0


def test_the_backstop_ends_a_wait_whose_signal_was_lost(monkeypatch):
    """A request that leaves the server with no post to its mailbox (a
    lost signal) still ends its handler, at the backstop's timeout,
    and that wake is counted as empty."""
    from elephas_tpu import serving_http

    monkeypatch.setattr(serving_http, "_WAIT_BACKSTOP_S", 0.05)
    srv = ServingServer(_FakeEngine(), watchdog=False)
    h = _Handlers(srv, 1, 1)
    with srv._cond:
        srv._tracked.clear()          # untracked, and nobody posted
    h.join()
    assert h.lines[h.stream_rids[0]] == [{"status": "cancelled"}]
    assert h.out[0]["status"] == "cancelled"
    total, empty = _wakeups(srv)
    assert empty >= 2 and total >= empty


def test_metrics_export_the_handler_wakeups(model):
    """``/metrics`` carries both wake-up counters beside the engine's
    series, at the values the server counted; a streamed request wakes
    its handler at least once (a cold first step's compile may outlast
    the backstop, so an empty wake or two is allowed here)."""
    params, config = model
    prompt = [int(t) for t in np.random.default_rng(8).integers(0, 300, 5)]
    with ServingServer(DecodeEngine(params, config, max_slots=2)) as srv:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            data=json.dumps({"prompt": prompt, "max_new_tokens": 6,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            lines = [json.loads(raw) for raw in resp]
        assert lines[-1] == {"status": "done"}
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/metrics",
                                    timeout=120) as resp:
            text = resp.read().decode()
    series = dict(line.rsplit(" ", 1) for line in text.splitlines()
                  if line.startswith("serving_http_handler_wakeups"))
    total = float(series["serving_http_handler_wakeups_total"])
    empty = float(series["serving_http_handler_wakeups_empty_total"])
    assert (total, empty) == _wakeups(srv)
    assert total >= 1 and 0 <= empty <= total
    assert "serving_steps_total" in text


def test_a_cancel_after_the_result_is_posted_still_delivers_it(monkeypatch):
    """A ``/v1/cancel`` that comes once the request has finished cancels
    nothing (``cancelled: false``), and its blocked handlers answer the
    result, not ``cancelled``."""
    from elephas_tpu import serving_http

    monkeypatch.setattr(serving_http, "_WAIT_BACKSTOP_S", 60.0)
    srv = ServingServer(_FakeEngine(), watchdog=False)
    h = _Handlers(srv, 1, 1)
    rids = h.stream_rids + h.waiter_rids
    for rid in rids:
        srv.engine.finish(rid, [rid, 5])
    _deliver(srv, {h.stream_rids[0]: [h.stream_rids[0], 5]})
    for rid in rids:
        assert srv._cancel({"id": rid}) == {"cancelled": False}
    h.join()
    assert h.lines[h.stream_rids[0]] == [
        {"tokens": [h.stream_rids[0], 5]}, {"status": "done"}]
    assert h.out[0] == {"status": "done", "tokens": [h.waiter_rids[0], 5]}
