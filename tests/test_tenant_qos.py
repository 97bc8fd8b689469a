"""Multi-tenant QoS: token-budget weighted fair queueing across
tenants, per-tenant quota 429s (with the public ``would_shed`` /
``retry_after_ms`` accessors consistent with real submit outcomes),
park-and-resume preemption asserted token-identical to the
never-preempted run with the parked blocks reclaimed as a cache hit,
the ``serving.preempt`` chaos site never losing a request, tenant
plumbing client -> router -> replica and through the disagg wire meta,
and ``Retry-After`` headers on engine and router-edge 429s."""
import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.fleet import FleetRouter, ReplicaPool
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine, QueueFullError
from elephas_tpu.serving_http import ServingServer
from elephas_tpu.serving_qos import (DEFAULT_TENANT, FairQueue,
                                     QueuedRequest, TenantQoS)
from elephas_tpu.utils.faults import FaultPlan, clear_plan, install_plan

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    # f32: the preempt/resume token-identity assertions compare the
    # resume path's extend program against continuous decode steps —
    # the cross-program rounding caveat the prefix-cache tests document
    config = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=64,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


@pytest.fixture(autouse=True)
def _no_fault_plan():
    clear_plan()
    yield
    clear_plan()


def _prompt(seed, n=8):
    return np.asarray(
        np.random.default_rng(seed).integers(0, 300, n), np.int32)


def _post(port, path, payload, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path, parse=True):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as resp:
        raw = resp.read()
        return json.loads(raw) if parse else raw.decode()


def _http_error(fn):
    """(status, body, headers) of the HTTPError ``fn`` must raise."""
    with pytest.raises(urllib.error.HTTPError) as exc:
        fn()
    return (exc.value.code, json.loads(exc.value.read()),
            exc.value.headers)


def _wait_admitted(engine, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if any(r is not None for r in engine._rid):
            return
        time.sleep(0.005)
    raise AssertionError("no request was admitted in time")


# ------------------------------------------------------ fair queue unit
def _item(rid, tokens, tenant, priority=1):
    return QueuedRequest(rid, np.zeros(tokens, np.int32), 4, 0.0, 0,
                         1.0, tenant, priority)


def test_fair_queue_is_token_budget_not_request_count():
    """Deficit round robin charges PROMPT TOKENS: a tenant submitting
    3x-longer prompts gets ~1/3 the admissions at equal weight, so the
    admitted-token shares (not request counts) converge to the
    weights."""
    q = FairQueue(TenantQoS(quantum_tokens=8))
    for i in range(8):
        q.append(_item(i, 24, "long"))
    for i in range(24):
        q.append(_item(100 + i, 8, "short"))
    tokens = {"long": 0, "short": 0}
    order = []
    for _ in range(16):
        item = q.pop()
        tokens[item.tenant] += int(item.prompt.size)
        order.append(item.tenant)
    # equal weights -> near-equal token shares over the window
    assert abs(tokens["long"] - tokens["short"]) <= 24
    # ... which means ~3 short admissions per long one
    assert 2 * order.count("long") <= order.count("short")


def test_fair_queue_weights_and_priority_tiers():
    """Weights skew the token share; a higher priority CLASS preempts
    the rotation outright (strict priority across classes, DRR within
    one)."""
    qos = TenantQoS(tenants={"a": {"weight": 3.0}, "b": {"weight": 1.0}},
                    quantum_tokens=8)
    q = FairQueue(qos)
    for i in range(16):
        q.append(_item(i, 8, "a"))
        q.append(_item(100 + i, 8, "b"))
    grants = {"a": 0, "b": 0}
    for _ in range(16):
        grants[q.pop().tenant] += 1
    assert grants["a"] >= 2.0 * grants["b"]   # 3:1 weights, some slack
    # a high-priority arrival jumps every normal-priority lane
    q.append(_item(999, 8, "vip", priority=2))
    assert q.pop().rid == 999


def test_priority_override_cannot_exceed_tenant_class():
    """Priority is an operator-granted property of the TENANT: a
    per-request override may lower it, never raise it — an uncapped
    override would let any client self-escalate past the isolation
    (outranking, even preempting, higher-priority tenants)."""
    from elephas_tpu.serving_qos import PRIORITY_CLASSES

    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "vip": {"priority": "high"}})
    assert qos.priority("batch", "high") == PRIORITY_CLASSES["low"]
    assert qos.priority("batch", 99) == PRIORITY_CLASSES["low"]
    assert qos.priority("vip") == PRIORITY_CLASSES["high"]
    assert qos.priority("vip", "low") == PRIORITY_CLASSES["low"]
    # unlisted tenants are capped at the default class
    assert qos.priority("anyone", "high") == PRIORITY_CLASSES["normal"]


def test_fair_queue_without_policy_is_fifo():
    q = FairQueue(None)
    for i, tenant in enumerate(["a", "b", "a", "c"]):
        q.append(_item(i, 8, tenant))
    assert [q.pop().rid for _ in range(4)] == [0, 1, 2, 3]


# --------------------------------------------------- WFQ at the engine
def test_wfq_admission_interleaves_tenants(model):
    """8 heavy-tenant submits land BEFORE 8 light-tenant submits; FIFO
    would admit all heavy first, WFQ alternates the two lanes."""
    params, config = model
    qos = TenantQoS(quantum_tokens=8, preempt=False)
    eng = DecodeEngine(params, config, max_slots=1, qos=qos)
    for i in range(8):
        eng.submit(_prompt(i), 2, tenant="heavy", admit=False)
    for i in range(8):
        eng.submit(_prompt(100 + i), 2, tenant="light", admit=False)
    while eng.pending:
        eng.step()
    admits = []
    for t in eng.recorder.recent(limit=16):
        for ev in t["events"]:
            if ev["event"] == "admitted":
                admits.append((ev["at"], ev["tenant"]))
    admits = [t for _, t in sorted(admits)]
    assert len(admits) == 16
    # light admissions are spread through the schedule, not parked
    # behind the whole heavy backlog
    first_half = admits[:8]
    assert first_half.count("light") >= 3, admits


# --------------------------------------------------------------- quotas
def test_tenant_quota_sheds_offender_only_and_accessors_agree(model):
    """A quota-breached tenant sheds with the quota-aware 429 while an
    under-quota tenant admits through the same engine — and the public
    would_shed/retry_after_ms accessors answer consistently with the
    actual submit outcomes, before and after the breach."""
    params, config = model
    qos = TenantQoS(tenants={
        "heavy": {"max_queued_tokens": 20, "max_queue": 8},
        "light": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=1, qos=qos)
    eng.submit(_prompt(0), 30)            # occupies the single slot
    assert not eng.would_shed(8, tenant="heavy")
    r1 = eng.submit(_prompt(1), 2, tenant="heavy", admit=False)
    r2 = eng.submit(_prompt(2), 2, tenant="heavy", admit=False)
    # 16 of 20 quota tokens queued: one more 8-token prompt breaches
    assert eng.would_shed(8, tenant="heavy")
    assert not eng.would_shed(8, tenant="light")
    with pytest.raises(QueueFullError) as exc:
        eng.submit(_prompt(3), 2, tenant="heavy", admit=False)
    assert exc.value.retry_after_ms >= 50
    assert "quota" in str(exc.value)
    assert eng.retry_after_ms(tenant="heavy") >= 50
    # the under-quota tenant queues through the very same path
    r3 = eng.submit(_prompt(4), 2, tenant="light", admit=False)
    # per-tenant accounting: the shed landed on the offender only
    stats = eng.stats
    assert stats["tenants"]["heavy"]["sheds"]["tenant_quota"] == 1
    assert "sheds" not in stats["tenants"].get("light", {})
    assert stats["requests_shed"] == 1
    # a prompt larger than the token quota is PERMANENTLY inadmissible
    # (400 at submit), not a retryable 429
    with pytest.raises(ValueError, match="quota"):
        eng.submit(_prompt(5, n=21), 2, tenant="heavy", admit=False)
    while eng.pending:
        eng.step()
    for rid in (r1, r2, r3):
        assert eng.result(rid) is not None


# ------------------------------------------------- preempt-and-resume
def test_preempt_parks_blocks_and_resume_is_token_identical(model):
    """The acceptance pin: a low-priority decode preempted by a
    high-priority admission re-queues, its KV blocks park in the block
    cache, resume admission reclaims them as a kv-cache hit (hit
    accounting asserted), and the final greedy output is
    token-identical to the same request never preempted."""
    params, config = model
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=1, paged=(24, 8),
                       qos=qos)
    pa, pb = _prompt(0, n=10), _prompt(1, n=4)
    ra = eng.submit(pa, 20, tenant="batch")
    for _ in range(6):                    # decode a while: KV > 1 block
        eng.step()
    hits_before = eng.stats["kv_cache"]["hits"]
    rb = eng.submit(pb, 4, tenant="live")   # no free slot -> preempt
    while eng.pending:
        eng.step()
    assert eng.result(ra) == _ref(params, config, pa, 20)
    assert eng.result(rb) == _ref(params, config, pb, 4)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["tenants"]["batch"]["preempted"] == 1
    trace = eng.request_trace(ra)
    events = [ev["event"] for ev in trace["events"]]
    assert "preempted" in events and "resumed" in events
    pre = next(ev for ev in trace["events"]
               if ev["event"] == "preempted")
    assert pre["parked_blocks"] >= 1
    # resume admission reclaimed the parked chain: a kv_cache_hit on
    # the timeline covering at least the parked blocks, and the
    # engine-level hit counter moved
    hit = next(ev for ev in trace["events"]
               if ev["event"] == "kv_cache_hit")
    assert hit["blocks"] >= pre["parked_blocks"]
    assert eng.stats["kv_cache"]["hits"] == hits_before + 1


def test_preemption_frees_pool_blocks_for_the_high_priority(model):
    """Block-pressure preemption: with every slot AND every pool block
    held by low-priority decodes, a high-priority submit still admits
    (victims are preempted lowest-class-first until capacity frees)."""
    params, config = model
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    # 2 slots; pool sized so two 28-token-footprint requests leave no
    # headroom for a third without preemption
    eng = DecodeEngine(params, config, max_slots=2, paged=(9, 8),
                       qos=qos)
    ra = eng.submit(_prompt(0, n=12), 12, tenant="batch")
    rb = eng.submit(_prompt(1, n=12), 12, tenant="batch")
    for _ in range(3):
        eng.step()
    rc = eng.submit(_prompt(2, n=6), 2, tenant="live")
    while eng.pending:
        eng.step()
    for rid, (seed, n, new) in {ra: (0, 12, 12), rb: (1, 12, 12),
                                rc: (2, 6, 2)}.items():
        assert eng.result(rid) == _ref(params, config,
                                       _prompt(seed, n=n), new)
    assert eng.stats["preemptions"] >= 1


def test_double_preemption_stays_token_identical(model):
    """A request preempted TWICE must not duplicate its pre-resume
    output into the rebuilt sequence (the resume prompt already folds
    it in) — pinned by a reviewer-reproduced bench crash: two
    high-priority bursts against the same low-priority decode, final
    output still token-identical to the never-preempted oracle."""
    params, config = model
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=1, paged=(24, 8),
                       qos=qos)
    pa = _prompt(0, n=10)
    ra = eng.submit(pa, 24, tenant="batch")
    for _ in range(5):
        eng.step()
    r1 = eng.submit(_prompt(1, n=4), 2, tenant="live")  # preempt #1
    while eng.result(r1) is None:
        eng.step()
    for _ in range(4):                                  # A resumed
        eng.step()
    r2 = eng.submit(_prompt(2, n=4), 2, tenant="live")  # preempt #2
    while eng.pending:
        eng.step()
    assert eng.stats["preemptions"] == 2
    assert eng.result(ra) == _ref(params, config, pa, 24)
    assert eng.result(r2) == _ref(params, config, _prompt(2, n=4), 2)


@pytest.mark.chaos
def test_preempt_fault_never_loses_the_request(model):
    """serving.preempt chaos: with the parking path failing (error)
    and then slow (delay), the preempted request still re-queues,
    resumes (by recompute when nothing parked), and finishes with the
    exact never-preempted output — a preemption fault may cost
    compute, never a client request."""
    params, config = model
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    install_plan(FaultPlan([
        {"site": "serving.preempt", "action": "error", "times": 1},
        {"site": "serving.preempt", "action": "delay", "after": 1,
         "delay": 0.01, "times": 1}]))
    pa = _prompt(0, n=10)
    for round_ in range(2):               # error round, then delay round
        eng = DecodeEngine(params, config, max_slots=1, paged=(24, 8),
                           qos=qos)
        ra = eng.submit(pa, 16, tenant="batch")
        for _ in range(5):
            eng.step()
        rb = eng.submit(_prompt(1, n=4), 2, tenant="live")
        while eng.pending:
            eng.step()
        assert eng.stats["preemptions"] == 1, round_
        assert eng.result(ra) == _ref(params, config, pa, 16), round_
        assert eng.result(rb) is not None, round_
    from elephas_tpu.utils.faults import active_plan

    plan = active_plan()
    assert [a for _, _, a in plan.fired("serving.preempt")] == [
        "error", "delay"]


# ----------------------------------------------------- HTTP Retry-After
def test_http_429_carries_retry_after_header(model):
    """Engine-level and tenant-quota 429s both carry the standard
    Retry-After header derived from the JSON retry_after_ms field."""
    params, config = model
    qos = TenantQoS(tenants={"heavy": {"max_queued_tokens": 12}})
    eng = DecodeEngine(params, config, max_slots=1, max_queue=4,
                       qos=qos)
    with ServingServer(eng) as srv:
        install_plan(FaultPlan([{"site": "serving.step",
                                 "action": "delay", "delay": 0.05,
                                 "times": None}]))
        _post(srv.port, "/v1/submit",
              {"prompt": [1, 2, 3, 4, 5], "max_new_tokens": 55})
        _wait_admitted(eng)
        _post(srv.port, "/v1/submit",
              {"prompt": [1, 2, 3, 4, 5, 6, 7, 8],
               "max_new_tokens": 4, "tenant": "heavy"})
        code, body, headers = _http_error(
            lambda: _post(srv.port, "/v1/submit",
                          {"prompt": [1, 2, 3, 4, 5, 6, 7, 8],
                           "max_new_tokens": 4, "tenant": "heavy"}))
        assert code == 429
        assert "quota" in body["error"]
        assert body["retry_after_ms"] >= 50
        assert headers["Retry-After"] is not None
        assert int(headers["Retry-After"]) == max(
            1, -(-body["retry_after_ms"] // 1000))


def test_router_edge_429_carries_retry_after_header(model):
    """The fleet edge 429 (every replica saturated) forwards the max
    retry_after_ms AND the Retry-After header derived from it."""
    params, config = model
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=1, max_queue=1),
        n=1).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.5) as router:
            install_plan(FaultPlan([{"site": "serving.step",
                                     "action": "delay", "delay": 0.05,
                                     "times": None}]))
            shed = None
            for i in range(8):
                try:
                    _post(router.port, "/v1/submit",
                          {"prompt": _prompt(i).tolist(),
                           "max_new_tokens": 40})
                except urllib.error.HTTPError as err:
                    shed = (err.code, json.loads(err.read()),
                            err.headers)
                    break
            assert shed is not None, "pool never saturated"
            code, body, headers = shed
            assert code == 429
            assert body["retry_after_ms"] >= 50
            assert int(headers["Retry-After"]) == max(
                1, -(-body["retry_after_ms"] // 1000))
    finally:
        clear_plan()
        pool.stop()


# -------------------------------------------------------- plumbing e2e
def test_tenant_flows_client_router_replica(model):
    """The tenant named at the edge (X-Tenant header) reaches the
    replica engine's QoS: per-tenant admitted counters and the
    tenant-labeled http series move on the replica, and the request's
    flight-recorder timeline is stamped with the tenant."""
    params, config = model
    qos = TenantQoS(tenants={"acme": {"weight": 2.0}})
    engines = []

    def factory():
        eng = DecodeEngine(params, config, max_slots=2, qos=qos)
        engines.append(eng)
        return eng

    pool = ReplicaPool(factory, n=1).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.5) as router:
            out = _post(router.port, "/v1/generate",
                        {"prompt": _prompt(0).tolist(),
                         "max_new_tokens": 3},
                        headers={"X-Tenant": "acme"})
            assert out["status"] == "done"
            # body field wins over the header when both are present
            out2 = _post(router.port, "/v1/generate",
                         {"prompt": _prompt(1).tolist(),
                          "max_new_tokens": 3, "tenant": "acme"},
                         headers={"X-Tenant": "ignored"})
            assert out2["status"] == "done"
            metrics = _get(pool.urls[0].split(":")[-1], "/metrics",
                           parse=False)
            assert ('serving_tenant_admitted_total{tenant="acme"} 2'
                    in metrics)
            assert ('http_requests_total{route="/v1/generate",'
                    'status="200",tenant="acme"} 2' in metrics)
            eng = engines[0]
            tenants = {t["events"][0].get("tenant")
                       for t in eng.recorder.recent(limit=4)}
            assert "acme" in tenants
    finally:
        pool.stop()


def test_tenant_rides_the_disagg_wire_meta(model):
    """tenant/priority survive the prefill tier's wire meta: the
    decode engine's admission sees them (per-tenant admitted counter
    + the admitted event's tenant stamp)."""
    from elephas_tpu.disagg import DisaggEngine, PrefillWorker

    params, config = model
    qos = TenantQoS(tenants={"acme": {"priority": "high"}})
    worker = PrefillWorker(DecodeEngine(params, config, max_slots=1),
                           quant=False, block_size=8,
                           name="prefill-0").start()
    decode = DecodeEngine(params, config, max_slots=2, tier="decode",
                          qos=qos)
    deng = DisaggEngine(decode, [worker])
    try:
        rid = deng.submit(_prompt(0).tolist(), 4, tenant="acme")
        deadline = time.monotonic() + 60
        out = None
        while out is None and time.monotonic() < deadline:
            if deng.pending:
                deng.step()
            out = deng.result(rid)
            time.sleep(0.002)
        assert out == _ref(params, config, _prompt(0), 4)
        assert decode.stats["tenants"]["acme"]["admitted"] == 1
        admitted = [ev for t in decode.recorder.recent(limit=4)
                    for ev in t["events"] if ev["event"] == "admitted"]
        assert admitted and admitted[-1]["tenant"] == "acme"
        # the disagg front end enforces the tenant quota at ITS submit
        deng2_qos = decode.qos.tenants["acme"]
        assert deng2_qos["priority"] == 2
    finally:
        deng.stop()
        worker.stop()


def test_disagg_quota_counts_prefill_staged_tokens(model):
    """The disagg front end's tenant quota must count tokens STAGED in
    the prefill tier, not just the decode queue (which a request only
    enters at KV-install time) — else a tenant piles unbounded work
    into the prefill stage and the quota never bites. The worker is
    deliberately never start()ed, so submitted jobs sit staged."""
    from elephas_tpu.disagg import DisaggEngine, PrefillWorker

    params, config = model
    qos = TenantQoS(tenants={"heavy": {"max_queued_tokens": 20}})
    worker = PrefillWorker(DecodeEngine(params, config, max_slots=1),
                           quant=False, block_size=8, name="prefill-0")
    decode = DecodeEngine(params, config, max_slots=2, tier="decode",
                          qos=qos)
    deng = DisaggEngine(decode, [worker])
    try:
        r1 = deng.submit(_prompt(0, n=8).tolist(), 4, tenant="heavy")
        r2 = deng.submit(_prompt(1, n=8).tolist(), 4, tenant="heavy")
        # 16 staged tokens: one more 8-token prompt breaches the quota
        with pytest.raises(QueueFullError, match="quota"):
            deng.submit(_prompt(2, n=8).tolist(), 4, tenant="heavy")
        assert decode.registry.render().count(
            'serving_tenant_sheds_total{tenant="heavy",'
            'reason="tenant_quota"} 1') == 1
        # another tenant still admits through the same front end
        r3 = deng.submit(_prompt(3, n=8).tolist(), 4, tenant="other-t")
        # cancelling releases the staged budget
        assert deng.cancel(r1) and deng.cancel(r2) and deng.cancel(r3)
        deng.submit(_prompt(4, n=8).tolist(), 4, tenant="heavy")
    finally:
        deng.stop()
        worker.stop()


# ------------------------------------------------------ metrics surface
def test_tenant_metrics_agree_with_stats_and_fold_unknown(model):
    """serving_tenant_* series agree with the /stats tenants dict, the
    queued-tokens gauge reads the live queue, and unconfigured tenant
    names fold into the bounded "other" label."""
    params, config = model
    qos = TenantQoS(tenants={"a": {}}, preempt=False)
    eng = DecodeEngine(params, config, max_slots=1, qos=qos)
    eng.submit(_prompt(0), 8)                       # occupies the slot
    eng.submit(_prompt(1), 2, tenant="a", admit=False)
    eng.submit(_prompt(2, n=6), 2, tenant="random-client-string",
               admit=False)
    text = eng.registry.render()
    assert 'serving_tenant_queued_tokens{tenant="a"} 8' in text
    assert 'serving_tenant_queued_tokens{tenant="other"} 6' in text
    stats = eng.stats
    assert stats["tenants"]["a"]["queued_tokens"] == 8
    assert stats["tenants"]["other"]["queued_tokens"] == 6
    while eng.pending:
        eng.step()
    text = eng.registry.render()
    assert 'serving_tenant_admitted_total{tenant="a"} 1' in text
    assert 'serving_tenant_admitted_total{tenant="other"} 1' in text
    # the default tenant label covers requests that named none
    assert ('serving_tenant_admitted_total{tenant="%s"} 1'
            % DEFAULT_TENANT) in text
