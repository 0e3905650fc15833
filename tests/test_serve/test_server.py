"""ServeServer integration over real sockets with a real (tiny) SAC
policy: request/response parity, concurrent load, hot reload with zero
dropped in-flight requests, deadline shedding, typed rejections."""

import threading
import time

import jax
import numpy as np
import pytest

from sheeprl_tpu.serve import (
    MicroBatcher,
    OversizedRequest,
    ParamsStore,
    RequestShed,
    ServeClient,
    ServeServer,
)
from sheeprl_tpu.serve.errors import ServeError
from sheeprl_tpu.serve.policies import SACServePolicy

OBS_DIM, ACT_DIM = 3, 1


def _make_actor(seed):
    from sheeprl_tpu.algos.sac.agent import SACAgent

    return SACAgent.init(
        jax.random.PRNGKey(seed), OBS_DIM, ACT_DIM,
        num_critics=2, actor_hidden_size=16, critic_hidden_size=16,
        action_low=np.array([-2.0]), action_high=np.array([2.0]),
        alpha=1.0, tau=0.005, precision="float32",
    ).actor


@pytest.fixture(scope="module")
def sac_policy():
    policy = SACServePolicy(OBS_DIM, ACT_DIM)
    return policy, _make_actor(0), _make_actor(1)


def _serving(policy, params, loaders=None, rungs=(1, 2, 4), window_ms=1.0,
             deadline_ms=2000.0, bind="unix:auto", telem=None):
    loaders = loaders or {}

    def loader(path):
        return loaders[path]  # KeyError -> failed reload, version kept

    store = ParamsStore(loader, params, source=None)

    def dispatch(stacked, pendings, rung):
        version, live = store.current()
        return policy.run(policy.step, live, version, stacked, pendings, rung), version

    batcher = MicroBatcher(
        dispatch, list(rungs), window_ms=window_ms, default_deadline_ms=deadline_ms
    )
    server = ServeServer(policy, store, batcher, bind=bind, telem=telem)
    server.start()
    return server, store


def _wait_until(settled, timeout_s=5.0):
    """The server counts a request and ends its span AFTER the answer is on
    the wire: a client that holds its answer waits for the bookkeeping."""
    deadline = time.monotonic() + timeout_s
    while not settled() and time.monotonic() < deadline:
        time.sleep(0.005)


def _obs(rows, seed=0):
    return {
        "obs": np.random.default_rng(seed).standard_normal(
            (rows, OBS_DIM)
        ).astype(np.float32)
    }


@pytest.mark.timeout(120)
def test_request_response_parity_bit_exact(sac_policy):
    policy, params, _ = sac_policy
    server, _store = _serving(policy, params)
    try:
        with ServeClient(server.address) as client:
            assert client.info["algo"] == "sac"
            assert client.info["rungs"] == [1, 2, 4]
            # batched-of-1 through rung 1: the same program as a direct call
            one = _obs(1)
            res, meta = client.request(one)
            assert meta["rung"] == 1 and meta["rows"] == 1
            direct = np.asarray(policy.step(params, one["obs"]))
            assert np.array_equal(res["actions"], direct)
            # 3 rows pad to rung 4; the slice matches the padded direct call
            three = _obs(3, seed=3)
            res3, meta3 = client.request(three)
            assert meta3["rung"] == 4 and res3["actions"].shape == (3, ACT_DIM)
            padded = np.concatenate(
                [three["obs"], np.zeros((1, OBS_DIM), np.float32)]
            )
            assert np.array_equal(
                res3["actions"], np.asarray(policy.step(params, padded))[:3]
            )
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_hot_reload_zero_dropped_requests(sac_policy):
    """Drive concurrent clients, flip the params mid-stream, and require
    every single request to come back served (no drops, no errors) with a
    version from {1, 2} and actions bit-exact for that version."""
    policy, params_v1, params_v2 = sac_policy
    server, store = _serving(
        policy, params_v1, loaders={"v2": params_v2}, deadline_ms=0.0
    )
    n_threads, per_thread = 8, 12
    results = []
    errors = []
    lock = threading.Lock()

    def worker(tid):
        try:
            with ServeClient(server.address) as client:
                for i in range(per_thread):
                    obs = _obs(1, seed=tid * 1000 + i)
                    res, meta = client.request(obs)
                    with lock:
                        results.append((obs["obs"], res["actions"], meta["version"]))
        except Exception as err:  # any failure is a dropped request
            with lock:
                errors.append(err)

    try:
        threads = [
            threading.Thread(
                target=worker, args=(t,), name=f"test-client-{t}", daemon=True
            )
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        # hot reload in the middle of the stream
        with ServeClient(server.address) as admin:
            reply = admin.reload("v2")
        assert reply["ok"] and reply["version"] == 2
        for t in threads:
            t.join(timeout=60.0)
        assert not errors, errors
        assert len(results) == n_threads * per_thread  # zero dropped
        versions = {v for _, _, v in results}
        assert 2 in versions  # some requests really ran on the new params
        by_version = {1: params_v1, 2: params_v2}
        for obs, actions, version in results:
            # concurrent submitters co-batch at unpredictable rungs, and
            # different rungs are different XLA programs — so this check
            # is allclose; the bit-exact receipt (same rung) lives in
            # test_request_response_parity_bit_exact
            np.testing.assert_allclose(
                actions, np.asarray(policy.step(by_version[version], obs)),
                rtol=0.0, atol=1e-6,
            )
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_failed_reload_keeps_serving_old_version(sac_policy):
    policy, params, _ = sac_policy
    server, store = _serving(policy, params)
    try:
        with ServeClient(server.address) as client:
            reply = client.reload("no-such-checkpoint")
            assert not reply["ok"] and reply["version"] == 1
            res, meta = client.request(_obs(1))
            assert meta["version"] == 1  # still serving v1
        assert store.reload_failures == 1
        assert server.gauges()["Serve/reload_failures"] == 1.0
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_deadline_shed_returns_retry_after(sac_policy):
    policy, params, _ = sac_policy
    # window far beyond the deadline: the request expires while queued
    server, _store = _serving(
        policy, params, window_ms=500.0, deadline_ms=10.0, rungs=(4,)
    )
    try:
        with ServeClient(server.address) as client:
            with pytest.raises(RequestShed) as exc:
                client.request(_obs(1))
            assert exc.value.retry_after_ms >= 0.0
            assert exc.value.reason == "deadline"
            # shed is not a connection failure: the stream keeps working
            res, meta = client.request(_obs(1), deadline_ms=10_000.0)
            assert res["actions"].shape == (1, ACT_DIM)
        assert server.gauges()["Serve/shed_total"] >= 1.0
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_oversized_request_typed_error(sac_policy):
    policy, params, _ = sac_policy
    server, _store = _serving(policy, params, rungs=(1, 2))
    try:
        with ServeClient(server.address) as client:
            with pytest.raises(OversizedRequest):
                client.request(_obs(3))
            res, _ = client.request(_obs(2))  # connection survives
            assert res["actions"].shape == (2, ACT_DIM)
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_tcp_transport(sac_policy):
    policy, params, _ = sac_policy
    server, _store = _serving(policy, params, bind="tcp:127.0.0.1:0")
    try:
        assert server.address.startswith("tcp:127.0.0.1:")
        with ServeClient(server.address) as client:
            res, meta = client.request(_obs(1))
            assert res["actions"].shape == (1, ACT_DIM)
    finally:
        server.close()


class _SpanRecorder:
    """Telemetry stand-in: thread-safe event capture + a live tracer."""

    enabled = True

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def event(self, name, /, **data):
        with self._lock:
            self.events.append((name, data))

    @property
    def tracer(self):
        from sheeprl_tpu.telemetry.trace import Tracer

        return Tracer(self)

    def of(self, name):
        with self._lock:
            return [d for n, d in self.events if n == name]


@pytest.mark.timeout(120)
def test_request_span_decomposition_and_echo(sac_policy):
    """sheepscope (ISSUE 17): every served request gets a span parented on
    the client's span id from the REQUEST meta, its own id echoed in the
    RESPONSE meta, and the full queue/pad/dispatch/slice/send breakdown."""
    policy, params, _ = sac_policy
    rec = _SpanRecorder()
    server, _store = _serving(policy, params, telem=rec)
    try:
        with ServeClient(server.address) as client:
            res, meta = client.request(_obs(1))
        assert "span" in meta, meta
        _wait_until(lambda: rec.of("span"))
        spans = rec.of("span")
        assert len(spans) == 1
        span = spans[0]
        assert span["name"] == "request" and span["outcome"] == "served"
        assert span["span"] == meta["span"] and span["id"] == meta["id"]
        # parented on the CLIENT's span id (a compact 8-hex id the client
        # stamped into the REQUEST meta)
        assert isinstance(span["parent"], str) and len(span["parent"]) == 8
        for phase in ("queue_ms", "pad_ms", "dispatch_ms", "slice_ms", "send_ms"):
            assert span[phase] >= 0.0, (phase, span)
        assert span["version"] == 1 and span["rows"] == 1
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_trace_off_leaves_wire_meta_clean(sac_policy, monkeypatch):
    """Kill switch: no span keys ride the wire in either direction — the
    exact frames an old peer would see."""
    monkeypatch.setenv("SHEEPRL_TPU_TRACE", "0")
    policy, params, _ = sac_policy
    rec = _SpanRecorder()
    server, _store = _serving(policy, params, telem=rec)
    try:
        with ServeClient(server.address) as client:
            _res, meta = client.request(_obs(1))
        assert "span" not in meta, meta
        assert rec.of("span") == []
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_conn_error_attributed_to_last_request(sac_policy):
    """A connection that dies mid-stream is span-tagged: the conn_error
    event names the request id + span it interrupted, so sheeptrace can
    tie the drop back into the chain."""
    import time as _time

    policy, params, _ = sac_policy
    rec = _SpanRecorder()
    server, _store = _serving(policy, params, telem=rec)
    try:
        client = ServeClient(server.address)
        _res, meta = client.request(_obs(1))
        # corrupt bytes on the live connection: the handler's FrameError
        client._sock.sendall(b"XXXX" + bytes(12))
        client._sock.close()
        deadline = _time.monotonic() + 20.0
        while not rec.of("serve.conn_error") and _time.monotonic() < deadline:
            _time.sleep(0.05)
        errors = rec.of("serve.conn_error")
        assert errors, rec.events
        assert errors[0]["request_id"] == meta["id"]
        assert errors[0]["span"] == meta["span"]
    finally:
        server.close()


@pytest.mark.timeout(120)
def test_gauges_expose_serving_telemetry(sac_policy):
    policy, params, _ = sac_policy
    server, _store = _serving(policy, params)
    try:
        with ServeClient(server.address) as client:
            for i in range(5):
                client.request(_obs(1, seed=i))
        _wait_until(lambda: server.gauges()["Serve/completed_total"] == 5.0)
        g = server.gauges()
        assert g["Serve/served_total"] == 5.0
        assert g["Serve/completed_total"] == 5.0
        assert g["Serve/latency_p50_ms"] > 0.0
        assert g["Serve/latency_p99_ms"] >= g["Serve/latency_p50_ms"]
        assert g["Serve/qps"] > 0.0
        assert g["Serve/params_version"] == 1.0
        assert 0.0 < g["Serve/batch_occupancy"] <= 1.0
    finally:
        server.close()
