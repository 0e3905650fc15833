"""sheepscope receipts (ISSUE 17): span emission + kill switch, trace
context riding PUSH/WEIGHTS frame meta, NTP-style clock sync, the
sender-monotonic heartbeat age, role telemetry shards, and the PROFILE
frame answered by a live ReplayService."""

import json
import os
import struct
import time

import numpy as np
import pytest

from sheeprl_tpu.flock import wire
from sheeprl_tpu.flock.service import (
    PROTO_VERSION,
    ReplayService,
    _ActorState,
    pack_push,
    unpack_push,
)
from sheeprl_tpu.telemetry import Telemetry
from sheeprl_tpu.telemetry.trace import ClockSync, Tracer


class _Recorder:
    """Telemetry stand-in that records events and exposes a live tracer."""

    enabled = True

    def __init__(self):
        self.events = []

    def event(self, name, /, **data):
        self.events.append((name, data))

    @property
    def tracer(self):
        return Tracer(self)

    def of(self, name):
        return [d for n, d in self.events if n == name]


# ---------------------------------------------------------------------------
# tracer + kill switch
# ---------------------------------------------------------------------------


def test_tracer_spans_and_points():
    rec = _Recorder()
    tracer = Tracer(rec)
    span = tracer.begin("collect", actor=1)
    assert span is not None and len(span.id) == 8
    cid = tracer.end(span, rows=4)
    assert cid == span.id
    pid = tracer.point("ingest", parent=cid, actor=1)
    spans = rec.of("span")
    assert [s["name"] for s in spans] == ["collect", "ingest"]
    collect, ingest = spans
    assert collect["parent"] is None and collect["actor"] == 1
    assert collect["rows"] == 4 and collect["t1"] >= collect["t0"]
    assert ingest["parent"] == cid and ingest["span"] == pid
    # a point with t0 covers [t0, now]
    t0 = time.time() - 0.5
    tracer.point("drain", t0=t0)
    drain = rec.of("span")[-1]
    assert drain["dur_ms"] >= 400.0


def test_trace_kill_switch(monkeypatch):
    monkeypatch.setenv("SHEEPRL_TPU_TRACE", "0")
    rec = _Recorder()
    tracer = Tracer(rec)
    assert not tracer.enabled
    span = tracer.begin("collect")
    assert span is None
    assert tracer.end(span) is None          # None-tolerant end
    assert tracer.point("ingest") is None
    assert rec.events == []
    # clock events are suppressed too
    clock = ClockSync(rec)
    clock.add(0.0, 10.0, 0.1)
    assert rec.events == []


def test_tracer_disabled_telemetry_is_noop():
    tracer = Telemetry(None, enabled=False).tracer
    assert not tracer.enabled
    assert tracer.begin("x") is None and tracer.point("y") is None


# ---------------------------------------------------------------------------
# trace context on the wire
# ---------------------------------------------------------------------------


def test_pack_push_trace_meta_roundtrip():
    tree = {"obs": np.zeros((2, 1, 3), np.float32)}
    trace = {"span": "deadbeef", "actor": 1, "mono_ts": 12.5}
    ops, meta = unpack_push(
        pack_push([(tree, None)], rows=2, env_steps=2, weight_version=3, trace=trace)
    )
    assert meta["trace"] == trace
    assert len(ops) == 1
    # old peers: no trace argument -> the key is absent entirely
    _, meta2 = unpack_push(
        pack_push([(tree, None)], rows=2, env_steps=2, weight_version=3)
    )
    assert "trace" not in meta2


def test_publish_span_rides_weights_meta():
    with ReplayService(
        algo="ppo", n_actors=1, mode="chunks", capacity_rows=8, telem=None,
    ) as svc:
        addr = svc.start()
        svc.publish([np.zeros(1, np.float32)], span="feedc0de")
        sock = wire.connect(addr, timeout=5.0)
        wire.send_json(
            sock, wire.HELLO,
            {"actor_id": 0, "role": "weights", "proto": PROTO_VERSION},
        )
        wire.send_json(sock, wire.GET_WEIGHTS, {"have_version": -1})
        kind, payload = wire.recv_frame(sock)
        assert kind == wire.WEIGHTS
        (meta_len,) = struct.unpack_from("<I", payload)
        meta = json.loads(payload[4 : 4 + meta_len].decode())
        assert meta == {"version": 1, "span": "feedc0de"}
        # span-less publish (tracing off / old learner): no key
        svc.publish([np.zeros(1, np.float32)])
        wire.send_json(sock, wire.GET_WEIGHTS, {"have_version": 1})
        kind, payload = wire.recv_frame(sock)
        (meta_len,) = struct.unpack_from("<I", payload)
        assert json.loads(payload[4 : 4 + meta_len].decode()) == {"version": 2}
        sock.close()


@pytest.mark.timeout(60)
def test_push_trace_emits_ingest_and_drain_provenance(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="ppo", run_id="r1")
    with ReplayService(
        algo="ppo", n_actors=1, mode="chunks", capacity_rows=8, telem=telem,
    ) as svc:
        addr = svc.start()
        sock = wire.connect(addr, timeout=5.0)
        wire.send_json(
            sock, wire.HELLO,
            {"actor_id": 0, "pid": 1, "role": "data", "proto": PROTO_VERSION},
        )
        wire.recv_json(sock, wire.WELCOME)
        tree = {"obs": np.zeros((5, 1, 3), np.float32)}
        payload = pack_push(
            [(tree, None)], rows=4, env_steps=4, weight_version=2,
            trace={"span": "abcd1234", "actor": 0, "mono_ts": time.monotonic()},
        )
        wire.send_frame(sock, wire.PUSH, payload)
        wire.recv_json(sock, wire.PUSH_OK)
        assert svc.next_chunk(timeout=5.0) is not None
        prov = svc.last_drain
        assert prov is not None and prov["actor"] == 0
        assert prov["weight_version"] == 2
        assert prov["wait_s"] >= 0.0 and prov["queued_s"] >= 0.0
        # the ingest span landed in the learner shard, parented on the
        # actor's push span, and its id is the drain's parent
        telem.close()
        events = [
            json.loads(line)
            for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()
        ]
        ingest = [e for e in events if e.get("event") == "span"]
        assert len(ingest) == 1 and ingest[0]["name"] == "ingest"
        assert ingest[0]["parent"] == "abcd1234"
        assert prov["span"] == ingest[0]["span"]
        # a timed-out drain clears the provenance
        assert svc.next_chunk(timeout=0.05) is None
        assert svc.last_drain is None
        sock.close()


# ---------------------------------------------------------------------------
# clock sync + sender-monotonic heartbeat age
# ---------------------------------------------------------------------------


def test_clock_sync_min_rtt_wins():
    rec = _Recorder()
    clock = ClockSync(rec)
    # server 10s ahead, symmetric 0.2s RTT
    assert clock.add(100.0, 110.1, 100.2)
    assert clock.offset_s == pytest.approx(10.0)
    assert clock.rtt_s == pytest.approx(0.2)
    # worse RTT: ignored
    assert not clock.add(200.0, 210.8, 201.0)
    assert clock.offset_s == pytest.approx(10.0)
    # better RTT: adopted + re-emitted
    assert clock.add(300.0, 310.04, 300.08)
    assert clock.offset_s == pytest.approx(10.0)
    assert clock.rtt_s == pytest.approx(0.08)
    emitted = rec.of("trace.clock")
    assert len(emitted) == 2
    assert emitted[-1]["samples"] == 3


def test_heartbeat_age_uses_sender_monotonic_clock():
    st = _ActorState(0)
    st.last_heartbeat = time.monotonic()
    st.note_sender_mono(1000.0)
    # sender advanced 5s, receiver advanced 5s -> silent for ~0
    st.note_sender_mono(1005.0)
    st.recv_mono0 -= 5.0  # receiver saw 5s pass since the baseline
    now = time.monotonic()
    assert st.heartbeat_age(now) == pytest.approx(0.0, abs=0.1)
    # receiver saw 9 MORE seconds pass with no newer stamp: silent ~9s
    st.recv_mono0 -= 9.0
    assert st.heartbeat_age(now) == pytest.approx(9.0, abs=0.1)
    # a monotonic REGRESSION (actor restarted) re-baselines instead of
    # producing a bogus negative age
    st.note_sender_mono(3.0)
    assert st.sender_mono0 == 3.0
    assert st.heartbeat_age(time.monotonic()) == pytest.approx(0.0, abs=0.1)


def test_heartbeat_age_falls_back_for_old_peers():
    st = _ActorState(0)
    st.last_heartbeat = 100.0
    assert st.heartbeat_age(103.5) == pytest.approx(3.5)
    st.note_sender_mono(None)  # old peer: no stamp, still the fallback
    assert st.heartbeat_age(103.5) == pytest.approx(3.5)


@pytest.mark.timeout(60)
def test_heartbeat_reply_carries_server_wall_ts():
    with ReplayService(
        algo="ppo", n_actors=1, mode="chunks", capacity_rows=8, telem=None,
    ) as svc:
        addr = svc.start()
        sock = wire.connect(addr, timeout=5.0)
        wire.send_json(
            sock, wire.HELLO,
            {"actor_id": 0, "pid": 1, "role": "data", "proto": PROTO_VERSION},
        )
        wire.recv_json(sock, wire.WELCOME)
        before = time.time()
        wire.send_json(
            sock, wire.HEARTBEAT,
            {"env_steps": 8, "weight_version": 0, "sps": 1.0,
             "mono_ts": time.monotonic(), "wall_ts": before},
        )
        reply = wire.recv_json(sock, wire.HEARTBEAT_OK)
        assert before <= reply["server_wall_ts"] <= time.time()
        sock.close()


# ---------------------------------------------------------------------------
# role shards + run id
# ---------------------------------------------------------------------------


def test_role_shard_filenames(tmp_path):
    learner = Telemetry(str(tmp_path), rank=0, algo="ppo", run_id="r1")
    actor = Telemetry(str(tmp_path), rank=0, algo="ppo", role="actor3", run_id="r1")
    serve = Telemetry(str(tmp_path), rank=0, algo="serve", role="serve", run_id="r1")
    learner.event("ping")
    actor.event("ping")
    serve.event("ping")
    for t in (learner, actor, serve):
        t.close()
    assert (tmp_path / "telemetry.jsonl").exists()
    assert (tmp_path / "telemetry.actor3.jsonl").exists()
    assert (tmp_path / "telemetry.serve.jsonl").exists()


def test_ensure_run_id_exports_to_environment(monkeypatch):
    from sheeprl_tpu.telemetry.trace import RUN_ENV, ensure_run_id

    monkeypatch.delenv(RUN_ENV, raising=False)
    rid = ensure_run_id()
    assert rid and len(rid) == 8
    assert os.environ[RUN_ENV] == rid
    assert ensure_run_id() == rid  # idempotent: subprocesses inherit ONE id
    monkeypatch.setenv(RUN_ENV, "fixed123")
    assert ensure_run_id() == "fixed123"


# ---------------------------------------------------------------------------
# PROFILE frame against a live service
# ---------------------------------------------------------------------------


@pytest.mark.timeout(240)
def test_profile_frame_opens_bounded_window(tmp_path):
    from sheeprl_tpu.telemetry.trace import profile_window

    telem = Telemetry(str(tmp_path), rank=0, algo="ppo", run_id="r1")
    try:
        with ReplayService(
            algo="ppo", n_actors=1, mode="chunks", capacity_rows=8, telem=telem,
        ) as svc:
            addr = svc.start()
            # generous socket timeout: jax.profiler's first-ever trace
            # start cold-initializes its infra, which can take >5s on a
            # loaded CI box
            sock = wire.connect(addr, timeout=60.0)
            wire.send_json(sock, wire.PROFILE, {"seconds": 0.05})
            reply = wire.recv_json(sock, wire.PROFILE)
            sock.close()
            assert reply["ok"] is True, reply
            assert reply["dir"].startswith(str(tmp_path)), reply
            assert reply["seconds"] == pytest.approx(0.05)
            # a second request while the window is open is refused, not
            # stacked — the running trace stays intact
            sock = wire.connect(addr, timeout=60.0)
            wire.send_json(sock, wire.PROFILE, {"seconds": 5})
            second = wire.recv_json(sock, wire.PROFILE)
            sock.close()
            # on a fast box the first window is still open -> refused; on
            # a slow one it may already have closed and this opened a
            # real (bounded) second window — both are correct behavior
            if second["ok"] is False:
                assert "already open" in second["error"]
            deadline = time.monotonic() + 30.0
            while profile_window().active and time.monotonic() < deadline:
                time.sleep(0.05)
            profile_window().close()  # idempotent on a closed window
            assert not profile_window().active
            # `active` flips False the moment close() starts, but the
            # timer thread emits profile.window.stop only AFTER
            # jax.profiler.stop_trace finishes dumping the artifact —
            # slow in a hot process. Wait for the event to land before
            # closing the shard.
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if '"profile.window.stop"' in (
                    tmp_path / "telemetry.jsonl"
                ).read_text():
                    break
                time.sleep(0.1)
    finally:
        telem.close()
    events = [
        json.loads(line)
        for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()
    ]
    names = [e.get("event") for e in events]
    assert "profile.window.start" in names, names
    assert "profile.window.stop" in names, names
    start = next(e for e in events if e["event"] == "profile.window.start")
    assert os.path.isdir(start["dir"])


# ---------------------------------------------------------------------------
# overhead bound (ISSUE 17 acceptance: trace overhead <= 2% sps)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_trace_overhead_within_two_percent(tmp_path):
    """The per-update span pattern the flock learner runs (drain point +
    train span + publish point: 3 JSONL lines) must cost <2% of a
    realistically sized update. The pattern costs ~40us on this box
    (fast-path JSON + cached kill switch + lazy span flush), so the bound
    is checked against a ~5ms workload — well under the smallest real
    flock update. Interleaved pairs + min-of-ratios, same methodology as
    the telemetry overhead bound."""
    a = np.random.default_rng(0).normal(size=(450, 450))

    def workload():
        return float(np.linalg.norm(a @ a))

    iters = 40
    telem = Telemetry(str(tmp_path), rank=0, algo="overhead")
    tracer = telem.tracer

    def run_plain():
        t0 = time.perf_counter()
        for _ in range(iters):
            workload()
        return time.perf_counter() - t0

    def run_traced():
        t0 = time.perf_counter()
        for u in range(iters):
            drain = tracer.point("drain", update=u)
            span = tracer.begin("train", parent=drain, update=u)
            workload()
            tracer.point("publish", parent=tracer.end(span), version=u)
        return time.perf_counter() - t0

    run_plain(), run_traced()  # warmup both paths
    ratios = [run_traced() / run_plain() for _ in range(6)]
    telem.close()
    overhead = min(ratios) - 1.0
    assert overhead < 0.02, f"trace overhead {overhead:.2%} exceeds 2%"
