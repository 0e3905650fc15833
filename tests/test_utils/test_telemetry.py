"""Telemetry subsystem (ISSUE 2): phase-timer nesting/exception safety, the
XLA compile tracker on a forced retrace, JSONL well-formedness + replay
through tools/telemetry_report.py, the NaN watchdog, decoupled-topology
gauges, and the always-on overhead bound (the instrumented path must stay
within 2% of uninstrumented on a CPU-sized workload)."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from sheeprl_tpu.telemetry import (
    CompileTracker,
    PhaseTimers,
    Telemetry,
    monitoring_supported,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_report_module():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools", "telemetry_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# phase timers
# ---------------------------------------------------------------------------


def test_phase_nesting_builds_hierarchical_names():
    t = PhaseTimers()
    with t.phase("train"):
        with t.phase("dispatch"):
            time.sleep(0.002)
    out = t.flush()
    assert set(out) == {"train", "train/dispatch"}
    # the parent's span covers the child
    assert out["train"] >= out["train/dispatch"] > 0.0
    assert t.flush() == {}  # flush clears


def test_phase_exception_safety_records_time_and_reraises():
    t = PhaseTimers()
    with pytest.raises(RuntimeError):
        with t.phase("doomed"):
            time.sleep(0.002)
            raise RuntimeError("boom")
    out = t.flush()
    assert out["doomed"] > 0.0


def test_mark_sections_accumulate_and_flush_restarts_open_phase():
    t = PhaseTimers()
    t.mark("a")
    time.sleep(0.002)
    t.mark("b")  # ends a, starts b
    time.sleep(0.002)
    first = t.flush()  # b is OPEN: contributes elapsed and restarts
    assert first["a"] > 0.0 and first["b"] > 0.0
    time.sleep(0.002)
    t.mark(None)
    second = t.flush()
    # b's post-flush time lands in the second interval — no loss, no double
    # count across the flush boundary
    assert set(second) == {"b"} and second["b"] > 0.0


# ---------------------------------------------------------------------------
# spans: the same transitions, kept in memory and written when the run ends
# ---------------------------------------------------------------------------


def _spans(path):
    with open(path) as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e["event"] == "span"], events


def test_spans_nest_under_the_phase_the_mark_and_the_iteration():
    t = PhaseTimers()
    t.iteration(7)
    t.mark("rollout/pack")
    with t.phase("outer"):
        with t.phase("inner"):
            pass
    t.mark("log/write")  # a mark after a mark closes the previous span
    t.count(scalars=85)
    t.count(flushed=True)
    t.iteration(8)  # ... and so does the next iteration, which closes 7 too
    t.mark("rollout/pack")
    by = {(s["name"], s["step"]): s for s in t.drain()}  # drain closes what is open
    it7, it8 = by["iteration", 7], by["iteration", 8]
    assert it7["parent"] is None and it8["parent"] is None and it7["span"] != it8["span"]
    assert by["rollout/pack", 7]["parent"] == it7["span"]
    assert by["outer", 7]["parent"] == by["rollout/pack", 7]["span"]
    assert by["outer/inner", 7]["parent"] == by["outer", 7]["span"]
    assert by["log/write", 7]["parent"] == it7["span"]
    assert by["log/write", 7]["scalars"] == 85 and by["log/write", 7]["flushed"] is True
    assert by["rollout/pack", 8]["parent"] == it8["span"]
    # linear marks tile their parent: one ends where the next starts
    assert by["rollout/pack", 7]["t1"] == by["log/write", 7]["t0"]
    assert by["log/write", 7]["t1"] == it7["t1"] == it8["t0"]
    assert {"name", "span", "parent", "t0", "t1", "dur_ms", "step", "p0"} <= set(it7)
    assert list(t.drain()) == []  # the ring is handed out once


def test_children_and_self_time_make_up_the_parent():
    t = PhaseTimers()
    t.iteration(0)
    time.sleep(0.001)  # self time: no child covers it
    t.mark("a")
    time.sleep(0.001)
    t.mark("b")
    time.sleep(0.001)
    t.mark(None)
    time.sleep(0.001)
    spans = list(t.drain())
    parent = next(s for s in spans if s["name"] == "iteration")
    children = [s for s in spans if s["parent"] == parent["span"]]
    assert [c["name"] for c in children] == ["a", "b"]
    assert all(parent["p0"] <= c["p0"] and c["t1"] <= parent["t1"] for c in children)
    self_ms = parent["dur_ms"] - sum(c["dur_ms"] for c in children)
    assert 1.5 < self_ms < parent["dur_ms"] - 1.5  # the two uncovered sleeps, nothing else
    # the iteration is a span, not a phase: it has no Time/* sum of its own
    assert set(t.flush()) == {"a", "b"}


def test_a_finer_span_is_logged_under_the_sum_its_mark_names(tmp_path):
    """A logged scalar costs the loop time, a span costs none: the mains keep
    their few `Time/*` sums and open finer spans under them."""
    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    telem.iteration(1)
    telem.mark("rollout/pack", phase="rollout")
    time.sleep(0.001)
    telem.mark("rollout/env_step", phase="rollout")
    time.sleep(0.001)
    telem.mark("buffer/sample")
    telem.mark(None)
    merged = telem.interval({"Loss/x": 1.0}, step=1)
    telem.close()
    assert {k for k in merged if k.startswith("Time/")} == {"Time/rollout_seconds", "Time/buffer/sample_seconds"}
    assert merged["Time/rollout_seconds"] >= 0.002
    spans, _ = _spans(tmp_path / "telemetry.jsonl")
    assert [s["name"] for s in spans] == ["rollout/pack", "rollout/env_step", "buffer/sample", "iteration"]


def test_the_ring_keeps_the_newest_spans_and_drops_the_oldest():
    t = PhaseTimers(ring=4)
    for i in range(10):
        t.mark(f"m{i}")
    t.mark(None)
    assert [s["name"] for s in t.drain()] == ["m6", "m7", "m8", "m9"]


@pytest.mark.parametrize("way_out", ["close", "abort", "abort_with_error"])
def test_every_way_out_of_a_run_writes_the_spans(tmp_path, way_out):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    telem.iteration(3)
    telem.mark("rollout/env_step")
    telem.count(scalars=4)
    telem.mark(None)
    path = tmp_path / "telemetry.jsonl"
    assert _spans(path)[0] == []  # nothing is written per span inside the loop
    if way_out == "close":
        telem.close()
    else:
        telem.abort("RuntimeError: boom" if way_out == "abort_with_error" else None)
    spans, events = _spans(path)
    assert [(s["name"], s["step"]) for s in spans] == [("rollout/env_step", 3), ("iteration", 3)]
    assert spans[0]["scalars"] == 4 and spans[0]["parent"] == spans[1]["span"]
    # a post-mortem still tells the ways out apart, and the spans come before the verdict
    assert (events[-1]["event"] == "end") == (way_out == "close")
    assert ("crash" in [e["event"] for e in events]) == (way_out == "abort_with_error")


@pytest.mark.parametrize("switch", ["SHEEPRL_TPU_TELEMETRY", "SHEEPRL_TPU_TRACE"])
def test_the_kill_switches_govern_the_spans(tmp_path, monkeypatch, switch):
    monkeypatch.setenv(switch, "0")
    enabled = switch != "SHEEPRL_TPU_TELEMETRY"  # what `from_args` reads
    telem = Telemetry(str(tmp_path), rank=0, algo="unit", enabled=enabled)
    telem.iteration(1)
    telem.mark("rollout")
    with telem.phase("inner"):  # phase() respects `enabled` as mark() does
        time.sleep(0.001)
    telem.count(n=1)
    merged = telem.interval({"Loss/x": 1.0}, step=1)
    telem.close()
    assert not telem.timers.ring
    path = tmp_path / "telemetry.jsonl"
    if enabled:  # tracing off: the Time/* sums stay, no span is kept or written
        assert merged["Time/inner_seconds"] > 0.0
        assert _spans(path)[0] == []
    else:  # telemetry off: no phase opens at all, no file
        assert merged == {"Loss/x": 1.0} and telem.timers.flush() == {}
        assert not path.exists()


def test_phases_lie_in_the_profilers_host_plane_on_its_clock(tmp_path):
    """In any open `jax.profiler` session the program's phases are
    `sheeprl/<phase>` events of the host plane, the iteration a step event
    that carries its step; outside a session nothing is annotated."""
    import glob

    import jax
    from jax.profiler import ProfileData

    t = PhaseTimers()
    t.iteration(1)  # no session open: a flag test, no annotation
    t.mark("before")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t.iteration(7)
        t.mark("rollout/pack")
        with t.phase("inner"):
            time.sleep(0.001)
        t.mark("log/write")
        t.iteration(None)
    finally:
        jax.profiler.stop_trace()
    (trace,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(trace).planes if p.name == "/host:CPU"]
    events = {e.name: e for p in host for line in p.lines for e in line.events if e.name.startswith("sheeprl/")}
    assert set(events) == {"sheeprl/iteration", "sheeprl/rollout/pack", "sheeprl/inner", "sheeprl/log/write"}
    assert dict(events["sheeprl/iteration"].stats)["step_num"] == 7
    it, inner = events["sheeprl/iteration"], events["sheeprl/inner"]
    assert it.start_ns <= inner.start_ns and inner.start_ns + inner.duration_ns <= it.start_ns + it.duration_ns
    assert inner.duration_ns >= 1e6
    # the annotations change nothing about what the ring keeps
    assert [s["name"] for s in t.drain()] == ["before", "iteration", "inner", "rollout/pack", "log/write", "iteration"]


class _Clock:
    """perf_counter / time for phase.py that move only when the test says."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now

    def time(self):
        return self.now


def _a_loop(t, clock, children):
    """Two iterations with a read inside a mark and inside a mark inside a
    phase; `children` splits each read as `pull` does."""
    for step in (1, 2):
        t.iteration(step)
        t.mark("rollout/action_wait", phase="rollout")
        clock.now += 1.0
        if children:
            t.child("ready")
            clock.now += 3.0
            t.child("copy")
            clock.now += 0.5
            t.child(None)
        else:
            clock.now += 3.5
        t.mark(None)
        with t.phase("update"):
            t.mark("log/pull", phase="log")
            if children:
                t.child("ready")
            clock.now += 2.0
            t.mark(None)
        clock.now += 0.25


def test_a_child_adds_to_no_sum_and_leaves_the_flush_as_it_was(monkeypatch):
    from sheeprl_tpu.telemetry import phase

    flushed = []
    for children in (False, True):
        clock = _Clock()
        monkeypatch.setattr(phase, "time", clock)
        t = PhaseTimers()
        _a_loop(t, clock, children)
        flushed.append(t.flush())
    assert flushed[0] == flushed[1] == {"rollout": 9.0, "update": 4.0, "log": 4.0}


def test_a_child_is_named_and_parented_by_the_innermost_open_span():
    t = PhaseTimers()
    t.child("ready")  # nothing open: no child
    _a_loop(t, _Clock(), children=True)
    spans = list(t.drain())
    by_id = {s["span"]: s for s in spans}
    parts = [s for s in spans if s["name"].endswith(("/ready", "/copy"))]
    assert [s["name"] for s in parts] == ["rollout/action_wait/ready", "rollout/action_wait/copy", "log/pull/ready"] * 2
    for s in parts:
        parent = by_id[s["parent"]]
        assert s["name"].startswith(parent["name"] + "/") and s["step"] == parent["step"]
        assert parent["p0"] <= s["p0"] and s["t1"] <= parent["t1"]
    # a mark opened inside a live phase is the innermost span: the child (and a count) is the mark's,
    # and the mark closing ends the child left open inside it
    pulls = [s for s in spans if s["name"] == "log/pull"]
    assert {by_id[s["parent"]]["name"] for s in parts if s["name"] == "log/pull/ready"} == {"log/pull"}
    assert all(by_id[s["parent"]]["name"] == "iteration" for s in pulls)


def test_a_count_goes_to_the_span_opened_last():
    t = PhaseTimers()
    t.iteration(0)
    with t.phase("update"):
        t.mark("log/pull", phase="log")
        t.count(arrays=2)
        t.mark(None)
        t.count(sequences=8)
    t.mark("rollout/pack")
    with t.phase("inner"):
        t.count(n=1)
    t.iteration(None)
    by = {s["name"]: s for s in t.drain()}
    assert by["log/pull"]["arrays"] == 2 and by["update"]["sequences"] == 8 and by["inner"]["n"] == 1
    assert "arrays" not in by["update"] and "n" not in by["rollout/pack"]


def test_a_child_opens_no_annotation_in_a_profiler_session(monkeypatch):
    """With a session open the read's span is annotated and its parts are
    not: a profiler's reader puts what happens in them down to the read."""
    from sheeprl_tpu.telemetry import phase

    opened = []

    class _Annotation:
        def __init__(self, name, **kw):
            self.name = name

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(phase, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(phase, "StepTraceAnnotation", _Annotation)
    t = PhaseTimers()
    _a_loop(t, _Clock(), children=True)
    assert opened == ["sheeprl/iteration", "sheeprl/rollout/action_wait", "sheeprl/update", "sheeprl/log/pull"] * 2
    assert sum(s["name"].endswith(("/ready", "/copy")) for s in t.drain()) == 6


@pytest.mark.parametrize("switch", ["spans_false", "SHEEPRL_TPU_TRACE", "SHEEPRL_TPU_TELEMETRY"])
def test_the_kill_switches_leave_no_child(tmp_path, monkeypatch, switch):
    if switch == "spans_false":
        t = PhaseTimers(spans=False)
    else:
        monkeypatch.setenv(switch, "0")
        t = Telemetry.from_args(object(), str(tmp_path), algo="unit")
    t.iteration(1)
    t.mark("log/pull")
    t.child("ready")
    t.child("copy")
    t.child(None)
    t.iteration(None)
    ring = t.ring if switch == "spans_false" else t.timers.ring
    assert not t.spans and not ring
    if switch != "spans_false":
        t.close()


# ---------------------------------------------------------------------------
# compile tracker
# ---------------------------------------------------------------------------


def test_compile_tracker_counts_forced_retrace():
    if not monitoring_supported():
        pytest.skip("jax.monitoring not available in this jax")
    import jax
    import jax.numpy as jnp

    tracker = CompileTracker().attach()
    try:
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        f(jnp.ones((7,))).block_until_ready()
        first = tracker.flush()
        f(jnp.ones((13,))).block_until_ready()  # new shape -> forced retrace
        second = tracker.flush()
    finally:
        tracker.detach()
    assert first["compiles"] >= 1
    assert second["compiles"] >= 1, "retrace did not increment the counter"
    assert second["total_compiles"] >= first["compiles"] + second["compiles"] - 1
    assert second["total_compile_seconds"] > 0.0
    # detached trackers stop counting
    f2 = jax.jit(lambda x: x - 5.0)
    f2(jnp.ones((3,))).block_until_ready()
    assert tracker.flush()["compiles"] == 0


# ---------------------------------------------------------------------------
# JSONL events + report replay
# ---------------------------------------------------------------------------


def test_jsonl_wellformed_and_replayable_by_report(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    telem.event("start", algo="unit", env_id="dummy", seed=1)
    telem.mark("rollout")
    time.sleep(0.002)
    telem.mark("train/dispatch")
    merged = telem.interval({"Loss/x": 0.25}, step=100, sps=50.0)
    assert merged["Loss/x"] == 0.25
    assert merged["Time/rollout_seconds"] > 0.0
    telem.close()

    path = tmp_path / "telemetry.jsonl"
    lines = path.read_text().strip().splitlines()
    events = [json.loads(l) for l in lines]  # every line parses strictly
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start" and kinds[-1] == "end"
    assert "log" in kinds
    log_ev = events[kinds.index("log")]
    assert log_ev["step"] == 100
    assert log_ev["metrics"]["Time/step_per_second"] == 50.0

    mod = _load_report_module()
    summary = mod.summarize(mod.load_events(str(tmp_path)))
    assert summary["end"] is not None and summary["crash"] is None
    assert summary["last_step"] == 100
    assert "rollout" in summary["phase_seconds"]
    assert mod.render(summary)  # renders without raising


def test_report_tolerates_truncated_tail_and_reports_crash(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    telem.event("start", algo="unit")
    telem.interval({"Loss/x": 1.0}, step=1)
    telem.event("crash", error="KeyboardInterrupt")
    telem.close()
    path = tmp_path / "telemetry.jsonl"
    with open(path, "a") as fh:
        fh.write('{"ts": 1, "event": "log", "metr')  # crash mid-write
    mod = _load_report_module()
    summary = mod.summarize(mod.load_events(str(path)))
    assert summary["crash"] is not None
    assert "CRASHED" in mod.render(summary)


def test_selftest_entrypoint_passes():
    mod = _load_report_module()
    assert mod.main(["--selftest"]) == 0


def test_sheeptrace_selftest_entrypoint_passes():
    """sheeptrace's selftest builds skewed multi-role shards through the
    real Telemetry and asserts clock merge + chain reconstruction — wired
    exactly like telemetry_report's."""
    spec = importlib.util.spec_from_file_location(
        "sheeptrace", os.path.join(REPO, "tools", "sheeptrace.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--selftest"]) == 0


def test_report_reads_role_shard_when_learner_shard_absent(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit", role="actor0")
    telem.event("start", algo="unit")
    telem.interval({"Loss/x": 1.0}, step=3)
    telem.close()
    assert not (tmp_path / "telemetry.jsonl").exists()
    mod = _load_report_module()
    summary = mod.summarize(mod.load_events(str(tmp_path)))
    assert summary["last_step"] == 3


# ---------------------------------------------------------------------------
# NaN watchdog
# ---------------------------------------------------------------------------


def test_nan_watchdog_fires_on_injected_inf(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    merged = telem.interval(
        {"Loss/ok": 1.0, "Loss/exploded": float("inf"), "Loss/gone": float("nan")},
        step=7,
    )
    telem.close()
    assert merged["Health/nonfinite_metrics"] == 2.0
    events = [
        json.loads(l)
        for l in (tmp_path / "telemetry.jsonl").read_text().strip().splitlines()
    ]
    nan_evs = [e for e in events if e["event"] == "health.nan"]
    assert len(nan_evs) == 1
    assert nan_evs[0]["keys"] == ["Loss/exploded", "Loss/gone"]
    assert nan_evs[0]["step"] == 7
    # the log event must still be strict JSON despite the non-finite values
    log_evs = [e for e in events if e["event"] == "log"]
    assert log_evs and isinstance(log_evs[0]["metrics"]["Loss/exploded"], str)


def test_disabled_telemetry_passes_metrics_through(tmp_path):
    telem = Telemetry(str(tmp_path), rank=0, algo="unit", enabled=False)
    metrics = {"Loss/x": 1.0}
    assert telem.interval(metrics, step=1) is metrics
    telem.mark("rollout")  # all no-ops, no file
    telem.close()
    assert not (tmp_path / "telemetry.jsonl").exists()


def test_nonzero_rank_writes_no_jsonl(tmp_path):
    telem = Telemetry(str(tmp_path), rank=1, algo="unit")
    out = telem.interval({"Loss/x": 1.0}, step=1)
    telem.close()
    assert "Loss/x" in out  # timers/merge still work (no-op logger eats it)
    assert not (tmp_path / "telemetry.jsonl").exists()


# ---------------------------------------------------------------------------
# decoupled-topology gauges
# ---------------------------------------------------------------------------


def test_decoupled_gauges_track_transfers_and_staleness():
    import jax.numpy as jnp

    from sheeprl_tpu.parallel.decoupled import make_decoupled_meshes

    meshes = make_decoupled_meshes(2)
    g0 = meshes.telemetry_gauges()
    assert g0["Decoupled/data_transfers"] == 0.0
    assert g0["Decoupled/weight_queue_depth"] == 0.0

    meshes.to_trainers({"x": jnp.ones((4, 3))})
    meshes.to_player({"w": jnp.ones((5,))})
    g1 = meshes.telemetry_gauges()
    assert g1["Decoupled/data_transfers"] == 1.0
    assert g1["Decoupled/data_mb_total"] > 0.0
    assert g1["Decoupled/weight_transfers"] == 1.0
    assert g1["Decoupled/weight_queue_depth"] == 1.0  # shipped, not applied

    meshes.note_weights_applied()
    g2 = meshes.telemetry_gauges()
    assert g2["Decoupled/weight_queue_depth"] == 0.0
    assert g2["Decoupled/weight_staleness_s"] >= 0.0


# ---------------------------------------------------------------------------
# end-to-end: tiny PPO run writes telemetry; the report reads it back
# ---------------------------------------------------------------------------


@pytest.mark.timeout(300)
def test_tiny_ppo_run_emits_telemetry_and_report_renders(tmp_path):
    import sheeprl_tpu.algos  # noqa: F401 - fire registrations
    from sheeprl_tpu.utils.registry import tasks

    tasks["ppo"](
        [
            "--env_id", "CartPole-v1", "--dry_run", "--num_envs", "1",
            "--rollout_steps", "8", "--per_rank_batch_size", "4",
            "--update_epochs", "1", "--dense_units", "8", "--mlp_layers", "1",
            "--cnn_features_dim", "16", "--mlp_features_dim", "8",
            "--root_dir", str(tmp_path), "--run_name", "telem",
        ]
    )
    log_dir = tmp_path / "telem"
    assert (log_dir / "telemetry.jsonl").exists()
    mod = _load_report_module()
    summary = mod.summarize(mod.load_events(str(log_dir)))
    assert summary["start"]["algo"] == "ppo"
    assert summary["end"] is not None and summary["crash"] is None
    # the acceptance phases: rollout + train/dispatch measured, checkpoint
    # lifecycle recorded via save_checkpoint's global emit
    assert summary["phase_seconds"].get("rollout", 0.0) > 0.0
    assert "train/dispatch" in summary["phase_seconds"]
    assert summary["checkpoints"], "checkpoint event missing"
    rendered = mod.render(summary)
    assert "phase breakdown" in rendered and "rollout" in rendered


# ---------------------------------------------------------------------------
# overhead bound
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_telemetry_overhead_within_two_percent(tmp_path):
    """The always-on instrumentation pattern every main uses (a few marks +
    one interval() per logging interval) must cost <2% of a realistically
    sized step. Per-mark cost on this box is ~5-10us and interval() ~200us
    (dominated by the JSONL flush), so the bound is checked against a
    ~3-4ms workload — the floor of what one env step + dispatch costs even
    on the tiny CPU configs; real updates are 10-1000x larger."""
    a = np.random.default_rng(0).normal(size=(300, 300))

    def workload():
        return float(np.linalg.norm(a @ a))

    iters, interval_every = 60, 15

    def run_plain():
        t0 = time.perf_counter()
        for _ in range(iters):
            workload()
        return time.perf_counter() - t0

    telem = Telemetry(str(tmp_path), rank=0, algo="overhead")

    def run_instrumented():
        t0 = time.perf_counter()
        for i in range(iters):
            telem.mark("rollout")
            workload()
            telem.mark("train/dispatch")
            telem.mark("log")
            if (i + 1) % interval_every == 0:
                telem.interval({"Loss/x": 1.0}, step=i)
        return time.perf_counter() - t0

    run_plain(), run_instrumented()  # warmup both paths
    # interleaved pairs + min-of-ratios: a box-wide slowdown hits both arms
    # of a pair equally, and one clean pair suffices to prove the bound
    ratios = [run_instrumented() / run_plain() for _ in range(6)]
    telem.close()
    overhead = min(ratios) - 1.0
    assert overhead < 0.02, f"telemetry overhead {overhead:.2%} exceeds 2%"
