"""sheepopt unified measured-decision framework receipts (ISSUE 11):
cache keying/invalidation, bit-exactness disqualification, the remat
acceptance gate, the scan-unroll legacy-store migration, the batch-chunk
probe cache, and the propose-diff golden."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.compile import decisions as dec
from sheeprl_tpu.compile.partition import decide_batch_chunk


def _counting_build(calls):
    def build(mult):
        calls.append(mult)

        def fn(x):
            y = x
            for _ in range(int(mult)):
                y = y * 1.0 + 1.0
            return y

        return fn

    return build


def test_decide_seconds_objective_and_cache_hit(tmp_path):
    """The ladder measures every candidate once, persists the decision,
    and a same-key re-run serves from the cache without building or
    compiling anything."""
    store = str(tmp_path / "decisions.json")
    calls = []
    x = jnp.arange(64.0)
    d = dec.decide(
        "toy", "probe", [0, 1], _counting_build(calls), (x,),
        repeats=1, store_path=store,
    )
    assert d.source == "measured"
    assert set(d.candidates) == {"0", "1"}
    assert calls.count(0) >= 1 and calls.count(1) == 1  # 0 also warms up
    n_calls = len(calls)
    again = dec.decide(
        "toy", "probe", [0, 1], _counting_build(calls), (x,),
        repeats=1, store_path=store,
    )
    assert again.source == "cache"
    assert again.winner == d.winner
    assert len(calls) == n_calls  # nothing rebuilt, nothing recompiled
    with open(store) as fh:
        assert d.key in json.load(fh)


def test_cache_invalidated_on_aval_and_version_drift(tmp_path):
    """The key carries avals + jax version + backend: drift in any of
    them is a miss — a decision measured at other shapes or on another
    toolchain never leaks."""
    store = str(tmp_path / "decisions.json")
    calls = []
    x8 = jnp.arange(8.0)
    d = dec.decide(
        "toy", "probe", [0], _counting_build(calls), (x8,),
        repeats=1, store_path=store,
    )
    assert f"jax{jax.__version__}" in d.key and "float32[8]" in d.key
    # aval drift -> fresh measurement
    calls.clear()
    d16 = dec.decide(
        "toy", "probe", [0], _counting_build(calls), (jnp.arange(16.0),),
        repeats=1, store_path=store,
    )
    assert d16.source == "measured" and calls
    # jax-version drift: rewrite the stored key as another version — the
    # current-version lookup must miss it
    with open(store) as fh:
        blob = json.load(fh)
    stale_key = d.key.replace(f"jax{jax.__version__}", "jax0.0.0")
    blob[stale_key] = blob.pop(d.key)
    with open(store, "w") as fh:
        json.dump(blob, fh)
    calls.clear()
    d2 = dec.decide(
        "toy", "probe", [0], _counting_build(calls), (x8,),
        repeats=1, store_path=store,
    )
    assert d2.source == "measured" and calls


def test_bit_exact_disqualification(tmp_path):
    """A candidate whose numerics differ from the baseline is disqualified
    and can never win, even when it is faster."""
    def build(mult):
        return lambda x: x * float(mult)

    d = dec.decide(
        "toy", "tainted", [1, 2], build, (jnp.arange(8.0),),
        repeats=1, store_path=str(tmp_path / "d.json"),
    )
    assert d.candidates["2"]["bit_exact"] is False
    assert d.winner == "1" and not d.accepted


def _scan_grad_build(width=64, steps=24):
    w = jax.random.normal(jax.random.PRNGKey(0), (width, width)) * 0.05
    xs = jax.random.normal(jax.random.PRNGKey(1), (steps, 4, width))
    c0 = jnp.zeros((4, width))

    def build(mode):
        def step(c, x):
            h = jnp.tanh(c @ w + x)
            h2 = jnp.tanh(h @ w)
            return jnp.tanh(h2 @ w + h), h2

        wrapped = dec_checkpoint(step, mode)

        def loss(c0, xs):
            _, ys = jax.lax.scan(wrapped, c0, xs)
            return jnp.sum(ys * ys)

        return jax.value_and_grad(loss, argnums=(0, 1))

    return build, (c0, xs)


def dec_checkpoint(step, mode):
    from sheeprl_tpu.ops.scan import checkpoint_body

    return checkpoint_body(step, mode)


def test_remat_acceptance_gate_accepts_byte_win(tmp_path):
    """A grad-of-scan probe where checkpointing strictly reduces
    `memory_analysis()` peak bytes: the bytes objective accepts a remat
    rung (bit-exact receipt required) under a permissive time budget, and
    the decision records the byte delta."""
    build, example = _scan_grad_build()
    d = dec.decide_remat(
        "test.scan_grad", build, example, repeats=1,
        store_path=str(tmp_path / "d.json"), max_time_cost_frac=10.0,
    )
    assert d.winner in ("on", "policy") and d.accepted
    assert d.candidate(d.winner)["bit_exact"] is True
    assert d.bytes_delta() is not None and d.bytes_delta() < 0


def test_remat_acceptance_gate_time_budget_rejects(tmp_path):
    """The <=X% exec-time gate is enforced: with a budget below the
    baseline's own time, no remat rung can qualify and the baseline is
    kept — bytes never win unboundedly."""
    build, example = _scan_grad_build()
    d = dec.decide_remat(
        "test.scan_grad_tight", build, example, repeats=1,
        store_path=str(tmp_path / "d.json"), max_time_cost_frac=-0.9,
    )
    assert d.winner == "off" and not d.accepted


def test_remat_no_scan_keeps_baseline(tmp_path):
    """With nothing live across a scan, remat cannot strictly reduce peak
    bytes — the baseline survives the bytes objective."""
    def build(mode):
        return lambda x: jnp.sum(x * 2.0)

    d = dec.decide_remat(
        "test.no_scan", build, (jnp.arange(32.0),), repeats=1,
        store_path=str(tmp_path / "d.json"), max_time_cost_frac=10.0,
    )
    assert d.winner == "off" and not d.accepted


def test_batch_chunk_probe_served_from_cache(tmp_path):
    """The decide_batch_chunk measurement (lowering + trial compile) is
    memoized in the unified cache: the second call never lowers or
    compiles, and the decision is re-derived from the cached counts."""
    lowers = []

    class CountingJit:
        def __init__(self, fn):
            self._jit = jax.jit(fn)
            self.__qualname__ = "test.counting_probe"
            self.__module__ = __name__

        def lower(self, *a):
            lowers.append(1)
            return self._jit.lower(*a)

    fn = CountingJit(lambda x: jnp.tanh(x) @ jnp.ones((8, 8)))
    example = (jnp.zeros((4, 8)),)
    store = str(tmp_path / "decisions.json")
    d1 = decide_batch_chunk(
        fn, example, batch=4, backend="cpu", store_path=store
    )
    assert lowers and "[probe cache]" not in d1.reason
    n = len(lowers)
    d2 = decide_batch_chunk(
        fn, example, batch=4, backend="cpu", store_path=store
    )
    assert len(lowers) == n  # zero lowering/trial compiles on the hit
    assert "[probe cache]" in d2.reason
    assert d2.chunk == d1.chunk
    assert d2.counts["convolutions"] == d1.counts["convolutions"]


def test_measured_probe_errors_not_cached(tmp_path):
    store = str(tmp_path / "decisions.json")
    rec, src = dec.measured_probe(
        "toy", "boom", (jnp.zeros(1),), lambda: {"error": "nope"},
        store_path=store,
    )
    assert rec["error"] == "nope" and src == "measured"
    rec2, src2 = dec.measured_probe(
        "toy", "boom", (jnp.zeros(1),), lambda: {"ok": 1}, store_path=store
    )
    assert src2 == "measured" and rec2 == {"ok": 1}  # retried, then cached
    _, src3 = dec.measured_probe(
        "toy", "boom", (jnp.zeros(1),), lambda: {"ok": 2}, store_path=store
    )
    assert src3 == "cache"


def test_remat_mode_and_checkpoint_body():
    assert dec.remat_mode(True) == "on" and dec.remat_mode(False) == "off"
    assert dec.remat_mode("on") == "on"
    assert dec.remat_mode("policy") == "policy"
    assert dec.remat_mode("auto") == "off"  # unresolved auto = baseline
    assert dec.remat_mode("junk") == "off"
    assert dec.remat_enabled("policy") and not dec.remat_enabled("off")
    from sheeprl_tpu.ops.scan import checkpoint_body

    step = lambda c, x: (c, x)  # noqa: E731
    assert checkpoint_body(step, "off") is step
    assert checkpoint_body(step, False) is step
    assert checkpoint_body(step, "auto") is step
    assert checkpoint_body(step, "on") is not step
    assert checkpoint_body(step, True) is not step
    assert checkpoint_body(step, "policy") is not step


# ---------------------------------------------------------------------------
# the remat receipt in the memory budget gate
# ---------------------------------------------------------------------------


def test_memory_budget_remat_receipt():
    """check_memory_budget gates the @remat/@scan twin pair: a remat train
    step whose peak stops undercutting its non-remat twin by the
    tolerance fails CI; a healthy reduction is a note."""
    from sheeprl_tpu.analysis.memory_check import check_memory_budget

    def entry(peak):
        return {"peak_bytes": peak, "aliases": [], "large_constants": []}

    good = {
        "memory": {
            "x@scan/train_step": entry(100),
            "x@remat/train_step": entry(70),
        }
    }
    failures, notes = check_memory_budget({"memory": dict(good["memory"])}, good)
    assert not failures
    assert any("remat peak" in n for n in notes)
    bad = {
        "memory": {
            "x@scan/train_step": entry(100),
            "x@remat/train_step": entry(95),
        }
    }
    failures, _ = check_memory_budget({"memory": dict(bad["memory"])}, bad)
    assert any("stopped buying its bytes" in f for f in failures)
    # only the train step is gated: other jits of the twins don't trip it
    other = {
        "memory": {
            "x@scan/player_step": entry(100),
            "x@remat/player_step": entry(100),
        }
    }
    failures, _ = check_memory_budget({"memory": dict(other["memory"])}, other)
    assert not failures


# ---------------------------------------------------------------------------
# sheepopt --propose golden
# ---------------------------------------------------------------------------


def _load_sheepopt():
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "sheepopt_under_test", os.path.join(repo, "tools", "sheepopt.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sheepopt_propose_diff_golden(tmp_path):
    """--propose over a fixture ledger: an undonated player_step emits
    the exact donating_jit diff for its known code site, a replicated
    comms entry emits the sharding proposal, and a scan buffer emits the
    --remat auto pointer."""
    so = _load_sheepopt()
    fixture = {
        "jits": {
            "dreamer_v2/player_step": {
                "donated": 0,
                "in_avals": [
                    "float32[4,256]", "float32[4,64]", "uint8[4,64,64,3]",
                ],
                "out_avals": ["float32[4,256]", "float32[4,64]"],
            },
        },
        "memory": {
            "dreamer_v2/player_step": {"aliases": [], "donated": 0},
            "dreamer_v2/train_step": {
                "scan_buffers": [
                    {"shape": "f32[4,256]", "bytes": 4096, "trip_count": 64}
                ],
            },
        },
        "comms": {
            "fix@mesh/train_step": {
                "replicated_inputs": ["f32[1024,1024]"],
                "replicated_bytes": 4194304,
                "mesh": {"data": 8},
            },
        },
    }
    with open(tmp_path / "dreamer_v2.json", "w") as fh:
        json.dump(fixture, fh)
    ledger = so.load_ledger(str(tmp_path))
    donations = so.propose_donations(ledger)
    assert len(donations) == 1
    p = donations[0]
    assert p["key"] == "dreamer_v2/player_step"
    assert p["open_matches"] == 2
    assert p["file"] == "sheeprl_tpu/algos/dreamer_v2/dreamer_v2.py"
    assert (
        "+    player_step = donating_jit(_player_step, donate_argnums=(1,))"
        in p["diff"]
    )
    shardings = so.propose_shardings(ledger)
    assert len(shardings) == 1
    assert shardings[0]["replicated_bytes"] == 4194304
    remat = so.propose_remat(ledger)
    assert any(
        r["key"] == "dreamer_v2/train_step" and "--remat auto" in r["advice"]
        for r in remat
    )
    # the skip-list honors justified refusals
    fixture["jits"]["ppo_recurrent/policy_step"] = {
        "donated": 0,
        "in_avals": ["float32[2,8]"],
        "out_avals": ["float32[2,8]"],
    }
    with open(tmp_path / "ppo_recurrent.json", "w") as fh:
        json.dump({"jits": {
            "ppo_recurrent/policy_step": fixture["jits"]["ppo_recurrent/policy_step"]
        }}, fh)
    donations = so.propose_donations(so.load_ledger(str(tmp_path)))
    assert not any(p["key"] == "ppo_recurrent/policy_step" for p in donations)


def test_sheepopt_propose_on_committed_ledger():
    """The real committed ledger parses and proposes without error — the
    CI artifact's contract (stdlib-only, advisory exit 0)."""
    so = _load_sheepopt()
    ledger = so.load_ledger(so.budget_dir())
    assert ledger["jits"]
    donations = so.propose_donations(ledger)
    remat = so.propose_remat(ledger)
    assert isinstance(donations, list) and isinstance(remat, list)
    # justified refusals never resurface
    assert not any(
        p["key"].startswith("ppo_recurrent") and p["key"].endswith("policy_step")
        for p in donations
    )
