"""`sheeprl_tpu serve` — the batched policy-inference serving tier.

Wiring, in dependency order:

  1. rebuild the policy from --ckpt (its args.json sidecar) or a fresh
     --model_argv init (policies.py);
  2. size the batch ladder from the committed sheepmem ledger, trial
     compiles memoized in the decision cache as the fallback (ladder.py);
  3. register ONE fixed-shape policy jit per accepted rung on the
     CompilePlan (`policy_b<rung>`) — `--warm_compile on` (the serving
     default) AOT-compiles them in the background while the socket comes
     up, and the analysis capture sweep (`SHEEPRL_TPU_PLAN_MODE=capture`)
     unwinds HERE with every serving executable recorded, so
     sheepcheck/sheepshard/sheepmem gate the serving jits exactly like
     the training jits;
  4. hot-reloadable params (params.py), micro-batcher (batcher.py),
     FLK1 socket front (server.py);
  5. the serve loop: heartbeat `Serve/*` telemetry intervals, optional
     checkpoint-directory polling for automatic hot reload, graceful
     drain on SIGTERM/SIGINT — in-flight batches finish, queued requests
     are served (the batcher's zero-drop close), NEW requests are shed
     with reason="draining", and the process exits rc 75 (the shared
     resumable/preempted code). `--serve_requests` completion stays a
     plain rc 0. An armed `peer.crash@k` fault SIGKILLs the server at
     loop step k (the server-crash injection site).

The resolved listen address is printed AND written to
`<log_dir>/serve_address` so scripted clients never parse stdout.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional, Sequence

import numpy as np

from ..utils.parser import DataclassArgumentParser
from ..utils.registry import register_algorithm

__all__ = ["main"]

ADDRESS_FILE = "serve_address"


@register_algorithm(name="serve")
def main(argv: Optional[Sequence[str]] = None) -> None:
    import jax

    from ..compile import CompilePlan
    from ..telemetry.core import Telemetry
    from ..utils.logger import create_logger
    # deferred: serve.args subclasses algos' StandardArgs, and THIS module
    # is imported by the algos registry while sheeprl_tpu.algos is itself
    # mid-import — a top-level import here would close the cycle
    from . import ladder as ladder_mod
    from .args import ServeArgs
    from .batcher import MicroBatcher
    from .params import ParamsStore
    from .policies import build_policy
    from .server import ServeServer

    parser = DataclassArgumentParser(ServeArgs)
    (args,) = parser.parse_args_into_dataclasses(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    np.random.seed(args.seed)

    logger, log_dir, run_name = create_logger(args, "serve", process_index=0)
    logger.log_hyperparams(args.as_dict())
    telem = Telemetry.from_args(args, log_dir, 0, algo="serve", role="serve")
    from ..telemetry.trace import install_profile_signal

    install_profile_signal(log_dir)
    plan = CompilePlan.from_args(args, telem)
    telem.add_gauges(plan.gauges)

    policy, params, loader = build_policy(args, log_dir)
    store = ParamsStore(loader, params, source=args.ckpt, telem=telem)

    qstate = None
    if args.quant == "int8":
        from . import quant as quant_mod

        qstate = quant_mod.QuantState(policy, args, log_dir, telem=telem)
        telem.add_gauges(qstate.gauges)

    requested = ladder_mod.parse_rungs(args.ladder, args.max_batch)
    spec = ladder_mod.ledger_spec(args.algo)
    if plan.capture_only:
        # capture sweep: record every requested rung — the gates must see
        # the full ladder, and sizing probes would defeat the point of a
        # compile-free capture
        accepted = list(requested)
    else:
        decisions = ladder_mod.size_ladder(
            policy.step, lambda r: policy.example(params, r), requested, spec,
            store_path=os.path.join(log_dir, "serve_ladder.json"),
        )
        for d in decisions:
            telem.event("serve.ladder", **d.as_event())
        accepted = [d.rung for d in decisions if d.accepted]

    int8_rungs: set = set()
    if qstate is not None:
        version0, live0 = store.current()
        if plan.capture_only:
            # capture sweep: fingerprint the int8 variant of EVERY rung —
            # the @int8 budget twins must see quantized programs, and
            # timed acceptance would defeat a compile-free capture
            qstate.params_for(version0, live0)
            if qstate.available:
                int8_rungs = set(accepted)
                qstate.int8_rungs = int8_rungs
        else:
            int8_rungs = qstate.accept_rungs(version0, live0, accepted)
        if int8_rungs:
            # rebuild the quantized twin in the reload thread, not on the
            # first int8 dispatch after a swap
            store.on_reload = qstate.params_for

    def _example_of(rung: int):
        if qstate is not None and rung in qstate.int8_rungs:
            return policy.example(qstate.params_for(*store.current()), rung)
        return policy.example(store.current()[1], rung)

    def _step_of(rung: int):
        if qstate is not None and rung in qstate.int8_rungs:
            return qstate.step_for(qstate.params_for(*store.current()))
        return policy.step

    runners = {
        rung: plan.register(
            f"policy_b{rung}",
            _step_of(rung),
            example=(lambda r=rung: _example_of(r)),
        )
        for rung in accepted
    }
    plan.start()  # capture mode unwinds here with the ladder recorded

    def dispatch(stacked, pendings, rung):
        version, live = store.current()
        if qstate is not None and rung in qstate.int8_rungs:
            live = qstate.params_for(version, live)
        out = policy.run(runners[rung], live, version, stacked, pendings, rung)
        return out, version

    batcher = MicroBatcher(
        dispatch, accepted,
        window_ms=args.batch_window_ms,
        default_deadline_ms=args.deadline_ms,
        telem=telem,
    )
    server = ServeServer(policy, store, batcher, bind=args.bind, telem=telem)
    stop = threading.Event()
    got_signal: list[str] = []

    def _on_signal(signum, _frame):
        got_signal.append(signal.Signals(signum).name)
        stop.set()

    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, _on_signal)

    poller = None
    start_t = time.monotonic()
    try:
        address = server.start()
        with open(os.path.join(log_dir, ADDRESS_FILE), "w") as fh:
            fh.write(address + "\n")
        print(f"sheepserve: serving {args.algo} v{store.version} at {address}", flush=True)
        telem.event(
            "serve.start", address=address, algo=args.algo,
            rungs=accepted, version=store.version, ckpt=args.ckpt,
            quant=args.quant, int8_rungs=sorted(int8_rungs),
        )
        telem.add_gauges(server.gauges)
        if args.reload_poll_s > 0 and args.ckpt:
            poller = threading.Thread(
                target=_poll_reloads, args=(args, store, stop),
                name="serve-reload-poll", daemon=True,
            )
            poller.start()

        from ..resilience import inject

        telem.add_gauges(inject.gauges)

        # occupancy-driven rung resize (ISSUE 20 tentpole d): when the live
        # Serve/occupancy telemetry shows dispatches consistently padding up
        # to a rung far above their actual rows, derive the intermediate
        # batch size, size it through the SAME ledger-first decision cache
        # as the startup ladder, and splice it into the batcher (expansion
        # only — existing rungs and the max-rung contract never move). The
        # new runner is the plain jitted step (registered-on-plan runners
        # are frozen at plan.start(); the jit dispatch cache compiles the
        # extra rung at its first use).
        retier = {"added": 0, "seen": 0}

        def _maybe_retier() -> None:
            if retier["added"] >= 2:
                return  # bounded: a resize per occupancy regime, not a churn
            g = batcher.gauges()
            dispatches = int(g["Serve/dispatches"])
            if dispatches - retier["seen"] < 16:
                return  # need a fresh occupancy window, not startup noise
            retier["seen"] = dispatches
            avg_rows = g["Serve/rows_served"] / max(dispatches, 1)
            cand = ladder_mod.derive_rung(avg_rows, batcher.rungs, args.max_batch)
            if cand is None:
                return
            sized = ladder_mod.size_ladder(
                policy.step,
                lambda r: policy.example(store.current()[1], r),
                [min(batcher.rungs), cand], spec,
                store_path=os.path.join(log_dir, "serve_ladder.json"),
            )
            d = next(s for s in sized if s.rung == cand)
            retier["added"] += 1  # even a rejection consumes the attempt
            telem.event(
                "serve.retier", rung=cand, occupancy_rows=round(avg_rows, 2),
                **d.as_event(),
            )
            if not d.accepted:
                return
            runners[cand] = _step_of(cand)
            batcher.set_rungs([*batcher.rungs, cand])

        step = 0
        while not stop.is_set():
            stop.wait(0.5)
            step += 1
            if step % 16 == 0:
                # a broken resize probe must never take down a serving loop
                try:
                    _maybe_retier()
                except Exception as err:
                    telem.event(
                        "serve.retier_error",
                        error=f"{type(err).__name__}: {err}",
                    )
            # the server-crash injection site: SIGKILL, no drain — the
            # recovery under test is the CLIENT's (typed ConnectionLost +
            # reconnect/resend under idempotent ids)
            if inject.get_plan().fire_at("peer.crash", step) is not None:
                os.kill(os.getpid(), signal.SIGKILL)
            if step % 4 == 0 or stop.is_set() or args.dry_run:
                elapsed = max(time.monotonic() - start_t, 1e-6)
                # a non-empty metrics dict guarantees a parseable JSONL
                # record every interval — heartbeat cadence alone could
                # miss a short-lived smoke run entirely
                telem.interval(
                    {"Serve/uptime_seconds": elapsed},
                    step=server.completed,
                    sps=server.completed / elapsed,
                )
            if args.serve_requests >= 0 and server.completed >= args.serve_requests:
                break
            if args.dry_run:
                break
    finally:
        stop.set()
        if got_signal:
            # graceful drain: queued requests finish (zero dropped
            # in-flight), new ones are shed with reason="draining"
            server.drain()
        telem.event(
            "serve.stop",
            completed=server.completed,
            version=store.version,
            signal=got_signal[0] if got_signal else None,
        )
        server.close()
        if poller is not None:
            poller.join(timeout=2.0)
        # final gauge flush so the telemetry report sees the last state
        telem.interval(
            {"Serve/uptime_seconds": max(time.monotonic() - start_t, 1e-6)},
            step=server.completed,
            sps=0.0,
        )
        plan.close()
        telem.close()
        logger.close()
    if got_signal:
        from ..resilience import RC_PREEMPTED

        # the DISTINCT resumable rc (75, EX_TEMPFAIL): supervisors treat a
        # drained serve exit exactly like a preempted training exit
        raise SystemExit(RC_PREEMPTED)


def _poll_reloads(args: ServeArgs, store, stop: threading.Event) -> None:
    """Watch --ckpt's parent directory; hot-reload when a newer valid
    checkpoint lands. Client RELOAD frames stay available either way."""
    from ..utils.checkpoint import latest_checkpoint

    ckpt_dir = os.path.dirname(os.path.abspath(args.ckpt))
    while not stop.wait(args.reload_poll_s):
        try:
            latest = latest_checkpoint(ckpt_dir, validate=True)
        # sheeplint: disable=SL012 — a transient listing error must not
        # kill the poller; the next tick retries
        except Exception:
            continue
        if latest and os.path.abspath(latest) != os.path.abspath(store.source or ""):
            store.reload(latest)
