"""Attribute the SAC-AE XLA:CPU compile stall to a specific split jit.

Round-4 context: the fused SAC-AE update stalls XLA:CPU >25 min at pixel
sizes; `--split_update` (four per-model jits) was built to sidestep it, but
the round-4 receipt runner STILL stalled >2.5 h in its first training step
with split_update=true (batch 32 / 128 units / 64x64x9 frames). This probe
builds the exact receipt-scale state WITHOUT envs and drives the split
train_step with the do-flags enabled one at a time, timing each jit's first
call under a SIGALRM bound — so the stall is attributed to critic / ema /
actor+alpha / recon rather than "somewhere in XLA".

Round-6 extensions (ISSUE 5):

  --recon-chunk N   probe the batch-chunked reconstruction partition
                    (compile/partition.py): the plus_recon phase compiles a
                    chunk-sized executable instead of the full-batch one.
  --sweep           run the (mode x batch x width) attribution matrix, one
                    SUBPROCESS per cell (fresh process: no in-memory jit
                    cache or allocator state leaks between cells; each cell
                    SIGALRM-bounded), and print a markdown table. This is
                    the receipt that resolves the VERDICT r5 951 s-vs->2.5 h
                    discrepancy: compile cost is ~linear in batch at fixed
                    program (23 convs) and superlinear (~x^2.4) in conv
                    channels, so the same nominal config lands anywhere from
                    minutes to hours depending on batch x width x host load.

Usage: python tools/sac_ae_compile_probe.py [--budget-s 900] [--batch 32]
Prints one JSON line per phase: {"phase": ..., "seconds": ... | "TIMEOUT"}.
Every cell disables the persistent compile cache (SHEEPRL_TPU_XLA_CACHE=0)
— cold compiles are the quantity under measurement.
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# cold compiles are the measurement: a warm persistent cache would zero the
# very numbers this probe exists to attribute
os.environ["SHEEPRL_TPU_XLA_CACHE"] = "0"

import argparse
import json
import signal
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np


class PhaseTimeout(Exception):
    pass


def _alarm(_sig, _frm):
    raise PhaseTimeout


def _sweep(ns) -> None:
    """(mode x batch x width) matrix, one bounded subprocess per cell."""
    import subprocess

    # the discrepancy-resolving matrix: batch scaling at two widths (split),
    # the fused reference, and the partitioned (chunked-recon) path. Each
    # phase is timed TWICE (first call, then exec-only) so compile and
    # execution separate.
    cells = [
        ("split", 2, 4, 0),
        ("split", 4, 4, 0),
        ("split", 2, 16, 0),
        ("split", 4, 16, 0),
        ("fused", 2, 16, 0),
        ("split", 4, 16, ns.recon_chunk or 2),
    ]

    rows = []
    for mode, batch, mult, chunk in cells:
        cmd = [
            sys.executable, __file__,
            "--budget-s", str(ns.budget_s), "--batch", str(batch),
            "--hidden", str(ns.hidden), "--mult", str(mult),
        ]
        if mode == "fused":
            cmd.append("--fused")
        if chunk:
            cmd += ["--recon-chunk", str(chunk)]
        t0 = time.perf_counter()
        proc = subprocess.run(
            cmd, capture_output=True, text=True,
            timeout=ns.budget_s * 6 + 120,
        )
        phases = {}
        for line in proc.stdout.splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "phase" in rec:
                phases[rec["phase"]] = rec
        row = {
            "mode": mode, "batch": batch, "mult": mult, "chunk": chunk,
            "phases": phases, "wall_s": round(time.perf_counter() - t0, 1),
            "rc": proc.returncode,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    def cell(rec, field="seconds"):
        if rec is None:
            return "?"
        return rec.get(field, "?")

    print("\n| mode | batch | conv mult | recon chunk | recon first s | recon exec s | recon compile s | total first-call s |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        p = r["phases"]
        rec = p.get("fused_all") if r["mode"] == "fused" else p.get("plus_recon")
        firsts = [
            v["seconds"] for v in p.values() if isinstance(v.get("seconds"), (int, float))
        ]
        n_expected = 1 if r["mode"] == "fused" else 4
        total = round(sum(firsts), 1) if len(p) == n_expected else "TIMEOUT"
        print(
            f"| {r['mode']} | {r['batch']} | {r['mult']} | {r['chunk'] or '-'} "
            f"| {cell(rec)} | {cell(rec, 'exec_seconds')} "
            f"| {cell(rec, 'compile_seconds_est')} | {total} |"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget-s", type=int, default=900)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument(
        "--mult", type=int, default=16,
        help="cnn_channels_multiplier — conv width, the superlinear axis of "
        "the XLA:CPU compile cost",
    )
    ap.add_argument("--fused", action="store_true", help="probe the fused path instead")
    ap.add_argument(
        "--recon-chunk", type=int, default=0,
        help="probe the batch-chunked recon partition (0 = unchunked)",
    )
    ap.add_argument(
        "--sweep", action="store_true",
        help="run the (mode x batch x width) matrix in bounded subprocesses "
        "and print the attribution table",
    )
    ap.add_argument(
        "--compile-only", action="store_true",
        help="AOT-compile every jit of the chosen path (lower().compile(), "
        "no execution) and print per-executable compile seconds — the "
        "receipt that 'compiles to first update' is bounded at any batch; "
        "the residual first-call cost is execution",
    )
    ns = ap.parse_args()
    if ns.sweep:
        return _sweep(ns)

    from sheeprl_tpu.algos.sac_ae.args import SACAEArgs
    from sheeprl_tpu.algos.sac_ae.agent import (
        SACAEAgent,
        SACAECNNDecoder,
        SACAECNNEncoder,
        SACAEDecoder,
        SACAEEncoder,
    )
    from sheeprl_tpu.algos.sac_ae.sac_ae import (
        TrainState,
        make_optimizers,
        make_split_train_step,
        make_train_step,
    )
    from sheeprl_tpu.utils.parser import DataclassArgumentParser

    parser = DataclassArgumentParser(SACAEArgs)
    (args,) = parser.parse_args_into_dataclasses([
        "--per_rank_batch_size", str(ns.batch),
        "--actor_hidden_size", str(ns.hidden),
        "--critic_hidden_size", str(ns.hidden),
        "--dense_units", str(ns.hidden),
        "--cnn_channels_multiplier", str(ns.mult),
    ])
    args.screen_size = 64

    key = jax.random.PRNGKey(0)
    key, k_cnn, k_agent, k_dec = jax.random.split(key, 4)
    cnn_keys, mlp_keys = ("rgb",), ()
    in_channels = 9  # 3 stacked rgb frames, the receipt configuration
    cnn_encoder = SACAECNNEncoder.init(
        k_cnn, in_channels, args.features_dim, cnn_keys,
        screen_size=args.screen_size,
        cnn_channels_multiplier=args.cnn_channels_multiplier,
    )
    encoder = SACAEEncoder(cnn_encoder=cnn_encoder, mlp_encoder=None)
    cnn_decoder = SACAECNNDecoder.init(
        k_dec, cnn_encoder.conv_output_shape, encoder.output_dim,
        cnn_keys, [in_channels],
        cnn_channels_multiplier=args.cnn_channels_multiplier,
    )
    decoder = SACAEDecoder(cnn_decoder=cnn_decoder, mlp_decoder=None)
    agent = SACAEAgent.init(
        k_agent, encoder, 1,
        num_critics=args.num_critics,
        actor_hidden_size=args.actor_hidden_size,
        critic_hidden_size=args.critic_hidden_size,
        action_low=np.array([-1.0]), action_high=np.array([1.0]),
        alpha=args.alpha, tau=args.tau, encoder_tau=args.encoder_tau,
    )
    optimizers = make_optimizers(args)
    qf_optim, actor_optim, alpha_optim, encoder_optim, decoder_optim = optimizers
    state = TrainState(
        agent=agent, decoder=decoder,
        qf_opt=qf_optim.init(agent.critic),
        actor_opt=actor_optim.init(agent.actor),
        alpha_opt=alpha_optim.init(agent.log_alpha),
        encoder_opt=encoder_optim.init(agent.critic.encoder),
        decoder_opt=decoder_optim.init(decoder),
    )

    b = ns.batch
    rng = np.random.default_rng(0)
    batch = {
        "rgb": jnp.asarray(rng.integers(0, 255, (1, b, 64, 64, 9), dtype=np.uint8)),
        "next_rgb": jnp.asarray(rng.integers(0, 255, (1, b, 64, 64, 9), dtype=np.uint8)),
        "actions": jnp.asarray(rng.normal(size=(1, b, 1)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(1, b, 1)).astype(np.float32)),
        "dones": jnp.zeros((1, b, 1), jnp.float32),
    }

    if ns.fused:
        train_step = make_train_step(args, optimizers, cnn_keys, mlp_keys)
    else:
        train_step = make_split_train_step(
            args, optimizers, cnn_keys, mlp_keys, recon_chunk=ns.recon_chunk
        )
    signal.signal(signal.SIGALRM, _alarm)

    if ns.compile_only:
        import jax as _jax

        b = ns.batch
        bspec = {
            "rgb": _jax.ShapeDtypeStruct((b, 64, 64, 9), jnp.uint8),
            "next_rgb": _jax.ShapeDtypeStruct((b, 64, 64, 9), jnp.uint8),
            "actions": _jax.ShapeDtypeStruct((b, 1), jnp.float32),
            "rewards": _jax.ShapeDtypeStruct((b, 1), jnp.float32),
            "dones": _jax.ShapeDtypeStruct((b, 1), jnp.float32),
        }
        c = ns.recon_chunk or b
        cspec = {k: _jax.ShapeDtypeStruct((c,) + v.shape[1:], v.dtype)
                 for k, v in bspec.items() if k in ("rgb",)}
        nspec = {"rgb": _jax.ShapeDtypeStruct((c, 64, 64, 9), jnp.float32)}
        if ns.fused:
            jobs = {"fused_train_step": (train_step, (
                state, {k: _jax.ShapeDtypeStruct((1,) + v.shape, v.dtype)
                        for k, v in bspec.items()},
                key, True, True, True))}
        else:
            jits = train_step.jits
            jobs = {
                "critic_step": (jits["critic"], (agent, state.qf_opt, bspec, key)),
                "ema_step": (jits["ema"], (agent,)),
                "actor_alpha_step": (jits["actor_alpha"], (
                    agent, state.actor_opt, state.alpha_opt, bspec, key)),
            }
            if ns.recon_chunk:
                jobs["recon_grads_step"] = (jits["recon_grads"], (
                    agent.critic.encoder, decoder, cspec, nspec))
                jobs["recon_apply_step"] = (jits["recon_apply"], (
                    agent, decoder, state.encoder_opt, state.decoder_opt,
                    agent.critic.encoder, decoder))
            else:
                jobs["recon_step"] = (jits["recon"], (
                    agent, decoder, state.encoder_opt, state.decoder_opt,
                    bspec, key))
        total = 0.0
        for name, (fn, ex) in jobs.items():
            from sheeprl_tpu.compile import avals_of

            t0 = time.perf_counter()
            signal.alarm(ns.budget_s)
            try:
                fn.lower(*avals_of(ex)).compile()
                signal.alarm(0)
                dt = round(time.perf_counter() - t0, 2)
                total += dt
                print(json.dumps({"jit": name, "compile_seconds": dt}), flush=True)
            except PhaseTimeout:
                print(json.dumps({"jit": name, "compile_seconds": "TIMEOUT",
                                  "budget_s": ns.budget_s}), flush=True)
                return
        print(json.dumps({"jit": "TOTAL", "compile_seconds": round(total, 2),
                          "batch": b, "mult": ns.mult,
                          "recon_chunk": ns.recon_chunk}), flush=True)
        return

    if ns.fused:
        phases = [("fused_all", (True, True, True))]
    else:
        phases = [
            ("critic_only", (False, False, False)),
            ("plus_ema", (True, False, False)),
            ("plus_actor_alpha", (True, True, False)),
            ("plus_recon", (True, True, True)),
        ]
    for name, (do_ema, do_actor, do_decoder) in phases:
        key, k = jax.random.split(key)
        t0 = time.perf_counter()
        signal.alarm(ns.budget_s)
        try:
            out_state, metrics = train_step(state, batch, k, do_ema, do_actor, do_decoder)
            jax.block_until_ready(metrics)
            signal.alarm(0)
            dt = round(time.perf_counter() - t0, 1)
            state = out_state
            # SECOND call at identical shapes: pure execution (the dispatch
            # cache serves the executable). first - exec ~= compile. This
            # split is the round-6 extension that resolved the r5 "951 s
            # compile" attribution: the scaling cost is execution.
            key, k2 = jax.random.split(key)
            t1 = time.perf_counter()
            signal.alarm(ns.budget_s)
            out_state, metrics = train_step(
                state, batch, k2, do_ema, do_actor, do_decoder
            )
            jax.block_until_ready(metrics)
            signal.alarm(0)
            exec_s = round(time.perf_counter() - t1, 1)
            print(json.dumps({
                "phase": name, "seconds": dt, "exec_seconds": exec_s,
                "compile_seconds_est": round(max(dt - exec_s, 0.0), 1),
            }), flush=True)
            state = out_state
        except PhaseTimeout:
            print(json.dumps({"phase": name, "seconds": "TIMEOUT",
                              "budget_s": ns.budget_s}), flush=True)
            break


if __name__ == "__main__":
    main()
