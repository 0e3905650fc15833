"""sheepquant serve tier: calibration, quality-receipt rung acceptance,
and quantized dispatch (`--quant int8`).

The int8 ladder rides the existing serve machinery end to end:

  - `ops/quant.py` calibrates per-channel activation scales on seeded
    held-out state batches (or loads the `quant_scales.npz` persisted next
    to the checkpoint by a previous run / the training-side
    `calibrate_from_buffer` pass) and swaps the policy pytree's `Linear`s
    for `QuantLinear`s — the surrounding SACActor / PlayerDV3 keeps its
    class, so the policies' jitted `step` functions serve quantized params
    unchanged;
  - each accepted ladder rung is then trial-compiled and exec-timed
    through `compile/decisions.py` under the NEW bounded-divergence
    acceptance: the int8 variant wins a rung only when it is faster AND
    its max action divergence on the held-out set stays within
    `--quant_bound`; past the bound it is DISQUALIFIED exactly like a
    non-bit-exact remat rung, and that rung keeps serving f32 — the
    ladder can be MIXED per rung;
  - the SAC trunk additionally dispatches through the fused Pallas int8
    kernel (`ops/pallas_kernels.fused_int8_trunk`) behind
    `use_pallas("sac_trunk")` when the trunk structure matches (two
    biased relu QuantLinears, no norms, QuantLinear mean head) — the
    kernel shares its math function with the generic QuantLinear path,
    so the receipt measured on either holds for both.

A hot reload re-derives scales for the new params version eagerly in the
reload thread (the ParamsStore `on_reload` hook — `Serve/quant_rederives`
counts these), so the dispatch path never pays a calibration; if the hook
fails, the first dispatch that needs the int8 rung rebuilds lazily.
Version N's quantized params keep serving until the rebuild lands — the
ParamsStore double-buffering contract extends to the quantized twins.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable

import numpy as np

__all__ = ["QuantState", "action_divergence"]

QUANT_MODES = ("off", "int8")

_CALIB_BATCHES = 4
_CALIB_ROWS = 64
_HELD_OUT_SEED_OFFSET = 1  # held-out receipt set never reuses calibration draws


def action_divergence(a: Any, b: Any) -> float:
    """Quality metric for `decide`: max elementwise |delta| over all float
    leaves of the two step outputs (actions for SAC; actions + recurrent
    state for DV3 — a state divergence compounds, so it counts too)."""
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    worst = 0.0
    for x, y in zip(la, lb):
        xa = np.asarray(x, dtype=np.float64)
        ya = np.asarray(y, dtype=np.float64)
        if xa.size:
            worst = max(worst, float(np.max(np.abs(xa - ya))))
    return worst


def _synth_obs(space, rng: np.random.Generator, rows: int) -> np.ndarray:
    """Seeded synthetic observations matching a gym space: uniform bytes
    for image spaces, unit normals for float vectors."""
    shape = (rows,) + tuple(space.shape)
    dt = np.dtype(space.dtype)
    if dt == np.uint8:
        return rng.integers(0, 256, size=shape, dtype=np.uint8)
    return rng.standard_normal(shape).astype(dt)


class QuantState:
    """Everything `--quant int8` adds to a serve process: scale
    derivation/persistence, per-version quantized params, per-rung
    quality-receipt decisions, and the `Serve/quant_*` gauges."""

    def __init__(self, policy, args, log_dir: str, telem: Any = None):
        self.policy = policy
        self.bound = float(args.quant_bound)
        self.telem = telem
        self.seed = int(getattr(args, "seed", 0) or 0)
        self.ckpt = args.ckpt
        self.store_path = os.path.join(log_dir, "serve_quant.json")
        self.available = True  # flips off when calibration cannot run
        self.int8_rungs: set[int] = set()
        self.rederives = 0
        self.decisions: dict[int, Any] = {}
        self._cache: tuple[int, Any] | None = None  # (version, qparams)
        # the reload hook and an int8 dispatch can race to derive the same
        # version; serialize so only one pays the calibration
        self._derive_lock = threading.Lock()
        self._step_int8: Callable | None = None
        self._fused = False

    # ---- calibration + quantization ---------------------------------------
    def _calib_inputs(self, version: int, params, rows: int, seed: int):
        """One seeded batch of step inputs (minus params): SAC takes a bare
        obs matrix, DV3 takes (state rows, obs dict)."""
        rng = np.random.default_rng(seed)
        if self.policy.algo == "sac":
            return (
                rng.standard_normal((rows, self.policy.obs_dim)).astype(np.float32),
            )
        row = self.policy._init_row(version, params)
        state = {k: np.repeat(v[None], rows, axis=0) for k, v in row.items()}
        obs = {
            k: _synth_obs(self.policy.obs_space[k], rng, rows)
            for k in self.policy.obs_keys
        }
        return (state, obs)

    def _calibrate(self, version: int, params) -> dict[str, np.ndarray]:
        from ..ops import quant as q

        if self.policy.algo == "sac":
            import jax.numpy as jnp

            call = lambda m, obs: m.get_greedy_actions(  # noqa: E731
                jnp.asarray(obs, jnp.float32)
            )
            batches = [
                self._calib_inputs(version, params, _CALIB_ROWS, self.seed + i)[0]
                for i in range(_CALIB_BATCHES)
            ]
        else:
            call = lambda m, b: self.policy.step(m, b[0], b[1])  # noqa: E731
            batches = [
                self._calib_inputs(version, params, _CALIB_ROWS, self.seed + i)
                for i in range(_CALIB_BATCHES)
            ]
        return q.calibrate(params, call, batches)

    def _scales_for(self, version: int, params) -> dict[str, np.ndarray] | None:
        """Persisted scales for the first version when available, freshly
        derived (and persisted, when serving a checkpoint) otherwise."""
        from ..ops import quant as q

        persisted = None
        if self.ckpt and version <= 1:
            persisted = q.load_scales(q.scales_path(self.ckpt))
        if persisted:
            self._event("serve.quant_scales", source="persisted", version=version)
            return persisted
        try:
            scales = self._calibrate(version, params)
        except Exception as err:
            self._event(
                "serve.quant_scales", source="error", version=version,
                error=f"{type(err).__name__}: {err}"[:200],
            )
            return None
        if not scales:
            return None
        if self.ckpt:
            try:
                q.save_scales(q.scales_path(self.ckpt), scales)
            except OSError:
                pass  # persistence is an optimization, never fatal
        self._event(
            "serve.quant_scales", source="calibrated", version=version,
            linears=len(scales),
        )
        return scales

    def params_for(self, version: int, params):
        """The quantized twin of `params`, cached per version. A version
        bump (hot reload) re-derives scales and re-quantizes — the swap
        changed the weights, so the old scales no longer describe the
        activations."""
        from ..ops import quant as q

        if self._cache is not None and self._cache[0] == version:
            return self._cache[1]
        with self._derive_lock:
            if self._cache is not None and self._cache[0] == version:
                return self._cache[1]
            if self._cache is not None:
                self.rederives += 1
            scales = self._scales_for(version, params)
            if scales is None:
                self.available = False
                return params
            qparams = q.quantize_linears(params, scales)
            self._cache = (version, qparams)
            return qparams

    # ---- the int8 step (fused kernel when the trunk matches) ---------------
    def step_for(self, qparams) -> Callable:
        """The jitted step the int8 rungs register and dispatch through:
        the fused Pallas SAC trunk when structure + gate allow, else the
        policy's own step (QuantLinear math through the generic path)."""
        if self._step_int8 is not None:
            return self._step_int8
        self._fused = _sac_fused_ready(self.policy, qparams)
        if self._fused:
            self._step_int8 = _make_fused_sac_step()
        else:
            self._step_int8 = self.policy.step
        return self._step_int8

    # ---- per-rung quality-receipt acceptance -------------------------------
    def accept_rungs(self, version: int, params, rungs: list[int]) -> set[int]:
        """Run the bounded-divergence ladder for every accepted serve rung:
        candidates [f32, int8] timed through `compile/decisions.decide`
        with max action divergence on the held-out set as the quality
        metric. Returns the rungs where int8 won; the decision records
        (receipts) land in `serve_quant.json` and `self.decisions`."""
        from ..compile import decisions as dec

        qparams = self.params_for(version, params)
        if not self.available:
            return set()
        step_f32 = self.policy.step
        step_int8 = self.step_for(qparams)
        won: set[int] = set()
        for rung in rungs:
            # the held-out calibration states ARE the receipt set: both
            # candidates run on them, so the measured divergence is the
            # committed quality receipt
            example = self._calib_inputs(
                version, params, rung, self.seed + _HELD_OUT_SEED_OFFSET
            )

            def build(label, _p=params, _q=qparams):
                if label == "int8":
                    return lambda *a: step_int8(_q, *a)
                return lambda *a: step_f32(_p, *a)

            d = dec.decide(
                "serve_quant",
                # the bound is part of the name: a tight-bound re-run
                # must re-measure, never inherit a loose-bound winner
                f"policy_b{rung}@{self.bound:g}",
                ["f32", "int8"],
                build,
                example,
                objective="seconds",
                quality_metric=action_divergence,
                quality_bound=self.bound,
                store_path=self.store_path,
            )
            rep = d.candidate("int8")
            if rep.get("error"):
                # losing on time or on the quality bound keeps the rung on
                # f32 by design; an int8 program that cannot compile or run
                # is a defect `--quant int8` must not paper over
                self._event(
                    "serve.quant_rung", rung=rung, accepted=False,
                    fused=self._fused, error=rep["error"],
                )
                raise RuntimeError(
                    f"--quant int8: the int8 candidate of rung {rung} failed "
                    f"to compile or run (fused={self._fused}): {rep['error']}"
                )
            self.decisions[rung] = d
            if d.winner == "int8":
                won.add(rung)
            self._event(
                "serve.quant_rung", rung=rung, accepted=d.winner == "int8",
                divergence=rep.get("divergence"), bound=self.bound,
                within_bound=rep.get("within_bound"), fused=self._fused,
                source=d.source,
            )
        self.int8_rungs = won
        return won

    # ---- observability -----------------------------------------------------
    def gauges(self) -> dict[str, float]:
        worst = 0.0
        for rung in self.int8_rungs:
            d = self.decisions.get(rung)
            if d is not None:
                div = d.candidate("int8").get("divergence")
                if div is not None:
                    worst = max(worst, float(div))
        return {
            "Serve/quant_enabled": 1.0 if self.available else 0.0,
            "Serve/quant_rungs": float(len(self.int8_rungs)),
            "Serve/quant_bound": self.bound,
            "Serve/quant_divergence_max": worst,
            "Serve/quant_rederives": float(self.rederives),
            "Serve/quant_fused": 1.0 if self._fused else 0.0,
        }

    def _event(self, name: str, **data: Any) -> None:
        if self.telem is not None:
            try:
                self.telem.event(name, **data)
            # sheeplint: disable=SL012 — telemetry must not break serving
            except Exception:
                pass


# ---------------------------------------------------------------------------
# fused SAC trunk dispatch
# ---------------------------------------------------------------------------


def _sac_fused_ready(policy, actor) -> bool:
    """Structural guard for the fused kernel (the fused_rssm dispatch
    pattern): SAC, gate on, a 2-layer biased relu trunk with no norms and
    no MLP head, every trunk weight quantized, and the whole quantized
    weight set within the kernel's VMEM budget. The decision is recorded
    (`kernel.select`, family sac_trunk)."""
    from ..ops import pallas_kernels as pk
    from ..ops.quant import QuantLinear

    if getattr(policy, "algo", None) != "sac":
        return False  # another policy family: the kernel is not a candidate
    if not pk.use_pallas("sac_trunk"):
        return False
    model = getattr(actor, "model", None)
    fc_mean = getattr(actor, "fc_mean", None)
    parts = [] if model is None else [*model.layers, fc_mean]
    fits = (
        model is not None
        and fc_mean is not None
        and model.act == "relu"
        and model.head is None
        and len(model.layers) == 2
        and all(n is None for n in model.norms)
        and all(isinstance(p, QuantLinear) and p.bias is not None for p in parts)
    )
    if not fits:
        return pk.select("sac_trunk", False)
    weights = [a for p in parts for a in (p.w_q, p.w_scale, p.in_scale, p.bias)]
    return pk.fused_int8_trunk_supported(*weights)


def _make_fused_sac_step() -> Callable:
    """The fused-kernel twin of `SACServePolicy.step`: same signature
    (actor, obs) -> actions, same pre-cast through the trunk's compute
    dtype, same f32 tanh squash outside the kernel — only the trunk math
    runs through `fused_int8_trunk` instead of three staged matmuls."""
    import jax
    import jax.numpy as jnp

    from ..ops import pallas_kernels as pk

    def step(actor, obs):
        dt = jnp.dtype(actor.compute_dtype)
        x = obs.astype(dt).astype(jnp.float32)
        l0, l1, m = actor.model.layers[0], actor.model.layers[1], actor.fc_mean
        mean = pk.fused_int8_trunk(
            x,
            l0.in_scale, l0.w_q, l0.w_scale, l0.bias,
            l1.in_scale, l1.w_q, l1.w_scale, l1.bias,
            m.in_scale, m.w_q, m.w_scale, m.bias,
        )
        scale = jax.lax.stop_gradient(actor.action_scale)
        bias = jax.lax.stop_gradient(actor.action_bias)
        return jnp.tanh(mean) * scale + bias

    return jax.jit(step)
