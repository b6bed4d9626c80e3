"""The unified fit engine: ONE optimization driver for every backend.

`fit_loop` owns, once for every backend:

  * the backtracking line search (core/linesearch semantics, including the
    adaptive-grow trial step and the max-rel-move trust cap),
  * convergence tests — raw relative energy decrease for deterministic
    objectives, an exponential-moving-average test for stochastic ones
    (a raw test would fire on sampling noise),
  * checkpoint/resume (the payload carries X plus the line-search and
    direction-solver state, so a resumed run replays the uninterrupted
    trajectory bit-for-bit; per-iteration fold_in keys make the stochastic
    surrogate exactly reproducible too),
  * callbacks and wall-clock/feval traces.

Backends implement the `Objective` protocol (docs/engine.md):

    energy_and_grad(X, key) -> (E, G)     key is None for deterministic
    energy(X, key)          -> E          line-search fast path
    make_direction_solver() -> (solve, state0)
                               solve(state, X, G) -> (P, state)

and may additionally provide

    stochastic: bool        EMA convergence + per-iteration PRNG keys
    diagnostics()           host-side dict of solver diagnostics from the
                            LAST step (e.g. PCG iteration count/residual,
                            streaming-Z EMA) — how per-iteration solver
                            state gets out of jitted steps and into the
                            telemetry records / diagnostics table; only
                            called when someone is listening (telemetry,
                            on_iteration, or a diagnostics-aware callback)
    make_fused_step()       a single jitted (X, E, G, state, alpha) ->
                            (X, E, G, state, alpha, n_evals, scalars)
                            program that replaces the whole direction/
                            line-search/update sequence; `scalars` packs
                            [E, |G|, alpha, n_evals] in E's dtype, so the
                            host reads an iteration in one transfer — how
                            the dense backend (core/minimize.py) runs one
                            XLA program per iteration
    place(X)                device placement for X-like arrays (e.g.
                            replicate on a mesh); used on checkpoint restore
    carry_state()           objective-side state to checkpoint (a pytree,
                            e.g. the sparse normalized models' streaming
                            partition-function estimate); saved with every
                            checkpoint and re-installed on resume via
    restore_carry(tree)     AFTER the engine's initial energy/grad call, so
                            the first post-resume iteration sees exactly
                            the state the uninterrupted run would have

Current backends (docs/engine.md): dense single-device
(core/minimize.py `DenseObjective`, fused step), and from
embed/trainer.py the dense 2-D-sharded block-Jacobi, the sparse
single-device, the row-sharded sparse (sparse/sharding.py) and the
Barnes-Hut tree (sparse/farfield.py) objectives.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import Checkpointer
from repro.core.linesearch import LSConfig
from repro.obs import IterationRecord, device_memory_stats, span

Array = jnp.ndarray


@runtime_checkable
class Objective(Protocol):
    """Duck-typed; see the module docstring for optional members."""

    def energy_and_grad(self, X: Array, key) -> tuple[Array, Array]: ...

    def energy(self, X: Array, key) -> Array: ...

    def make_direction_solver(self): ...


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    max_iters: int = 200
    tol: float = 1e-7
    ls: LSConfig = LSConfig(init_step="adaptive_grow")
    convergence: str = "auto"    # 'raw' | 'ema' | 'auto' (ema iff stochastic)
    ema_decay: float = 0.9
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    seed: int = 0
    max_seconds: float | None = None


@dataclasses.dataclass
class EngineResult:
    X: Array
    energies: np.ndarray      # E_k, k = 0..n_iters (includes E_0)
    grad_norms: np.ndarray
    step_sizes: np.ndarray
    times: np.ndarray         # cumulative wall-clock seconds at each iterate
    n_fevals: np.ndarray      # cumulative energy evaluations
    n_iters: int
    converged: bool
    setup_time: float         # direction-solver init (e.g. Cholesky)
    resumed_from: int | None
    state: Any = None         # final direction-solver state
    loop_transfers: int = 0   # arrays the loop copied device->host: one
                              # per iteration on the fused path
    diagnostics: list[dict] | None = None   # per-iteration table (only
                                            # collected when someone asked:
                                            # telemetry / on_iteration /
                                            # diagnostics-aware callback)


def initial_step(X, P, alpha_prev: float, ls: LSConfig) -> float:
    """Adaptive-grow initial trial step with the max-rel-move trust cap —
    host-side mirror of the policy inside the jitted fused step."""
    alpha0 = min(alpha_prev / ls.rho, 1.0)
    if ls.max_rel_move is not None:
        xc = X - jnp.mean(X, axis=0, keepdims=True)
        # one batched transfer for both scalars (RPR001)
        scale_d, p_rms_d = jax.device_get(
            (jnp.sqrt(jnp.mean(xc * xc)), jnp.sqrt(jnp.mean(P * P))))
        scale = float(scale_d) + 1e-3
        p_rms = float(p_rms_d) + 1e-30
        alpha0 = min(alpha0, ls.max_rel_move * scale / p_rms)
    return alpha0


def host_backtrack(energy_of, X, e0: float, G, P, alpha0: float,
                   ls: LSConfig) -> tuple[float, float, int]:
    """Armijo backtracking with host-side floats (one energy eval per
    trial).  Returns the accepted (alpha, E(X + alpha P), n_evals) — the
    energy is always evaluated AT the accepted alpha, including on
    backtrack exhaustion (where alpha shrinks once more after the last
    failed trial)."""
    gtp = float(jnp.vdot(G, P))
    alpha = alpha0
    n_evals = 0
    for _ in range(ls.max_backtracks):
        e_new = energy_of(X + alpha * P)
        n_evals += 1
        if e_new <= e0 + ls.c1 * alpha * gtp:
            break
        alpha *= ls.rho
    else:
        e_new = energy_of(X + alpha * P)
        n_evals += 1
    return alpha, e_new, n_evals


def _place(objective, X):
    place = getattr(objective, "place", None)
    return place(X) if place is not None else X


def _callback_wants_diagnostics(callback) -> bool:
    """True when `callback` accepts a 4th positional argument (or *args),
    i.e. is the `callback(it, X, e, diagnostics)` form.  Unintrospectable
    callables are treated as the 3-arg form."""
    try:
        sig = inspect.signature(callback)
    except (TypeError, ValueError):
        return False
    n_pos = 0
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n_pos += 1
        elif p.kind == p.VAR_POSITIONAL:
            return True
    return n_pos >= 4


def fit_loop(
    objective: Objective,
    X0: Array,
    cfg: LoopConfig = LoopConfig(),
    callback: Callable[..., None] | None = None,
    *,
    on_iteration: Callable[[int, Array, dict], None] | None = None,
    telemetry=None,
) -> EngineResult:
    """Run the unified optimization loop to convergence or budget.

    Stops on relative (raw or EMA) energy decrease < tol, on max_iters, or
    on max_seconds of wall-clock (the paper's fixed-budget comparisons).

    `callback` takes one of two forms, both supported.  The 4-arg
    `callback(it, X, e, diagnostics)` receives the per-iteration
    diagnostics dict (engine fields + whatever `objective.diagnostics()`
    lifts out of the jitted step), as does the
    `on_iteration(it, X, diagnostics)` hook.  The 3-arg
    `callback(it, X, e)` receives no diagnostics: when it is the only
    listener the engine never calls `objective.diagnostics()`, so the
    callback adds no device->host read per iteration (the benchmark's
    timed windows rely on this).  `telemetry` is a
    `repro.obs.Telemetry`: its recorder gets one typed record per
    iteration (JSONL when configured) and the engine's spans land on its
    tracer: `setup`, `compile`, per iteration `solve-iter` (inside it
    `step` + `fetch` on the fused path, `direction` + `line-search` +
    `grad` on the host path) then `iter-host` (records, callbacks,
    convergence test), and `checkpoint` (docs/observability.md).  The
    recorder's meta gets `loop_transfers`, the count of arrays the loop
    copied device->host (`EngineResult.loop_transfers`).
    """
    cb_wants_diag = (callback is not None
                     and _callback_wants_diagnostics(callback))
    if telemetry is not None:
        with telemetry.activate():
            return _fit_loop(objective, X0, cfg, callback, cb_wants_diag,
                             on_iteration, telemetry)
    return _fit_loop(objective, X0, cfg, callback, cb_wants_diag,
                     on_iteration, None)


def _fit_loop(objective, X0, cfg, callback, cb_wants_diag, on_iteration,
              telemetry) -> EngineResult:
    stochastic = bool(getattr(objective, "stochastic", False))
    conv = cfg.convergence
    if conv == "auto":
        conv = "ema" if stochastic else "raw"
    if conv not in ("raw", "ema"):
        raise ValueError(f"unknown convergence mode {conv!r}")

    recorder = telemetry.recorder if telemetry is not None else None
    want_diag = (recorder is not None or cb_wants_diag
                 or on_iteration is not None)
    obj_diag = getattr(objective, "diagnostics", None)
    record_memory = recorder is not None and recorder.record_memory

    t0 = time.perf_counter()
    with span("setup", phase=True):
        solve, state = objective.make_direction_solver()
        state = jax.block_until_ready(state)
    setup_time = time.perf_counter() - t0

    make_fused = getattr(objective, "make_fused_step", None)
    fused_step = make_fused() if make_fused is not None else None

    X = X0
    # the fused step threads alpha as a device scalar; the host path as a
    # python float — keep both so each backend sees its native type
    alpha_dev = jnp.asarray(1.0, dtype=X0.dtype)
    alpha_host = 1.0

    carry = getattr(objective, "carry_state", None)

    ckpt = (Checkpointer(cfg.checkpoint_dir) if cfg.checkpoint_dir else None)
    start_it, resumed_from = 0, None
    ema = None
    obj_carry = None
    saved_eg = None
    if ckpt is not None:
        latest = ckpt.latest_step()
        if latest is not None:
            template = {"X": X, "alpha": np.zeros(()), "ema": np.zeros(()),
                        "state": state}
            if carry is not None:
                template["obj"] = carry()
            try:
                payload = ckpt.restore(
                    latest, {**template, "E": np.zeros(()), "G": X})
            except ValueError:
                try:
                    # pre-(E, G) payloads: resume re-evaluates at X
                    payload = ckpt.restore(latest, template)
                except ValueError:
                    # pre-engine checkpoints stored a bare X: resume from
                    # it with fresh line-search/solver state
                    payload = {"X": ckpt.restore(latest, X), "alpha": 1.0,
                               "ema": None, "state": state}
            X = _place(objective, jnp.asarray(payload["X"]))
            alpha_host = float(payload["alpha"])
            alpha_dev = jnp.asarray(alpha_host, dtype=X0.dtype)
            ema = (float(payload["ema"])
                   if payload["ema"] is not None else None)
            state = payload["state"]
            obj_carry = payload.get("obj")
            if "E" in payload and not stochastic:
                saved_eg = (payload["E"], payload["G"])
            start_it, resumed_from = latest, latest

    key0 = jax.random.PRNGKey(cfg.seed + 1) if stochastic else None
    key = jax.random.fold_in(key0, start_it) if stochastic else None
    if saved_eg is not None:
        # deterministic resume: reuse the checkpointed (E, G) rather than
        # re-evaluating — the fused-step backends produce (E, G) through a
        # differently-fused XLA program than a standalone energy_and_grad,
        # and bit-identical resume requires feeding iteration start_it + 1
        # exactly the values the uninterrupted run computed
        E = jnp.asarray(float(saved_eg[0]), X0.dtype)
        G = _place(objective, jnp.asarray(saved_eg[1]))
    else:
        # the first energy/grad call traces + compiles the backend's XLA
        # program(s) — this span IS the compile phase of the run
        with span("compile", phase=True):
            E, G = jax.block_until_ready(objective.energy_and_grad(X, key))
    if obj_carry is not None:
        # re-install the checkpointed objective state AFTER the initial
        # energy/grad call (which may have advanced it), so iteration
        # start_it + 1 sees exactly what the uninterrupted run saw
        objective.restore_carry(obj_carry)

    # one batched transfer for the pre-loop scalars instead of three
    # separate implicit syncs (RPR001) — same values, bit-identical
    e_host, g_host = (float(v) for v in
                      jax.device_get((E, jnp.linalg.norm(G))))
    energies = [e_host]
    gnorms = [g_host]
    steps: list[float] = []
    times = [0.0]
    fevals = [1]
    if ema is None:
        ema = e_host
    if recorder is not None:
        recorder.set_meta(start_it=start_it, resumed_from=resumed_from,
                          stochastic=stochastic, max_iters=cfg.max_iters,
                          e0=e_host)

    def save(step):
        if ckpt is not None:
            payload = {
                "X": X,
                "alpha": np.asarray(alpha_host, np.float64),
                "ema": np.asarray(ema, np.float64),
                "state": state,
                # current (E, G) so a deterministic resume replays the
                # uninterrupted trajectory bit-for-bit without re-fusing
                "E": np.asarray(energies[-1], np.float64),
                "G": np.asarray(G),
            }
            if carry is not None:
                payload["obj"] = carry()
            with span("checkpoint", it=step):
                ckpt.save(step, payload)

    converged = False
    diags: list[dict] = []
    transfers = 0
    # the host path's own transfers besides its gradient fetches:
    # initial_step's two scalars and host_backtrack's <G, P>
    ls_transfers = 1 + (2 if cfg.ls.max_rel_move is not None else 0)
    t_loop = time.perf_counter()
    it = start_it
    for it in range(start_it + 1, cfg.max_iters + 1):
        with span("solve-iter", it=it):
            if fused_step is not None:
                with span("step"):
                    X, E_new, G, state, alpha_dev, _, scalars = \
                        fused_step(X, E, G, state, alpha_dev)
                # the iteration's one wait and one transfer (RPR001):
                # energy, |G|, accepted step, n_evals
                with span("fetch"):
                    e_rec, g_host, alpha_host, n_ev = \
                        jax.device_get(scalars).tolist()
                n_ev = int(n_ev)
                transfers += 1
            else:
                n_ev = 0
                if stochastic:
                    # one PRNG key per iteration: the line search descends
                    # a deterministic surrogate (common random numbers)
                    key = jax.random.fold_in(key0, it)
                    with span("grad"):
                        E, G = objective.energy_and_grad(X, key)
                        # E is e0 for the backtrack below; batch it with
                        # |G| in one transfer (RPR001)
                        e_host, g_host = (
                            float(v) for v in
                            jax.device_get((E, jnp.linalg.norm(G))))
                    n_ev += 1
                    transfers += 2
                else:
                    # deterministic: E is unchanged since its transfer
                    # last iteration (or pre-loop) — reuse the host copy
                    e_host = energies[-1]
                # the host waits for P in initial_step's transfer
                with span("direction"):
                    P, state = solve(state, X, G)
                    alpha0 = initial_step(X, P, alpha_host, cfg.ls)
                with span("line-search"):
                    alpha_host, e_new, n_bt = host_backtrack(
                        lambda Xn: float(objective.energy(Xn, key)),
                        X, e_host, G, P, alpha0, cfg.ls)
                n_ev += n_bt
                transfers += ls_transfers + n_bt
                X = X + alpha_host * P
                if stochastic:
                    e_rec = e_new  # this iteration's surrogate, accepted X
                else:
                    with span("grad"):
                        E, G = objective.energy_and_grad(X, key)
                        e_rec, g_host = (
                            float(v) for v in
                            jax.device_get((E, jnp.linalg.norm(G))))
                    n_ev += 1
                    transfers += 2
        with span("iter-host", it=it):
            now = time.perf_counter() - t_loop
            energies.append(e_rec)
            gnorms.append(g_host)
            steps.append(alpha_host)
            times.append(now)
            fevals.append(fevals[-1] + n_ev)
            diag = None
            if want_diag:
                extras = dict(obj_diag()) if obj_diag is not None else {}
                if record_memory:
                    extras.update(device_memory_stats())
                diag = {"it": it, "energy": e_rec, "grad_norm": gnorms[-1],
                        "alpha": alpha_host, "n_evals": n_ev, "t": now,
                        "iter_s": now - times[-2], **extras}
                diags.append(diag)
                if recorder is not None:
                    recorder.record(IterationRecord(
                        it=it, energy=e_rec, grad_norm=gnorms[-1],
                        alpha=alpha_host, n_evals=n_ev, t=now,
                        iter_s=now - times[-2], extras=extras))
            if callback is not None:
                if cb_wants_diag:
                    callback(it, X, e_rec, diag)
                else:
                    callback(it, X, e_rec)
            if on_iteration is not None:
                on_iteration(it, X, diag)
            if conv == "ema":
                ema_new = cfg.ema_decay * ema + (1.0 - cfg.ema_decay) * e_rec
                rel = abs(ema - ema_new) / max(abs(ema_new), 1e-30)
                ema = ema_new
            else:
                rel = abs(energies[-2] - e_rec) / max(abs(e_rec), 1e-30)
        if ckpt is not None and it % cfg.checkpoint_every == 0:
            save(it)
        if rel < cfg.tol:
            converged = True
            break
        if fused_step is not None:
            E = E_new
        if cfg.max_seconds is not None and now > cfg.max_seconds:
            break
    save(it)
    if recorder is not None:
        recorder.set_meta(loop_transfers=transfers)

    return EngineResult(
        X=X,
        energies=np.asarray(energies),
        grad_norms=np.asarray(gnorms),
        step_sizes=np.asarray(steps),
        times=np.asarray(times),
        n_fevals=np.asarray(fevals),
        n_iters=it - start_it,
        converged=converged,
        setup_time=setup_time,
        resumed_from=resumed_from,
        state=state,
        diagnostics=diags if want_diag else None,
        loop_transfers=transfers,
    )
