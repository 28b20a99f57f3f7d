"""ParaTAA (Algorithm 1): parallel sampling of diffusion models with
Triangular Anderson Acceleration — as a RESUMABLE stepwise solver.

One driver covers FP / FP+ / AA / AA+ / TAA via `mode` + `order_k`:
  * FP  (Shih et al. 2023)  : mode="fp",  order_k = window size
  * FP+ (paper)             : mode="fp",  order_k tuned
  * ParaTAA (paper)         : mode="taa", order_k & history_m tuned
  * mode="seq"              : the eq. (6) sequential reference expressed as
                              a stepwise state (one timestep per iteration),
                              so serving can chunk/retire it like a solver

Each solver iteration evaluates eps_theta at `window` timesteps in ONE
batched call — that batch is the parallel axis that gets sharded over the
mesh (window folds into the denoiser's batch dim; see repro.launch.serve).

The fixed-point formulation makes sampling interruptible: the whole loop
carry is an explicit :class:`SolverState` pytree, built by ``init_state``
and advanced by ``step_chunk(state, K)`` — K guarded iterations per call,
finished lanes no-op — so a host loop can stop, inspect, resume, or swap
per-lane work between chunks (iteration-level continuous batching, Sec 4.1
early stopping, Sec 4.2 warm starts).  ``sample`` / ``sample_recording``
are thin run-to-convergence drivers over the same iterate and are
bitwise-identical to driving ``step_chunk`` until ``finished``.

Per-request knobs ride IN the state as data, so a vmapped batch mixes them
freely without retracing: ``thresh`` carries the (possibly per-request)
tolerance, ``iter_cap`` the per-request iteration budget (s_max, a
max-iters override, or a Sec 4.1 quality-steps early exit).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coeffs import SolverCoeffs, system_matrices
from repro.core.system import noise_term, first_order_residuals
from repro.core.anderson import anderson_update
from repro.models.shardctx import window_constrain


@dataclasses.dataclass(frozen=True)
class ParaTAAConfig:
    order_k: int = 4           # order of the nonlinear system (Def. 2.1)
    history_m: int = 3         # AA history size (m=1 ~ plain FP)
    window: int = 0            # sliding window size w (0 => w = T)
    mode: str = "taa"          # fp | aa | aa+ | taa | seq
    tau: float = 1e-3          # stopping tolerance
    lam: float = 1e-8          # Gram regularizer (Remark 3.3)
    s_max: int = 100           # max iterations
    safeguard: bool = True     # Theorem 3.6 post-processing
    t_init: int = 0            # 0 => fresh start (T_init = T)
    use_pallas: Optional[bool] = None  # kernels.ops dispatch for the TAA
                               # Gram/apply passes (None = auto: Pallas on
                               # TPU, the bitwise-identical jnp refs elsewhere)
    interpret: bool = False    # Pallas interpret mode (kernel tests on CPU)
    time_axis: Optional[str] = None  # mesh axis the solve window shards
                               # over (None = unsharded window; resolved
                               # against the ambient shardctx mesh at trace
                               # time, so the config stays a pure pytree-
                               # static value).  Sharded: the window eps
                               # eval only; every cross-row reduction stays
                               # replicated, so the time_shards > 1 program
                               # is bitwise-identical to the unsharded one.
    fuse_round: bool = False   # route the Anderson round through
                               # ops.taa_round: ONE launch per iteration on
                               # the Pallas path (gram + solve + apply
                               # fused), the bitwise-identical staged jnp
                               # composition elsewhere


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SolverState:
    """The entire solver carry as one explicit pytree.

    Loop-carried iterates (shapes use the FLAT latent dimension D):

    x:        (T+1, D) current trajectory iterate (x[T] pinned to the noise).
    e:        (T+1, D) stored eps evaluations (rows outside the window reuse
              their stored value in the cheap F^(k) polish).
    R_prev:   (T, D) previous residual (Anderson dF bookkeeping).
    dX, dF:   (m, T, D) Anderson histories.
    r_last:   (T,) latest first-order residuals.
    t2:       highest unconverged row (-1 => converged).
    it:       iterations executed so far (never advances once finished).
    nfe:      eps evaluations issued so far.
    done:     convergence flag (tolerance met; NOT the same as finished).

    Per-request data (constant through the solve, vmapped over lanes):

    xi:       (T+1, D) noise draws.
    noise_k:  (T, D) w_xi @ xi, the k-th order system's noise term.
    thresh:   (T,) squared per-row stopping thresholds (carries tau).
    iter_cap: iteration budget — s_max, a per-request max-iters override,
              or a quality-steps early exit (Sec 4.1).

    ``finished`` (= done | it >= iter_cap) is the retirement predicate a
    serving layer polls between chunks.
    """
    x: jax.Array
    e: jax.Array
    R_prev: jax.Array
    dX: jax.Array
    dF: jax.Array
    r_last: jax.Array
    t2: jax.Array
    it: jax.Array
    nfe: jax.Array
    done: jax.Array
    xi: jax.Array
    noise_k: jax.Array
    thresh: jax.Array
    iter_cap: jax.Array

    @property
    def finished(self) -> jax.Array:
        """Retire predicate: converged OR out of iteration budget."""
        return self.done | (self.it >= self.iter_cap)


def _build_static(coeffs: SolverCoeffs, cfg: ParaTAAConfig):
    T = coeffs.T
    w = cfg.window if cfg.window else T
    w = min(w, T)
    k = min(cfg.order_k, T)
    mats_k = system_matrices(coeffs, k)
    static = dict(
        T=T, w=w, k=k,
        lift_k=jnp.asarray(mats_k.lift, jnp.float32),
        weps_k=jnp.asarray(mats_k.w_eps, jnp.float32),
        wxi_k=jnp.asarray(mats_k.w_xi, jnp.float32),
        a=jnp.asarray(coeffs.a, jnp.float32),
        b=jnp.asarray(coeffs.b, jnp.float32),
        c=jnp.asarray(coeffs.c, jnp.float32),
        taus=jnp.asarray(coeffs.taus, jnp.float32),
        thresh_scale=jnp.asarray(coeffs.g2[1:], jnp.float32),  # (T,) row t -> g2[t+1]
    )
    return static


def _rows(mat, x):
    """``mat @ x`` over trajectory rows in full f32.  The TPU's default f32
    matmul rounds its inputs to bf16, an error of ~|x|/256 per element that
    dwarfs the stopping thresholds (tau * sqrt(g2) ~ 1e-3) and keeps the
    solve from converging; the CPU computes f32 either way."""
    return jnp.matmul(mat, x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _iterate(state: SolverState, static, cfg: ParaTAAConfig,
             eps_fn) -> SolverState:
    """One Algorithm-1 iteration.  Returns the new state.

    Named scopes split the iteration in the HLO metadata: ``parataa/
    denoise`` (the window's eps evaluation), ``residual`` (F, R, the
    first-order residuals and the window bookkeeping), ``anderson`` (the
    histories, the safeguard and the accelerated update) and ``pins`` (the
    replicate pins, where a time mesh puts its all-gather)."""
    with jax.named_scope("parataa"):
        return _iterate_scoped(state, static, cfg, eps_fn)


def _iterate_scoped(state, static, cfg, eps_fn):
    T, w = static["T"], static["w"]
    x, e, xi = state.x, state.e, state.xi
    D = x.shape[1]

    t2 = state.t2
    t1 = jnp.maximum(0, t2 - w + 1)

    # --- line 3: evaluate eps at window timesteps t1+1 .. t1+w in parallel --
    # The w window rows are independent in this pass, so they shard over the
    # `time` mesh axis: each time shard evaluates w / time_shards denoiser
    # rows.  The downstream replicate pins (e, R, the updated rows) make the
    # collective back an all-gather — exact, so bitwise vs unsharded.
    ta = cfg.time_axis
    with jax.named_scope("denoise"):
        xs = jax.lax.dynamic_slice(x, (t1 + 1, 0), (w, D))
        taus_w = jax.lax.dynamic_slice(static["taus"], (t1 + 1,), (w,))
        xs = window_constrain(xs, ta)
        taus_w = window_constrain(taus_w, ta)
        e_w = window_constrain(eps_fn(xs, taus_w).astype(e.dtype), ta)
        e = jax.lax.dynamic_update_slice(e, e_w, (t1 + 1, 0))
    with jax.named_scope("pins"):
        e = window_constrain(e, ta, replicate=True)

    with jax.named_scope("residual"):
        # --- update residual R = F^(k)(x, e) - x (rows 0..T-1) --------------
        # lift_k/weps_k contract OVER rows (triangular system) — replicated.
        F = _rows(static["lift_k"], x) + _rows(static["weps_k"], e) \
            + state.noise_k
        R = F - x[:T].astype(jnp.float32)
    with jax.named_scope("pins"):
        R = window_constrain(R, ta, replicate=True)

    with jax.named_scope("residual"):
        # --- lines 4-9: first-order residuals, window bookkeeping -----------
        # Deviation from Algorithm 1 (robustness fix, see DESIGN §7): rows
        # above t2 are NOT hard-frozen — they keep taking the (cheap,
        # eps-free) F^(k) polish with their stored e.  The k-th order
        # system with FIXED e is linear-triangular and exactly
        # first-order-consistent at its fixed point, so converged rows stay
        # converged, while hard-freezing them at threshold-level error can
        # deadlock lower rows whose (smaller) thresholds sit below the
        # inherited error.  eps evaluations are still confined to the
        # window — the compute saving is unchanged.
        r = first_order_residuals((static["a"], static["b"], static["c"]),
                                  x, e, xi)
        rows = jnp.arange(T)
        active = rows >= t1
        conv = r <= state.thresh
        unconv = active & ~conv
        any_unconv = jnp.any(unconv)
        # highest unconverged active row
        new_t2_active = T - 1 - jnp.argmax(jnp.flip(unconv))
        # all active rows converged: done if t1 == 0, else slide the window
        # down
        new_t2 = jnp.where(any_unconv, new_t2_active,
                           jnp.where(t1 == 0, jnp.int32(-1), t1 - 1))
        done = new_t2 < 0
        new_t1 = jnp.maximum(0, new_t2 - w + 1)
        upd_mask = (rows >= new_t1) & ~done

    with jax.named_scope("anderson"):
        # --- histories (Sec. 3 notation): write dF[(i-1) % m] = R^i - R^{i-1}
        it = state.it
        m = cfg.history_m
        dF = state.dF
        slot_prev = jnp.maximum(it - 1, 0) % m
        dF_entry = jnp.where(it >= 1, R - state.R_prev, jnp.zeros_like(R))
        dF = jax.lax.dynamic_update_index_in_dim(
            dF, dF_entry.astype(dF.dtype), slot_prev, 0)

        # --- lines 10-11: accelerated update over the (new) window ----------
        guard = None
        if cfg.safeguard:
            # rows whose entire suffix has converged (rows above new_t2 are
            # frozen-converged by construction)
            conv_or_frozen = conv | (rows > new_t2)
            suffix_all = jnp.flip(jnp.cumprod(
                jnp.flip(conv_or_frozen.astype(jnp.int32))))
            # row T-1's suffix is empty
            guard = jnp.concatenate([suffix_all[1:] > 0, jnp.array([True])])
        mode = cfg.mode if cfg.history_m > 1 else "fp"
        x_rows_new = anderson_update(
            x[:T], R.astype(x.dtype), state.dX, dF, upd_mask,
            mode=mode, lam=cfg.lam, safeguard_mask=guard,
            use_pallas=cfg.use_pallas, interpret=cfg.interpret,
            time_axis=ta, fuse_round=cfg.fuse_round)
    with jax.named_scope("pins"):
        x_rows_new = window_constrain(x_rows_new, ta, replicate=True)

    with jax.named_scope("anderson"):
        x_new = jnp.concatenate([x_rows_new, x[T:]], axis=0)
        # write dX[i % m] = x^{i+1} - x^i
        slot = it % m
        dX = jax.lax.dynamic_update_index_in_dim(
            state.dX, (x_new[:T] - x[:T]).astype(state.dX.dtype), slot, 0)

    return dataclasses.replace(
        state, x=x_new, e=e, R_prev=R, dX=dX, dF=dF,
        t2=new_t2, it=it + 1, done=done,
        r_last=r, nfe=state.nfe + w)


def _seq_iterate(state: SolverState, static, cfg: ParaTAAConfig,
                 eps_fn) -> SolverState:
    """One eq.-(6) sequential timestep on the same state layout: read
    x[t2+1], write x[t2], slide t2 down.  Bitwise-identical math to
    ``repro.diffusion.samplers._sequential_sample`` (same a/b/c recursion),
    but resumable/chunkable like the parallel iterate."""
    with jax.named_scope("parataa"):
        return _seq_iterate_scoped(state, static, eps_fn)


def _seq_iterate_scoped(state, static, eps_fn):
    D = state.x.shape[1]
    t = state.t2 + 1                               # current timestep T..1
    x_t = jax.lax.dynamic_slice(state.x, (t, 0), (1, D))
    tau_t = jax.lax.dynamic_slice(static["taus"], (t,), (1,))
    with jax.named_scope("denoise"):
        e = eps_fn(x_t, tau_t)
    a_t = jax.lax.dynamic_slice(static["a"], (t,), (1,))
    b_t = jax.lax.dynamic_slice(static["b"], (t,), (1,))
    c_prev = jax.lax.dynamic_slice(static["c"], (t - 1,), (1,))
    xi_prev = jax.lax.dynamic_slice(state.xi, (t - 1, 0), (1, D))
    x_prev = a_t[0] * x_t[0] + b_t[0] * e[0] + c_prev[0] * xi_prev[0]
    x = jax.lax.dynamic_update_slice(state.x, x_prev[None].astype(state.x.dtype),
                                     (state.t2, 0))
    new_t2 = state.t2 - 1
    return dataclasses.replace(
        state, x=x, t2=new_t2, it=state.it + 1, nfe=state.nfe + 1,
        done=new_t2 < 0)


def _iterate_fn(cfg: ParaTAAConfig):
    return _seq_iterate if cfg.mode == "seq" else _iterate


def init_state(coeffs: SolverCoeffs, cfg: ParaTAAConfig, xi,
               x_init: Optional[jax.Array] = None, dtype=jnp.float32,
               t_init=None, tau_sq=None, iter_cap=None) -> SolverState:
    """Build the solver's initial :class:`SolverState` (jit-able).

    xi:       (T+1, *shape) noise draws (xi[T] = x_T); flattened internally.
    x_init:   optional (T+1, *shape) initialization trajectory (Sec. 4.2).
    t_init:   restart depth T_init; may be a traced int32 scalar so a
              vmapped batch mixes warm-start depths per lane.
    tau_sq:   SQUARED stopping tolerance override (traced scalar OK) — kept
              squared so the host packs ``float32(tau**2)`` and the default
              (``cfg.tau ** 2`` as a python float) stays bitwise-identical.
    iter_cap: iteration budget override (traced int32 OK): a per-request
              max-iters bound or quality-steps early exit; default s_max.
    """
    T = coeffs.T
    shape = xi.shape[1:]
    D = int(np.prod(shape))
    xi_f = xi.reshape(T + 1, D)
    x0_f = None if x_init is None else x_init.reshape(T + 1, D)

    static = _build_static(coeffs, cfg)
    noise_k = _rows(static["wxi_k"], xi_f)
    if tau_sq is None:
        tau_sq = cfg.tau ** 2
    thresh = tau_sq * static["thresh_scale"] * D
    if iter_cap is None:
        iter_cap = cfg.s_max

    if t_init is None:
        t_init = cfg.t_init if cfg.t_init else T
    if cfg.mode == "seq":
        t_init = T                                 # seq always walks all rows
    if x0_f is None:
        x0_f = xi_f  # standard Gaussian init (paper Sec. 5 setting)
    x = x0_f.astype(dtype)
    # x_T is always the initial noise
    x = x.at[T].set(xi_f[T].astype(dtype))
    m = cfg.history_m
    return SolverState(
        x=x,
        e=jnp.zeros((T + 1, D), dtype),
        R_prev=jnp.zeros((T, D), jnp.float32),
        dX=jnp.zeros((m, T, D), dtype),
        dF=jnp.zeros((m, T, D), dtype),
        r_last=jnp.full((T,), jnp.inf, jnp.float32),
        t2=jnp.asarray(t_init, jnp.int32) - 1,
        it=jnp.asarray(0, jnp.int32),
        nfe=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False),
        xi=xi_f,
        noise_k=noise_k,
        thresh=jnp.asarray(thresh, jnp.float32),
        iter_cap=jnp.asarray(iter_cap, jnp.int32),
    )


def _flat_eps(eps_fn: Callable, shape) -> Callable:
    """Adapt a (w, *shape)-shaped eps_fn to the state's flat (w, D) layout."""
    if not shape:
        return eps_fn
    D = int(np.prod(shape))

    def eps_flat(xw, taus_w):
        return eps_fn(xw.reshape((-1,) + tuple(shape)), taus_w).reshape(-1, D)

    return eps_flat


def step_chunk(eps_fn: Callable, coeffs: SolverCoeffs, cfg: ParaTAAConfig,
               state: SolverState, num_iters: int, *,
               sample_shape=()) -> SolverState:
    """Advance ``state`` by up to ``num_iters`` solver iterations (jit-able;
    ``num_iters`` is static).

    Each step is guarded on ``state.finished``, so already-retired lanes of
    a vmapped batch pass through unchanged — driving this repeatedly until
    ``finished`` reproduces the monolithic ``sample`` loop bitwise, chunk
    boundaries and per-lane budgets included.  ``sample_shape`` is the
    unflattened latent shape ``eps_fn`` expects (``()`` = already flat).
    """
    static = _build_static(coeffs, cfg)
    eps_flat = _flat_eps(eps_fn, sample_shape)
    it_fn = _iterate_fn(cfg)

    def step(s, _):
        s2 = jax.lax.cond(
            s.finished, lambda s: s,
            lambda s: it_fn(s, static, cfg, eps_flat), s)
        return s2, None

    out, _ = jax.lax.scan(step, state, None, length=num_iters)
    return out


def state_info(state: SolverState) -> dict:
    """The legacy info dict for a (possibly still-running) state."""
    return dict(iters=state.it, nfe=state.nfe, converged=state.done,
                residuals=state.r_last)


def lane_residual(state: SolverState) -> jax.Array:
    """Scalar per-lane convergence telemetry: the WORST row's latest
    first-order residual (the quantity each row's threshold gates, so the
    max is the lane's distance from its stopping criterion).  Shape
    follows the leading batch axes of ``r_last`` — a scalar for one lane,
    ``(slots,)`` for a vmapped bank — and rides the stepwise step
    program's packed poll summary (f32, bitcast into the int32 payload so
    the host still fetches ONE array per round).  Fresh lanes report +inf
    (``r_last`` init) until their first parallel iterate; sequential
    lanes report +inf forever (eq. 6 has no fixed-point residual)."""
    return jnp.max(state.r_last, axis=-1)


def sample(eps_fn: Callable, coeffs: SolverCoeffs, cfg: ParaTAAConfig, xi,
           x_init: Optional[jax.Array] = None, dtype=jnp.float32,
           t_init=None, tau_sq=None, iter_cap=None):
    """Run to convergence (or the iteration budget): a thin while_loop
    driver over ``init_state`` + the stepwise iterate.

    eps_fn: (x (w, *shape), taus (w,)) -> eps (w, *shape)
    xi:     (T+1, *shape) noise draws (xi[T] = x_T)
    x_init: optional (T+1, *shape) initialization trajectory (Sec. 4.2)
    t_init: optional runtime override of cfg.t_init; may be a traced int32
            scalar, so a vmapped batch can mix warm-start depths per sample
    tau_sq / iter_cap: per-request overrides (see ``init_state``)
    Returns (trajectory (T+1, *shape), info dict).
    """
    shape = xi.shape[1:]
    state = init_state(coeffs, cfg, xi, x_init=x_init, dtype=dtype,
                       t_init=t_init, tau_sq=tau_sq, iter_cap=iter_cap)
    static = _build_static(coeffs, cfg)
    eps_flat = _flat_eps(eps_fn, shape)
    it_fn = _iterate_fn(cfg)

    out = jax.lax.while_loop(
        lambda s: ~s.finished,
        lambda s: it_fn(s, static, cfg, eps_flat), state)
    return out.x.reshape((coeffs.T + 1,) + shape), state_info(out)


def sample_recording(eps_fn, coeffs: SolverCoeffs, cfg: ParaTAAConfig, xi,
                     x_init: Optional[jax.Array] = None, dtype=jnp.float32,
                     t_init=None, tau_sq=None, iter_cap=None):
    """Fixed-s_max scan variant that records per-iteration diagnostics:
    residual vectors (s_max, T) and x_0 iterates (s_max, D) — used by the
    benchmark reproductions of Figures 1, 2, 4, 6 and the early-stopping
    analysis.  A thin scan driver over the same stepwise iterate."""
    shape = xi.shape[1:]
    state = init_state(coeffs, cfg, xi, x_init=x_init, dtype=dtype,
                       t_init=t_init, tau_sq=tau_sq, iter_cap=iter_cap)
    static = _build_static(coeffs, cfg)
    eps_flat = _flat_eps(eps_fn, shape)
    it_fn = _iterate_fn(cfg)

    def step(s, _):
        s2 = jax.lax.cond(
            s.finished, lambda s: s,
            lambda s: it_fn(s, static, cfg, eps_flat), s)
        rec = dict(r=s2.r_last, x0=s2.x[0], t2=s2.t2, done=s2.done)
        return s2, rec

    out, recs = jax.lax.scan(step, state, None, length=cfg.s_max)
    info = dict(iters=out.it, nfe=out.nfe, converged=out.done,
                res_history=recs["r"], x0_history=recs["x0"],
                t2_history=recs["t2"], done_history=recs["done"])
    return out.x.reshape((coeffs.T + 1,) + shape), info
