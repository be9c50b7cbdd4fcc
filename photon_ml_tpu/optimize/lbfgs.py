"""Pure-functional L-BFGS and OWL-QN with masked updates.

Replaces the reference's Breeze-backed LBFGS/OWLQN adapters
(photon-lib .../optimization/LBFGS.scala:38-154, OWLQN.scala:39-83) with a
single jit/vmap-safe implementation:

- fixed-size (m, d) correction history with circular indexing (static shapes
  for XLA; m = numCorrections, default 10). A wide one-lane solve keeps it as
  ``[m, d_pad / 128, 128]`` instead, a pair one contiguous run of tiles
  (``history_row_width``: the TPU tiles ``[m, d]`` eight rows to a tile, and
  every read of one row of it is a strided copy). A w0 split over a mesh axis
  splits the history along its columns the same way (``state_partition``),
  each device's part whole rows;
- two-loop recursion preconditioned by the gamma = s.y/y.y scaling;
- weak-Wolfe line search by bisection/expansion (c1=1e-4, c2=0.9) run inside
  ``lax.while_loop`` with masked state so vmapped lanes freeze independently.
  There are two searches and one verdict (``_verdict``: the same Armijo and
  curvature tests, bracket, bisection, finiteness and trial cap). The ``points``
  search (``_line_search``) evaluates ``value_and_grad(w + t p)`` at every step
  length it tries: a pass over the features each way a trial. The ``margins``
  search (``_margin_search``) serves an objective that comes as its steps
  (``common.MarginFns``: a GLM's margins z are affine in w): the carry holds z
  beside w, an iteration takes u = direction_margins(p) once, every trial is
  phi(t), phi'(t) from row-length sums over z + t u, and the gradient is taken
  once, at the step kept, from z + t u: ONE matvec and ONE rmatvec an
  iteration however many lengths are tried, and no d-length array in the
  search's state. z is carried from step to step (z + t u), computed from w
  only at the solve's first evaluation; its drift is rounding (PERF.md, PR 37).
  Which search runs is decided on what the solve is handed
  (``walks_margins``): margin functions, no l1 weight, no box, one lane.
  ``host_driver`` (streamed rows) mirrors the ``points`` search;
- OWL-QN (l1_weight > 0): pseudo-gradient, direction orthant projection, and
  orthant-constrained line-search steps; the correction pairs use the plain
  gradient, convergence uses the pseudo-gradient — matching the OWL-QN
  algorithm the reference delegates to Breeze for. WHETHER a solve is OWL-QN
  is decided on the host (a positive weight); the weight itself is an operand
  of the program, so a lambda path or a tuner's trials share ONE compiled
  solver, and a solve without it lowers to plain L-BFGS with no pseudo-gradient
  or orthant op in it;
- box constraints (L-BFGS-B, reference LBFGSB.scala:39-92): gradient
  projection — the "gradient" driving the two-loop direction and the
  convergence test is the projected gradient w - P(w - g), which vanishes
  exactly at bound-held coordinates — with every line-search trial point
  projected onto the box and Armijo measured on the actual displacement
  f(P(w + t*d)) <= f + c1*g.(w_t - w). Unlike clamp-after-step this
  converges to the constrained KKT point when bounds are active.

Every lane of state carries a ``done`` flag; once set, all updates become
no-ops, which is what makes ``jax.vmap(solve_lbfgs, ...)`` correct for the
batched per-entity random-effect solves.

Two batching modes serve the random-effect solve (SURVEY.md §2.1 P8):

- ``vmap(solve_lbfgs)`` over entity-leading blocks ``[E, K, S]`` — the
  original path, exact per-entity history bookkeeping;
- ``solve_lbfgs(..., batched=True)`` over **entity-minor** stacks: ``w`` is
  ``[S, E]`` and every reduction runs over axis 0, so the entity axis rides
  the TPU's 128-lane dimension regardless of S. With S=32 the entity-leading
  layout wastes 3/4 of every vector lane; entity-minor is fully packed. The
  one semantic difference: the correction history uses a shared circular
  cursor with per-lane validity (``rho == 0`` marks an invalid pair) instead
  of per-lane cursors, which only diverges in the rare curvature-guard case
  (``s.y`` too small on an improving step) — the optimum reached is the same.

The lane shape is fully generic (``lanes = jnp.shape(f0)``, reductions over
axis 0), so ``batched=True`` also drives lambda-lane stacks for lane-batched
hyperparameter sweeps (game/lanes.py): ``w`` is ``[d, L]`` with one reg
candidate per lane of a shared objective, or ``[S, E, L]`` for entity x
lambda random-effect stacks. Masked commits are what make the sweep safe —
a converged or diverged lambda lane freezes at its last committed iterate
(per-lane ``ConvergenceReason``) without stalling or perturbing neighbors.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import obs
from .common import (
    ConvergenceReason,
    MarginFns,
    SolverResult,
    ValueAndGradFn,
    _norm,
    _vdot,
    as_partial,
    check_convergence,
    finite_state,
)

Array = jax.Array

_C1 = 1e-4  # Armijo (sufficient decrease)
_C2 = 0.9  # curvature

_LANES = 128
_TILE = 8 * _LANES  # the TPU's (8, 128) tile of 32-bit elements
# The TPU lays an ``[m, d]`` history out in (8, 128) tiles: m = 10 rows pad to
# 16, and row j is 512 bytes of every 4 KB tile, so each ``S[j]`` of the
# two-loop recursion is first copied out, strided (2.84 ms a 219 MB row at a
# tenth of the chip's bandwidth, forty times an iteration: PERF.md, PR 39).
# From this width on a one-lane solve keeps a pair as one contiguous row
# instead. Below it a row is under a thousand tiles and its copy microseconds,
# and the programs there are pinned letter for letter (tests/test_glm_enet.py's
# golden hashes, tests/test_re_build.py's bucketed-against-flat equality).
HISTORY_ROWS_MIN_DIM = 1 << 20


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def history_row_width(w_shape: Tuple[int, ...], batched: bool, shards: int = 1) -> Optional[int]:
    """How a solve over coefficients of ``w_shape`` stores its correction
    pairs, decided on the shape and the state's shard count alone: ``d_pad``
    (d rounded up to whole tiles, and to whole rows of 128 on each of
    ``shards`` devices that split the state) when the history is ``[m, d_pad /
    128, 128]``, a pair one contiguous, unpadded run of tiles; None for ``[m,
    *w_shape]``, which the TPU tiles over (pair, column). Only a one-lane solve
    at least ``HISTORY_ROWS_MIN_DIM`` wide keeps rows: the packed lanes' ``[m,
    S, E]`` is entity-minor already."""
    if batched or len(w_shape) != 1 or w_shape[0] < HISTORY_ROWS_MIN_DIM:
        return None
    return _round_up(w_shape[0], math.lcm(_TILE, _LANES * shards))


def history_account(
    dim: int, num_corrections: int, itemsize: int, shards: int = 1
) -> Tuple[str, int]:
    """What a host-level solve over ``dim`` coefficients keeps as its history
    on EACH of the ``shards`` devices its state is split over, from shapes
    alone (the ``fe.solve`` span's ``history`` and ``history_bytes``): ``rows``
    and 2 m d_pad / shards elements, or ``tiled`` and the (8, 128) tiling's 2 x
    (m rounded up to 8) x (a shard's columns rounded up to 128)."""
    d_pad = history_row_width((dim,), False, shards)
    if d_pad is not None:
        return "rows", 2 * num_corrections * (d_pad // shards) * itemsize
    width = _round_up(-(-dim // shards), _LANES)
    return "tiled", 2 * _round_up(num_corrections, 8) * width * itemsize


def state_partition(w) -> Optional[NamedSharding]:
    """The sharding a one-lane solve's coefficient-length state takes: ``w``'s
    own where ``w`` is a placed ``[d]`` array split along its axis over more
    than one device of a mesh (a fixed effect's state sharded over the data
    axis by ``GLMProblem.run``, or a tiled one's over the model axis); None
    for a vector whole on one device or on each (every one-chip solve)."""
    sharding = getattr(w, "sharding", None)
    if isinstance(w, jax.core.Tracer) or not isinstance(sharding, NamedSharding):
        return None
    if jnp.ndim(w) != 1 or state_shards(sharding) <= 1:
        return None
    return sharding


def state_shards(sharding: Optional[NamedSharding]) -> int:
    """Devices a ``[d]`` vector under ``sharding`` is split over (1: none)."""
    spec = tuple(sharding.spec) if sharding is not None else ()
    if not spec or spec[0] is None:
        return 1
    axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    return math.prod(sharding.mesh.shape[a] for a in axes)


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """OWL-QN pseudo-gradient of f(w) + l1*||w||_1."""
    gp = g + l1
    gm = g - l1
    pg = jnp.where(w > 0, gp, jnp.where(w < 0, gm, 0.0))
    at_zero = jnp.where(gm > 0, gm, jnp.where(gp < 0, gp, 0.0))
    return jnp.where(w == 0, at_zero, pg)


def _two_loop(
    S: Array, Y: Array, rho: Array, count: Array, head: Array, g: Array,
    unroll: bool = False,
) -> Array:
    """Two-loop recursion over a circular history buffer.

    S, Y: [m, d]; rho: [m]; count = #valid pairs; head = index of next write.
    Slot order from newest to oldest: head-1, head-2, ...
    A history kept by rows (``history_row_width``: S, Y ``[m, d_pad / 128,
    128]`` for a g of ``[d]``) runs the same recursion over d_pad entries, the
    tail exact zeros: g is padded once, a pair's row read in place, and the
    direction's first d entries handed back.

    ``unroll=True`` (the batched entity-minor mode) runs two fully-unrolled
    ``lax.scan``s over the history rotated into newest-first order (``roll``
    compiles to two slices + concat, not a gather). Unrolling matters there:
    the recursion is a dependency chain of 2m small ops, and a rolled
    ``fori_loop`` pays ms-scale per-step scheduling overhead on [d, E] stacks
    (measured ~11x on [32, 14k]). The vmapped/single-problem path keeps the
    opaque ``fori_loop``: it isolates the recursion from surrounding fusion,
    which is what keeps per-entity results bit-identical across bucket shapes
    (tests/test_re_build.py bucketed-vs-flat exactness).
    """
    m = S.shape[0]

    if unroll:
        # rotate so index 0 is the newest pair (head - 1), 1 the next, ...
        Sn = jnp.flip(jnp.roll(S, -head, axis=0), axis=0)
        Yn = jnp.flip(jnp.roll(Y, -head, axis=0), axis=0)
        rhon = jnp.flip(jnp.roll(rho, -head, axis=0), axis=0)
        valid = jnp.arange(m) < count  # newest-first validity

        def loop1s(q, x):
            Sj, Yj, rhoj, vld = x
            alpha = jnp.where(vld, rhoj * _vdot(Sj, q), 0.0)
            q = q - alpha * Yj
            return q, alpha

        q, alphas = jax.lax.scan(loop1s, g, (Sn, Yn, rhon, valid), unroll=m)

        # gamma from the newest pair; an invalid batched-mode pair stores
        # zeros, so the yy > 0 guard falls back to gamma = 1 per lane
        ys = _vdot(Sn[0], Yn[0])
        yy = _vdot(Yn[0], Yn[0])
        gamma = jnp.where(
            (count > 0) & (yy > 0), ys / jnp.where(yy > 0, yy, 1.0), 1.0
        )
        r = gamma * q

        def loop2s(r, x):
            Sj, Yj, rhoj, vld, alpha = x
            beta = jnp.where(vld, rhoj * _vdot(Yj, r), 0.0)
            r = r + jnp.where(vld, alpha - beta, 0.0) * Sj
            return r, None

        # oldest to newest = reverse scan over the newest-first order
        r, _ = jax.lax.scan(
            loop2s, r, (Sn, Yn, rhon, valid, alphas), reverse=True, unroll=m
        )
        return r

    d = g.shape[0]
    by_rows = S.ndim == g.ndim + 2
    if by_rows:
        g = jnp.pad(g, (0, S.shape[1] * S.shape[2] - d))

    def pair(H, j):
        # a row's [R, 128] tiles are its d_pad entries in order: the TPU
        # re-reads them as [d_pad] for nothing (never reshape H itself: [m,
        # d_pad] is the tiled layout again)
        return H[j].reshape(-1) if by_rows else H[j]

    def newest_to_oldest(i):
        return (head - 1 - i) % m

    def loop1(i, carry):
        q, alphas = carry
        j = newest_to_oldest(i)
        valid = i < count
        alpha = jnp.where(valid, rho[j] * _vdot(pair(S, j), q), 0.0)
        q = q - jnp.where(valid, alpha, 0.0) * pair(Y, j)
        return q, alphas.at[i].set(alpha)

    q, alphas = jax.lax.fori_loop(
        0, m, loop1, (g, jnp.zeros((m,) + g.shape[1:], dtype=g.dtype))
    )

    newest = newest_to_oldest(0)
    ys = _vdot(pair(S, newest), pair(Y, newest))
    yy = _vdot(pair(Y, newest), pair(Y, newest))
    gamma = jnp.where((count > 0) & (yy > 0), ys / jnp.where(yy > 0, yy, 1.0), 1.0)
    r = gamma * q

    def loop2(i, r):
        # oldest to newest: i runs m-1 .. 0 over the newest_to_oldest index
        idx = m - 1 - i
        j = newest_to_oldest(idx)
        valid = idx < count
        beta = jnp.where(valid, rho[j] * _vdot(pair(Y, j), r), 0.0)
        r = r + jnp.where(valid, alphas[idx] - beta, 0.0) * pair(S, j)
        return r

    r = jax.lax.fori_loop(0, m, loop2, r)
    return r[:d] if by_rows else r


def _verdict(s, f: Array, dg: Array, max_iters: int, decrease=None, slope=None):
    """The verdict on a search state's newest trial, for both searches (``s``
    is a ``_LineSearchState`` or a ``_MarginSearchState``): Armijo and finite,
    curvature, the bracket, the step length to try next, ``done``.

    ``decrease`` is Armijo's right-hand term, ``c1 * t * dg`` unless given
    (L-BFGS-B measures it on the projected displacement). ``slope`` gives the
    trial's directional derivative; None enforces Armijo alone (OWL-QN and
    L-BFGS-B backtracking). It is a callable, called after the Armijo verdict,
    so that the points search's reduction stays where its program had it."""
    armijo_ok = s.f_t <= f + (_C1 * s.t * dg if decrease is None else decrease)
    ok = armijo_ok & jnp.isfinite(s.f_t)
    if slope is not None:
        # weak Wolfe (Lewis-Overton bisection scheme): convergent under pure
        # bisection/expansion and still guarantees s.y > 0 for the history
        curv_ok = slope() >= _C2 * dg
    else:
        curv_ok = jnp.ones(jnp.shape(f), bool)
    accept = ok & curv_ok

    # bracket update
    new_hi = jnp.where(ok, s.hi, s.t)
    new_lo = jnp.where(ok & ~curv_ok, s.t, s.lo)
    new_t = jnp.where(
        jnp.isinf(new_hi), 2.0 * new_lo + 1.0, 0.5 * (new_lo + new_hi)
    )
    # if Armijo failed, bisect downward
    new_t = jnp.where(ok, new_t, 0.5 * (s.lo + s.t))

    it = s.it + 1
    done = accept | (it >= max_iters)
    return s._replace(
        t=jnp.where(done, s.t, new_t),
        lo=jnp.where(done, s.lo, new_lo),
        hi=jnp.where(done, s.hi, new_hi),
        it=it,
        done=done,
        success=s.success | accept,
    )


class _LineSearchState(NamedTuple):
    t: Array
    lo: Array
    hi: Array
    f_t: Array
    g_t: Array
    w_t: Array
    it: Array
    done: Array
    success: Array


def _line_search(
    value_and_grad: ValueAndGradFn,
    w: Array,
    f: Array,
    direction: Array,
    dg: Array,  # directional derivative of the (possibly l1-augmented) objective
    l1: Optional[Array],  # None = no l1 term (plain L-BFGS / L-BFGS-B)
    orthant: Optional[Array],
    max_iters: int,
    box: Optional[Tuple[Array, Array]] = None,
    g_plain: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array, Array, Array]:
    """The ``points`` search: weak-Wolfe bisection over trial POINTS, each a
    ``value_and_grad`` call (``_margin_search`` is the other search; the
    verdict, ``_verdict``, is shared). Returns (w_new, f_new, g_new,
    success, the step length kept, the number of trials judged: one objective
    evaluation each, the first trial's among them).

    Each trial is judged (Armijo / curvature / finite, bracket, next step
    length) before the next is evaluated, as ``host_driver._line_search`` does
    for one lane: nothing is evaluated after the trial that ends the search.

    A trial whose value is not finite (Poisson's ``exp`` past z = 88 in f32
    gives ``inf``, and ``inf - inf`` downstream ``NaN``) is a FAILED step on
    every branch: it is never accepted and the step is halved.

    For OWL-QN (orthant is not None) each trial point is projected onto the
    orthant and only the Armijo condition is enforced (standard OWL-QN
    backtracking); f and dg then refer to the l1-augmented objective.

    For L-BFGS-B (box is not None) each trial point is projected onto the box
    and Armijo is measured on the actual displacement
    f_t <= f + c1 * g.(w_t - w) (projected-gradient line search), again with
    no curvature condition.
    """
    dtype = w.dtype
    # lane shape comes from f: () for a single problem, [E] for entity-minor
    lanes = jnp.shape(f)

    def trial(t):
        w_t = w + t * direction
        if orthant is not None:
            w_t = jnp.where(w_t * orthant < 0, 0.0, w_t)
        if box is not None:
            w_t = jnp.clip(w_t, box[0], box[1])
        f_t, g_t = value_and_grad(w_t)
        if l1 is not None:
            f_t = f_t + l1 * jnp.sum(jnp.abs(w_t), axis=0)
        return w_t, f_t, g_t

    def judge(s: _LineSearchState) -> _LineSearchState:
        # s holds every lane's newest trial: the verdict on it, the bracket and,
        # in ``t`` of a lane that searches on, the step length to try next. A
        # done lane's trial is frozen, so judging it again says the same.
        # The barrier keeps the verdict's reductions out of the evaluation's
        # fusions: they read the trial as written, as they did across the
        # loop's carry when the loop judged first. Fused, the packed lanes of
        # a TPU round otherwise and stop on other iterations (PERF.md, PR 35)
        s = jax.lax.optimization_barrier(s)
        if box is not None:
            return _verdict(s, f, dg, max_iters, decrease=_C1 * _vdot(g_plain, s.w_t - w))
        if orthant is not None:
            return _verdict(s, f, dg, max_iters)
        return _verdict(s, f, dg, max_iters, slope=lambda: _vdot(s.g_t, direction))

    w0_t, f0_t, g0_t = trial(jnp.asarray(1.0, dtype))

    # the first trial is judged before the loop: a search whose full step every
    # lane accepts runs no trip
    init = judge(_LineSearchState(
        t=jnp.full(lanes, 1.0, dtype),
        lo=jnp.zeros(lanes, dtype),
        hi=jnp.full(lanes, jnp.inf, dtype),
        f_t=f0_t,
        g_t=g0_t,
        w_t=w0_t,
        it=jnp.asarray(0, jnp.int32),
        done=jnp.zeros(lanes, bool),
        success=jnp.zeros(lanes, bool),
    ))

    def cond(s: _LineSearchState):
        return jnp.logical_not(jnp.all(s.done))

    def body(s: _LineSearchState):
        # evaluate first, judge last: a trip runs only while some lane still
        # searches, so no evaluation follows an accepted step
        w_t, f_t, g_t = trial(s.t)
        # freeze trial values if done
        return judge(s._replace(
            f_t=jnp.where(s.done, s.f_t, f_t),
            g_t=jnp.where(s.done, s.g_t, g_t),
            w_t=jnp.where(s.done, s.w_t, w_t),
        ))

    final = jax.lax.while_loop(cond, body, init)
    return final.w_t, final.f_t, final.g_t, final.success, final.t, final.it


class _MarginSearchState(NamedTuple):
    """``_LineSearchState`` without its d-length arrays: the trial is two
    scalars, phi(t) in ``f_t`` and phi'(t) in ``slope_t``."""

    t: Array
    lo: Array
    hi: Array
    f_t: Array
    slope_t: Array
    it: Array
    done: Array
    success: Array


def _margin_search(
    value_and_slope, f: Array, dg: Array, max_iters: int
) -> Tuple[Array, Array, Array]:
    """``_line_search`` for one plain L-BFGS lane whose objective's margins
    are affine in w (``MarginFns``): ``value_and_slope(t)`` gives phi(t) =
    F(w + t p) and phi'(t) from row-length sums, where ``_line_search`` reads
    F and ``grad F . p`` off a pass over the features. The same verdict on the
    same two numbers (``_verdict``), so the same step lengths; the search
    touches no feature and carries no d-length array. Returns (the step
    length kept, success, the number of trials judged); the caller takes the
    gradient once, at the step kept."""
    dtype = f.dtype

    def judged(s: _MarginSearchState, t: Array) -> _MarginSearchState:
        f_t, slope_t = value_and_slope(t)
        s = s._replace(f_t=f_t, slope_t=slope_t)
        return _verdict(s, f, dg, max_iters, slope=lambda: s.slope_t)

    one = jnp.asarray(1.0, dtype)
    init = judged(_MarginSearchState(
        t=one,
        lo=jnp.asarray(0.0, dtype),
        hi=jnp.asarray(jnp.inf, dtype),
        f_t=f,
        slope_t=dg,
        it=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False),
        success=jnp.asarray(False),
    ), one)
    final = jax.lax.while_loop(
        lambda s: jnp.logical_not(s.done), lambda s: judged(s, s.t), init
    )
    return final.t, final.success, final.it


class _LBFGSState(NamedTuple):
    w: Array
    f: Array  # objective incl. l1 term if OWL-QN
    g: Array  # plain gradient of the smooth part
    it: Array
    k: Array  # global loop counter (scalar; == it for never-frozen lanes)
    done: Array
    reason: Array
    S: Array
    Y: Array
    rho: Array
    count: Array
    head: Array
    loss_history: Array
    grad_norm_history: Array
    # None = a leafless entry of the loop's carry. ``evals`` is carried by an
    # OWL-QN solve and by one asked to count (``count_evals``: the host-level
    # fixed-effect solve); ``zeroed`` by OWL-QN alone
    evals: Optional[Array] = None  # objective evaluations so far
    zeroed: Optional[Array] = None  # coefficients the orthant projection zeroed
    # the margins search alone: z = margins(w), carried (z + t u at a step
    # kept), and, where the solve counts, its passes over the features
    z: Optional[Array] = None
    matvecs: Optional[Array] = None
    rmatvecs: Optional[Array] = None


@partial(
    jax.jit,
    static_argnames=(
        "max_iterations",
        "num_corrections",
        "max_line_search_iterations",
        "has_box",
        "batched",
        "count_evals",
        "state_sharding",
    ),
)
def _solve(
    value_and_grad: ValueAndGradFn,
    w0: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    max_iterations: int,
    num_corrections: int,
    l1_weight: Optional[Array],  # an OPERAND; None (no leaf) = plain L-BFGS
    max_line_search_iterations: int,
    has_box: bool,
    box_lower: Array,
    box_upper: Array,
    batched: bool = False,
    count_evals: bool = False,
    margins: Optional[MarginFns] = None,  # solve_lbfgs: plain one-lane L-BFGS only
    state_sharding: Optional[NamedSharding] = None,  # solve_lbfgs: ``state_partition(w0)``
) -> SolverResult:
    m = num_corrections
    dtype = w0.dtype
    box = (box_lower, box_upper) if has_box else None
    l1 = l1_weight
    owlqn = l1 is not None
    # an int32 beside the floats of the carry: it changes none of them
    counted = owlqn or count_evals
    walk = margins is not None
    d_pad = history_row_width(w0.shape, batched, state_shards(state_sharding))
    history_shape = (m,) + w0.shape if d_pad is None else (m, d_pad // _LANES, _LANES)

    def pinned(H):
        """The history split as the coefficients are, along its columns (each
        device's part whole rows: ``history_row_width``), never replicated."""
        if state_sharding is None:
            return H
        spec = (None, state_sharding.spec[0]) + (None,) * (H.ndim - 2)
        return jax.lax.with_sharding_constraint(
            H, NamedSharding(state_sharding.mesh, PartitionSpec(*spec))
        )

    def as_pair(v):
        """s or y as the history stores a pair."""
        if d_pad is None:
            return v
        # zeros in the tail: they add nothing to a dot of the recursion
        return jnp.pad(v, (0, d_pad - v.shape[0])).reshape(history_shape[1:])

    def full_objective(w):
        f, g = value_and_grad(w)
        if owlqn:
            f = f + l1 * jnp.sum(jnp.abs(w), axis=0)
        return f, g

    if box is not None:
        w0 = jnp.clip(w0, box[0], box[1])  # start feasible
    z0 = None
    if margins is not None:
        # the solve's one margins(w): every later z is z + t u
        z0 = margins.margins(w0)
        f0, g0 = margins.grad_from_margins(z0, w0)
    else:
        f0, g0 = full_objective(w0)
    lanes = jnp.shape(f0)  # () single problem / [E] entity-minor batch

    hist = jnp.full((max_iterations + 1,) + lanes, jnp.nan, dtype)

    def effective_grad(w, g):
        if owlqn:
            return _pseudo_gradient(w, g, l1)
        if box is not None:
            # projected gradient: zero at bound-held coordinates, so both the
            # quasi-Newton direction and the convergence test respect the
            # active set (LBFGSB.scala:39-92 semantics)
            return w - jnp.clip(w - g, box[0], box[1])
        return g

    pg0 = effective_grad(w0, g0)

    # a lane whose data is already corrupt has no good iterate to roll back
    # to: freeze it at w0 immediately instead of letting NaN flow through the
    # two-loop recursion (every comparison against NaN is False, so nothing
    # downstream would ever catch it)
    bad0 = ~finite_state(f0, g0) & jnp.ones(lanes, bool)

    init = _LBFGSState(
        w=w0,
        f=f0,
        g=g0,
        it=jnp.zeros(lanes, jnp.int32),
        k=jnp.asarray(0, jnp.int32),
        done=bad0,
        reason=jnp.where(
            bad0, int(ConvergenceReason.NUMERICAL_DIVERGENCE), 0
        ).astype(jnp.int32),
        S=pinned(jnp.zeros(history_shape, dtype)),
        Y=pinned(jnp.zeros(history_shape, dtype)),
        rho=jnp.zeros((m,) + lanes, dtype),
        count=jnp.asarray(0, jnp.int32) if batched else jnp.zeros(lanes, jnp.int32),
        head=jnp.asarray(0, jnp.int32) if batched else jnp.zeros(lanes, jnp.int32),
        loss_history=hist.at[0].set(f0),
        grad_norm_history=hist.at[0].set(_norm(pg0)),
        evals=jnp.ones(lanes, jnp.int32) if counted else None,
        zeroed=jnp.zeros(lanes, jnp.int32) if owlqn else None,
        z=z0,
        matvecs=jnp.ones(lanes, jnp.int32) if walk and counted else None,
        rmatvecs=jnp.ones(lanes, jnp.int32) if walk and counted else None,
    )

    def cond(s: _LBFGSState):
        return jnp.logical_not(jnp.all(s.done))

    def body(s: _LBFGSState):
        pg = effective_grad(s.w, s.g)
        direction = -_two_loop(s.S, s.Y, s.rho, s.count, s.head, pg, unroll=batched)
        if owlqn:
            # project direction into the descent orthant of -pg
            direction = jnp.where(direction * pg >= 0, 0.0, direction)
        dg = _vdot(direction, pg)
        # fall back to steepest descent if not a descent direction
        bad = dg >= 0
        direction = jnp.where(bad, -pg, direction)
        dg = jnp.where(bad, -_vdot(pg, pg), dg)

        orthant = None
        if owlqn:
            orthant = jnp.where(s.w != 0, jnp.sign(s.w), -jnp.sign(pg))

        if walk:
            # one gather for the direction, a search over row-length sums, one
            # scatter-add for the gradient at the step kept
            u = margins.direction_margins(direction)
            t_new, ls_ok, ls_trials = _margin_search(
                lambda t: margins.value_and_slope(s.z, u, t, s.w, direction),
                s.f, dg, max_line_search_iterations,
            )
            w_new = s.w + t_new * direction
            z_new = s.z + t_new * u
            f_new, g_new = margins.grad_from_margins(z_new, w_new)
        else:
            w_new, f_new, g_new, ls_ok, t_new, ls_trials = _line_search(
                value_and_grad, s.w, s.f, direction, dg, l1, orthant,
                max_line_search_iterations, box=box, g_plain=s.g,
            )

        # a non-finite trial outcome is numerical divergence: the masked
        # commit below keeps the last good iterate (rollback is free), and
        # excluding the lane from `improved` refuses the corrupted (s, y)
        # correction pair
        finite_new = finite_state(f_new, g_new)
        improved = ls_ok & (f_new < s.f) & finite_new

        # history update (only when improved)
        s_vec = w_new - s.w
        y_vec = g_new - s.g
        sy = _vdot(s_vec, y_vec)
        store = improved & (sy > 1e-10 * _norm(y_vec) ** 2)
        keep = s.done
        if batched:
            # shared circular cursor: every iteration writes the slot for all
            # lanes; a lane that must not store marks its pair invalid with
            # rho = 0 (the two-loop weights every history term by rho, so an
            # invalid pair contributes exactly nothing, and the gamma guard
            # falls back to 1 on all-zero newest pairs)
            S = s.S.at[s.head].set(jnp.where(store, s_vec, 0.0))
            Y = s.Y.at[s.head].set(jnp.where(store, y_vec, 0.0))
            rho = s.rho.at[s.head].set(
                jnp.where(store, 1.0 / jnp.where(sy != 0, sy, 1.0), 0.0)
            )
            head = (s.head + 1) % m
            count = jnp.minimum(s.count + 1, m)
        else:
            S = jnp.where(store, s.S.at[s.head].set(as_pair(s_vec)), s.S)
            Y = jnp.where(store, s.Y.at[s.head].set(as_pair(y_vec)), s.Y)
            rho = jnp.where(
                store, s.rho.at[s.head].set(1.0 / jnp.where(sy != 0, sy, 1.0)), s.rho
            )
            head = jnp.where(store & ~keep, (s.head + 1) % m, s.head)
            count = jnp.where(store & ~keep, jnp.minimum(s.count + 1, m), s.count)
            S = pinned(jnp.where(keep, s.S, S))
            Y = pinned(jnp.where(keep, s.Y, Y))
            rho = jnp.where(keep, s.rho, rho)

        evals = zeroed = None
        if counted:
            # one evaluation for every trial the search judged
            evals = jnp.where(keep, s.evals, s.evals + ls_trials)
        if owlqn:
            crossed = ((s.w + t_new * direction) * orthant < 0) & improved & ~keep
            zeroed = s.zeroed + jnp.sum(crossed, axis=0, dtype=jnp.int32)
        z_out = matvecs = rmatvecs = None
        if walk:
            z_out = jnp.where(improved & ~keep, z_new, s.z)
            if counted:
                # direction_margins and grad_from_margins, once each
                matvecs = jnp.where(keep, s.matvecs, s.matvecs + 1)
                rmatvecs = jnp.where(keep, s.rmatvecs, s.rmatvecs + 1)

        it_new = s.it + 1
        pg_new = effective_grad(w_new, g_new)
        reason = check_convergence(
            it_new,
            max_iterations,
            f_new,
            s.f,
            _norm(pg_new),
            loss_abs_tol,
            grad_abs_tol,
            objective_not_improving=~improved,
            diverged=~finite_new,
        )
        newly_done = reason != 0

        # masked commit: frozen lanes keep their state
        sel = lambda a, b: jnp.where(keep, a, b)
        w_out = sel(s.w, jnp.where(improved, w_new, s.w))
        f_out = sel(s.f, jnp.where(improved, f_new, s.f))
        g_out = sel(s.g, jnp.where(improved, g_new, s.g))
        it_out = jnp.where(keep, s.it, it_new)
        # history writes go at the global counter row (active lanes all sit at
        # it == k): a row-mask select handles per-lane freezing without
        # per-lane scatter indices
        k_new = s.k + 1
        row = (
            jnp.arange(max_iterations + 1) == k_new
        ).reshape((max_iterations + 1,) + (1,) * len(lanes))
        write = row & ~keep
        lh = jnp.where(write, f_out, s.loss_history)
        gh = jnp.where(write, _norm(effective_grad(w_out, g_out)), s.grad_norm_history)

        return _LBFGSState(
            w=w_out,
            f=f_out,
            g=g_out,
            it=it_out,
            k=k_new,
            done=keep | newly_done,
            reason=jnp.where(keep, s.reason, reason).astype(jnp.int32),
            S=S,
            Y=Y,
            rho=rho,
            count=count,
            head=head,
            loss_history=lh,
            grad_norm_history=gh,
            evals=evals,
            zeroed=zeroed,
            z=z_out,
            matvecs=matvecs,
            rmatvecs=rmatvecs,
        )

    final = jax.lax.while_loop(cond, body, init)
    pg_final = effective_grad(final.w, final.g)
    return SolverResult(
        coefficients=final.w,
        loss=final.f,
        gradient=pg_final,
        iterations=final.it,
        reason=final.reason,
        loss_history=final.loss_history,
        grad_norm_history=final.grad_norm_history,
        cg_iterations=jnp.zeros_like(final.it),
        line_search_evals=final.evals,
        orthant_zeroed=final.zeroed,
        nonzeros=jnp.sum(final.w != 0, axis=0, dtype=jnp.int32) if owlqn else None,
        matvecs=final.matvecs,
        rmatvecs=final.rmatvecs,
    )


def walks_margins(margins, l1_weight: float, box_constraints, batched: bool) -> bool:
    """Whether a solve's line search walks margins (``_margin_search``) or
    evaluates points (``_line_search``). Only plain one-lane L-BFGS can walk:
    OWL-QN projects each trial point onto an orthant and L-BFGS-B clips it to
    the box, so their margins are not affine in the step length; the packed
    lanes keep the search their rounding was tuned on (PERF.md, PR 35)."""
    return (
        margins is not None
        and not float(l1_weight) > 0.0
        and box_constraints is None
        and not batched
    )


def solve_lbfgs(
    value_and_grad: ValueAndGradFn,
    w0: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    max_iterations: int = 100,
    num_corrections: int = 10,
    l1_weight: float = 0.0,
    box_constraints: Optional[Tuple[Array, Array]] = None,
    max_line_search_iterations: int = 25,
    batched: bool = False,
    count_evals: bool = False,
    margins: Optional[MarginFns] = None,
) -> SolverResult:
    """Minimize f(w) (+ l1*||w||_1 when ``l1_weight`` > 0) starting at w0.

    ``l1_weight`` is a host number: positive selects the OWL-QN program and
    enters it as an operand (every positive weight runs the same compiled
    solver), zero selects plain L-BFGS, whose program has no l1 operand.

    ``value_and_grad`` must be a pure fn of w (closing over its batch); the
    absolute tolerances come from :func:`photon_ml_tpu.optimize.common.abs_tolerances`.

    ``batched=True`` solves an entity-minor stack of independent problems in
    lockstep: ``w0`` is ``[d, E]``, ``value_and_grad`` maps ``[d, E] ->
    ([E], [d, E])``, and the tolerances are per-lane ``[E]``.

    ``count_evals=True`` makes a plain L-BFGS solve carry the count of its
    objective evaluations as an OWL-QN solve always does
    (``SolverResult.line_search_evals``); the default leaves the plain
    program as it was, counter-free (the random effects' packed solves).

    A one-lane ``w0`` placed split over a mesh axis (``state_partition``)
    keeps every coefficient-length array of the solve, the history included,
    split as it is.

    ``margins`` are the same objective as its steps (``MarginFns``: its
    margins are affine in w). A solve that can walk them does
    (:func:`walks_margins`) and never calls ``value_and_grad``; any other
    solve never sees them. A counting walk also reports its passes over the
    features (``SolverResult.matvecs`` / ``rmatvecs``).
    """
    has_box = box_constraints is not None
    zero = jnp.zeros_like(w0)
    lower, upper = box_constraints if has_box else (zero, zero)
    # the solver KIND is chosen here, from a host value; the weight is data
    l1 = jnp.asarray(l1_weight, w0.dtype) if float(l1_weight) > 0.0 else None
    result = _solve(
        as_partial(value_and_grad),
        w0,
        jnp.asarray(loss_abs_tol, w0.dtype),
        jnp.asarray(grad_abs_tol, w0.dtype),
        max_iterations,
        num_corrections,
        l1,
        max_line_search_iterations,
        has_box,
        lower,
        upper,
        batched,
        count_evals,
        MarginFns(*map(as_partial, margins))
        if walks_margins(margins, l1_weight, box_constraints, batched)
        else None,
        None if batched else state_partition(w0),
    )
    obs.record_solver_metrics("lbfgs", result)
    return result
