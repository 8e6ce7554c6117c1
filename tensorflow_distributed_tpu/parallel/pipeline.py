"""Pipeline parallelism: GPipe microbatch schedule over the "pipe" axis.

The reference has no pipeline parallelism (single-stage model,
SURVEY.md §2b checklist) — this is a beyond-reference capability,
designed TPU-first rather than ported:

- Layer stacks live as ONE stacked pytree (leaves [S, ...], leading dim
  sharded over the "pipe" mesh axis) instead of per-stage modules —
  XLA sees one program, each device holding its stage's slice.
- The schedule is a ``lax.scan`` over T = M + S - 1 ticks inside a
  ``shard_map`` restricted to the pipe axis (``axis_names={"pipe"}``),
  so data/tensor/sequence sharding of the activations continues to be
  handled by the surrounding GSPMD partitioner.
- Activations hop stage s -> s+1 once per tick via ``lax.ppermute`` —
  neighbor ICI traffic, the TPU-native analog of NCCL P2P send/recv.
- GPipe bubble ticks compute on garbage and are masked with
  ``jnp.where`` — a MEASURED choice, not an oversight: wrapping the
  stage in ``lax.cond`` and letting AD differentiate through it was
  tried and is SLOWER (2332 vs 1746 ms/step at S=4, M=4 on the 8-way
  CPU mesh — cond blocks fusion and complicates the scan's saved
  residuals on the AD path). Bubble fraction is the standard
  (S-1)/(M+S-1). The 1F1B schedule below DOES skip bubble work with
  real ``lax.cond`` branches — its backward is hand-rolled, so
  nothing ADs through the cond. Same S=4/M=4 measurement: 1F1B went
  2729 (old where-masked form) -> 831 ms/step (3.3x), which also puts
  it 2.1x ahead of GPipe's 1746 ms — hence 1f1b is the config
  default.

Everything is differentiable: the backward pipeline falls out of AD
(scan reverses, ppermute transposes to the opposite rotation).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tensorflow_distributed_tpu.parallel.mesh import AXIS_PIPE


def pipeline_apply(stage_fn: Callable[..., jax.Array],
                   stage_params: Any, x: jax.Array, mesh: Mesh,
                   num_microbatches: int,
                   rng: Any = None, stage_aux: bool = False):
    """Run ``x`` through S pipeline stages with an M-microbatch schedule.

    stage_params: pytree whose leaves have leading dim S (sharded
    ``P("pipe")``); ``stage_fn(one_stage_params, x_mb) -> y_mb`` must
    preserve the microbatch shape (a transformer block stack does).
    x: [B, ...] with B % num_microbatches == 0. Returns [B, ...].

    ``rng``: optional PRNG key for in-stage dropout. When given,
    stage_fn is called as ``stage_fn(params, x_mb, key)`` with a key
    folded over (microbatch, stage) so no two (mb, stage) pairs share
    masks; bubble ticks reuse a clipped mb index (their output is
    masked out at commit, so their mask content is irrelevant).

    ``stage_aux``: when True, stage_fn returns ``(y_mb, aux)`` with
    ``aux`` a pytree of scalars (e.g. MoE router losses); bubble-tick
    aux is masked out and the call returns ``(out, aux_sums)`` where
    aux_sums are summed over all (stage, microbatch) pairs —
    differentiable, so AD through this schedule back-propagates router
    losses too.
    """
    S = mesh.shape[AXIS_PIPE]
    M = num_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if M < S:
        raise ValueError(f"need microbatches >= stages ({M} < {S})")
    mb = B // M

    def per_pipe(params, x):
        # Local leaves arrive [1, ...] (this stage's slice); drop the
        # stage dim.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        s = jax.lax.axis_index(AXIS_PIPE)
        xm = x.reshape(M, mb, *x.shape[1:])
        perm = [(i, (i + 1) % S) for i in range(S)]

        def run_stage(t, inp):
            if rng is None:
                out = stage_fn(params, inp)
            else:
                key = jax.random.fold_in(
                    jax.random.fold_in(rng, jnp.clip(t - s, 0, M - 1)), s)
                out = stage_fn(params, inp, key)
            return out if stage_aux else (out, ())

        if stage_aux:
            aux0 = jax.eval_shape(lambda: run_stage(0, xm[0])[1])
            aux0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), aux0)
        else:
            aux0 = ()

        def tick(carry, t):
            state, outs, aux_acc = carry
            # Stage 0 ingests microbatch t; later stages eat the
            # activation their neighbor pushed last tick.
            feed = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            y, aux = run_stage(t, jnp.where(s == 0, feed, state))
            # Stage s does real work for microbatch t - s only.
            valid = jnp.logical_and(t - s >= 0, t - s < M)
            aux_acc = jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(valid, b, 0), aux_acc, aux)
            # The last stage commits finished microbatch t-(S-1).
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, oidx, 0,
                                                keepdims=False)
            write = jnp.logical_and(s == S - 1, t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, prev), oidx, 0)
            return (jax.lax.ppermute(y, AXIS_PIPE, perm), outs,
                    aux_acc), None

        outs0 = jnp.zeros_like(xm)
        (_, outs, aux_acc), _ = jax.lax.scan(
            tick, (jnp.zeros_like(xm[0]), outs0, aux0),
            jnp.arange(M + S - 1))
        # Stage-stacked output: only the last stage's slice is real.
        # Aux is real on EVERY stage; psum totals it over the pipe.
        aux_tot = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, AXIS_PIPE), aux_acc)
        return outs.reshape(B, *x.shape[1:])[None], aux_tot

    out, aux = jax.shard_map(
        per_pipe, mesh=mesh, axis_names={AXIS_PIPE},
        in_specs=(P(AXIS_PIPE), P()),
        out_specs=(P(AXIS_PIPE), P()),
        check_vma=False)(stage_params, x)
    return (out[-1], aux) if stage_aux else out[-1]


def bubble_fraction(num_microbatches: int, num_stages: int,
                    schedule: str = "gpipe") -> float:
    """Fraction of stage-ticks spent idle (computing masked garbage).

    gpipe: the classic (S-1)/(M+S-1) over M+S-1 forward ticks (the
    backward pipeline mirrors it under AD). 1f1b: the paired
    fwd+bwd schedule runs M + 2(S-1) tick pairs, of which 2(S-1) are
    ramp-up/drain bubbles.

    On interleaved (virtual-stage) schedules — analyzed across rounds
    3-4, IMPLEMENTED for correctness in round 5
    (``interleaved_pipeline_value_and_grad``; the [S, V, lps] layout,
    [S*V]-deep virtual ring, parity-pinned in
    tests/test_pipeline_1f1b.py). The analysis stands and the
    implementation embodies it: every schedule here is a lockstep
    ``lax.scan`` whose tick runs one fwd + one bwd slot per (device,
    chunk) between ppermutes, so wall time is ticks x slot time
    regardless of which devices' slots are cond-skipped. Folding V
    chunk-columns per device makes the chunk round-robin pipe SV
    chunks deep with MV chunk-jobs per device: utilization
    MV/(MV + 2(SV-1)) — STRICTLY WORSE than the plain M/(M + 2(S-1))
    for V > 1 (M=8, S=4: 57% plain, 53% at V=2). Megatron's bubble/V
    win does not come from interleaving alone but from its ASYMMETRIC
    grouped schedule: warmup ticks run fwd-ONLY chunk bursts (up to
    S-1+2(V-1) forwards queued per device before the first backward)
    so ramp chunks overlap useful steady-state work — a schedule a
    uniform one-fwd-one-bwd tick cannot express. Expressing it would
    need per-tick static slot tables driving variable work per tick;
    on this hardware (single-chip S=1 — no bubble at all, PARITY.md)
    the asymmetric form buys nothing measurable, so the uniform-tick
    implementation is the correctness vehicle and the schedule-level
    A/B is an owed on-chip measurement. What DOES pay, and IS
    implemented, is making bubble half-ticks free:
    pipeline_value_and_grad's tick wraps each half in a real
    ``lax.cond`` (possible because its backward is hand-rolled —
    nothing ADs through the cond), skipping ramp/drain garbage compute
    instead of where-masking it. Measured 3.3x per-step at S=4, M=4
    (see module docstring); the reported 2(S-1)/(M+2(S-1)) fraction
    remains the SLOT accounting — the skipped slots now cost ~0 time
    rather than a full stage pass. (Exception: stages carrying seq
    collectives run where-masked — the ``bubble`` switch — because a
    collective under per-pipe-rank control flow is not SPMD-legal.)"""
    M, S = num_microbatches, num_stages
    if schedule == "gpipe":
        return (S - 1) / (M + S - 1)
    if schedule == "1f1b":
        return 2 * (S - 1) / (M + 2 * (S - 1))
    raise ValueError(f"schedule {schedule!r}; have ('gpipe', '1f1b')")


def variant_residual_mask(res_fn: Callable[[Any, jax.Array, jax.Array],
                                           list],
                          params: Any, x0: jax.Array) -> list:
    """Which vjp-residual leaves actually vary per microbatch?

    ``res_fn(params, x_mb, m) -> flat residual leaves`` (m: the
    microbatch index that seeds dropout keys). Returns a bool per leaf:
    True = depends on (x_mb, m) and must be ring-buffered per in-flight
    microbatch; False = a pure function of the stage params (weight
    matrices and their compute-dtype casts — the transpose operands
    ``jax.vjp`` captures alongside the activations), identical for
    every microbatch, so the stash backward computes it ONCE per step
    instead of storing D copies.

    The split is read off the jaxpr: seed the variant set with the
    (x, m) input vars and propagate — any equation consuming a variant
    var marks all its outputs variant. Call/scan/cond/remat equations
    are handled at the equation level, i.e. conservatively: a false
    positive only stashes more than needed, never corrupts a gradient.
    """
    flat_p, tree_p = jax.tree_util.tree_flatten(params)
    n_p = len(flat_p)

    def flat_fn(*args):
        p = jax.tree_util.tree_unflatten(tree_p, args[:n_p])
        return res_fn(p, args[n_p], args[n_p + 1])

    from jax.extend.core import Literal

    closed = jax.make_jaxpr(flat_fn)(*flat_p, x0, jnp.int32(0))
    jaxpr = closed.jaxpr
    variant = set(jaxpr.invars[n_p:])  # the x and m vars
    for eqn in jaxpr.eqns:
        if any(not isinstance(v, Literal) and v in variant
               for v in eqn.invars):
            variant.update(eqn.outvars)
    return [not isinstance(v, Literal) and v in variant
            for v in jaxpr.outvars]


def split_by_mask(leaves, mask):
    """(variant_leaves, const_leaves) per the bool mask — the single
    inverse pair with merge_by_mask; all stash bookkeeping goes
    through these two so the pairing can't drift."""
    if len(leaves) != len(mask):
        raise AssertionError(f"{len(leaves)} leaves vs {len(mask)} mask")
    return ([l for l, v in zip(leaves, mask) if v],
            [l for l, v in zip(leaves, mask) if not v])


def merge_by_mask(variant_leaves, const_leaves, mask):
    """Inverse of split_by_mask: reassemble the full leaf list."""
    vs, cs = iter(variant_leaves), iter(const_leaves)
    out = [next(vs) if v else next(cs) for v in mask]
    for leftover in (vs, cs):
        if next(leftover, None) is not None:
            raise AssertionError("leaf count mismatch in merge_by_mask")
    return out


def _select_tree(pred, new, old):
    """``jnp.where`` over matching pytrees — the single predication
    primitive for the ``bubble="where"`` paths (one implementation so
    every select site in both schedules stays in lockstep)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(pred, n, o), new, old)


def pipeline_value_and_grad(stage_fn: Callable[..., jax.Array],
                            last_fn: Callable[[Any, jax.Array, Any],
                                              tuple],
                            stage_params: Any, last_params: Any,
                            x: jax.Array, aux: Any, mesh: Mesh,
                            num_microbatches: int, rng: Any = None,
                            cotangent_scale: Any = 1.0,
                            stage_aux_cotangent: Any = None,
                            backward: str = "recompute",
                            bubble: str = "cond"):
    """1F1B pipeline: hand-scheduled forward AND backward in one pass.

    GPipe (``pipeline_apply`` + outer AD) must finish every forward
    before the first backward, so each stage holds O(M) microbatch
    residuals. Here backward for microbatch m starts as soon as m
    clears the last stage — the per-microbatch loss (``last_fn``) is
    computed AT the last stage inside the schedule, seeding the
    cotangent that flows back up the ring while later microbatches are
    still flowing down. Peak per-stage state is the input stash of
    depth min(2S, M) — INDEPENDENT of M — plus the gradient
    accumulators; backward ticks recompute the stage forward from the
    stashed input (jax.vjp), the same trade per-stage remat makes.

    Schedule: T = M + 2(S-1) tick pairs; at tick t stage s runs
    forward for microbatch t - s and backward for t - 2(S-1) + s (when
    in range). The last stage's backward of microbatch m lands on the
    same tick as its forward. Bubble half-ticks are SKIPPED with real
    ``lax.cond`` branches (safe here precisely because the backward is
    hand-rolled — nothing ADs through the cond), so ramp/drain costs
    ~no compute; skip branches return exact zeros, which is what the
    plain-add accumulators rely on. Per tick each stage ppermutes its
    activation DOWN the ring and its input-cotangent UP — neighbor ICI
    traffic both ways.

    Interfaces:
      stage_fn(params, x_mb[, key]) -> y_mb       (same as pipeline_apply)
      last_fn(last_params, y_mb, aux_mb) -> (scalar_sum, metrics_sums)
        — UNNORMALIZED per-microbatch sums; the caller normalizes.
      aux: pytree with leading dim B (targets, masks, ...), microbatch-
        sliced alongside x.
      cotangent_scale: seed for d(scalar_sum) — e.g. 1/total_mask so
        the accumulated grads equal the mean-loss grads exactly.

    Returns (value_sum, metrics_sums, (d_stage_params, d_last_params,
    d_x)) — d_stage_params stage-stacked [S, ...] like stage_params,
    d_x [B, ...] (feeds the embedding vjp outside).

    ``stage_aux_cotangent``: when not None, stage_fn returns
    ``(y_mb, aux)`` (aux a pytree of scalars — MoE router losses) and
    this argument is the matching pytree of objective weights: each
    backward tick seeds the stage vjp with (d_y, stage_aux_cotangent),
    so router-loss gradients flow into both the stage params and the
    upstream activations exactly like any other loss term. The return
    grows a 4th element: aux sums over all (stage, microbatch) pairs
    — (value_sum, metrics_sums, aux_sums, grads).

    ``backward``: what each stage stashes between a microbatch's
    forward and backward ticks.
      "recompute" (default) — stash the stage INPUT; the backward tick
        re-runs the stage forward under jax.vjp to rebuild residuals.
        Minimal memory (D copies of one activation), but every
        microbatch pays the stage forward twice: 4x forward-equivalent
        FLOPs per token instead of AD's 3x — read as the dominant
        pipelined-MFU cost on chip in round 4 (24.8% vs 46.5%
        unpipelined at matched shapes under jax 0.4.37; not
        re-measured, PERF.md "Before the benchmark").
      "stash" — run jax.vjp at the FORWARD tick and stash the vjp
        residuals themselves: ``jax.vjp``'s pulled-back function is a
        ``jax.tree_util.Partial`` — a pytree — so its leaves stash
        into per-slot ring buffers like any activation, and the
        backward tick re-attaches them to the (static) treedef
        obtained via ``jax.eval_shape`` — no recompute, Megatron's
        default memory/compute trade. Ring-buffered leaves are only
        the MICROBATCH-VARIANT residuals: ``variant_residual_mask``
        reads the residual jaxpr and splits out the leaves that are a
        pure function of params (the stage weight matrices and their
        compute-dtype casts, which jax.vjp captures as transpose
        operands) — those are computed once per step instead of D
        copies per ring. Before this hoist, the weight copies
        dominated stash's HBM traffic and made it measurably SLOWER
        than recompute on v5e at GPT-2-small shapes (19.9% vs 30.8%
        MFU, PARITY.md) — that measurement predates the hoist and is
        owed a re-run; stash stays opt-in until it's re-measured.

    ``bubble``: how ramp/drain slots are suppressed.
      "cond" (default) — real ``lax.cond`` branches skip the bubble
        compute entirely (the measured 3.3x win, module docstring).
        REQUIRES the stage to contain no cross-device collectives:
        the predicate varies per pipe rank, and XLA SPMD cannot honor
        a collective under non-uniform control flow — with ring
        attention's seq-ppermutes inside the branch this silently
        computes garbage (measured: wrong loss, NaN under learned
        pos-emb, on the virtual mesh).
      "where" — compute every slot and mask the results (the GPipe-
        style predication this schedule used before round 4): bubble
        slots cost a full stage pass, but every collective executes
        unconditionally on every rank. train.pipeline_step selects
        this automatically when mesh.seq > 1 routes the stage through
        ring attention.
    """
    S = mesh.shape[AXIS_PIPE]
    M = num_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if M < S:
        raise ValueError(f"need microbatches >= stages ({M} < {S})")
    if bubble not in ("cond", "where"):
        raise ValueError(f"bubble {bubble!r}; have ('cond', 'where')")
    if backward not in ("recompute", "stash"):
        raise ValueError(f"backward {backward!r}; "
                         "have ('recompute', 'stash')")
    stash_residuals = backward == "stash"
    mb = B // M
    D = min(2 * S, M)  # stash depth >= max in-flight (2S - 1)

    def per_pipe(params, last_p, x, aux, scale):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        s = jax.lax.axis_index(AXIS_PIPE)
        xm = x.reshape(M, mb, *x.shape[1:])
        auxm = jax.tree_util.tree_map(
            lambda a: a.reshape(M, mb, *a.shape[1:]), aux)
        down = [(i, (i + 1) % S) for i in range(S)]
        up = [((i + 1) % S, i) for i in range(S)]
        is_last = s == S - 1

        aux_on = stage_aux_cotangent is not None

        def with_key(m):
            if rng is None:
                fn = lambda p, xx: stage_fn(p, xx)  # noqa: E731
            else:
                key = jax.random.fold_in(jax.random.fold_in(rng, m), s)
                fn = lambda p, xx: stage_fn(p, xx, key)  # noqa: E731
            # Normalize to (y, aux) so forward/backward share one shape.
            return fn if aux_on else (lambda p, xx: (fn(p, xx), ()))

        def head(m, y):
            aux_mb = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, m, 0, keepdims=False), auxm)
            val, vjp_fn, met = jax.vjp(
                lambda lp, yy: last_fn(lp, yy, aux_mb), last_p, y,
                has_aux=True)
            dlast, dy = vjp_fn(jnp.asarray(scale, val.dtype))
            return val, met, dlast, dy

        zero_dp = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero_dlast = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), last_p)
        if aux_on:
            aux_abs = jax.eval_shape(
                lambda: with_key(0)(params, xm[0])[1])
            zero_aux = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), aux_abs)
            aux_seed = jax.tree_util.tree_map(
                lambda w, a: jnp.asarray(w, a.dtype),
                stage_aux_cotangent, zero_aux)
        else:
            zero_aux, aux_seed = (), ()
        met_abs = jax.eval_shape(
            lambda lp, yy, am: last_fn(lp, yy, am)[1], last_p, xm[0],
            jax.tree_util.tree_map(lambda a: a[0], auxm))
        zero_met = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), met_abs)

        zero_dp_step = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), params)

        if stash_residuals:
            # The vjp pullback is a Partial — a pytree. Abstract-trace
            # it once for the (static) treedef + residual shapes; the
            # treedef is microbatch-invariant (tracing is shape-based;
            # the dropout key's VALUE lives in the stashed leaves, so
            # the right fwd-tick masks reach the backward).
            vjp_abs = jax.eval_shape(
                lambda p, xx: jax.vjp(with_key(jnp.int32(0)), p, xx)[1],
                params, xm[0])
            res_treedef = jax.tree_util.tree_structure(vjp_abs)
            abs_leaves = jax.tree_util.tree_leaves(vjp_abs)
            # Ring-buffer only the leaves that actually vary per
            # microbatch. The rest — the stage weights and their
            # compute-dtype casts, which jax.vjp captures as transpose
            # operands — are a pure function of params: compute them
            # ONCE per step instead of storing D copies (at GPT-scale
            # stages the weight copies dominated the stash's HBM
            # traffic and made it lose to recompute, PARITY.md).
            res_mask = variant_residual_mask(
                lambda p, xx, m: jax.tree_util.tree_leaves(
                    jax.vjp(with_key(m), p, xx)[1]),
                params, xm[0])
            if all(res_mask):
                const_leaves = []
            else:
                # x enters as zeros; every computation feeding only the
                # discarded variant outputs is dead code XLA removes,
                # so this costs the casts, not a stage forward.
                res0 = jax.vjp(with_key(jnp.int32(0)), params,
                               jnp.zeros_like(xm[0]))[1]
                _, const_leaves = split_by_mask(
                    jax.tree_util.tree_leaves(res0), res_mask)
            variant_abs, _ = split_by_mask(abs_leaves, res_mask)
            stash0 = tuple(
                jnp.zeros((D,) + l.shape, l.dtype) for l in variant_abs)
        else:
            stash0 = jnp.zeros((D,) + xm[0].shape, xm.dtype)

        def tick(carry, t):
            (fwd_msg, bwd_msg, stash, dp_acc, dlast_acc, dx_buf,
             val_acc, met_acc, aux_acc) = carry

            # ---- forward half: stage s runs microbatch t - s.
            # REAL branch (lax.cond), not where-masking: a ramp/drain
            # tick whose forward slot is a bubble SKIPS the stage
            # compute instead of computing on garbage and masking the
            # result — the 2(S-1)-tick bubble costs half the naive
            # predicated schedule's wall clock.
            mf = t - s
            mf_valid = jnp.logical_and(mf >= 0, mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            inp = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(xm, mf_c, 0, keepdims=False),
                fwd_msg)

            def fwd_run(inp, stash):
                slot = jnp.mod(mf_c, D)
                if stash_residuals:
                    (y, aux_v), vjp_fn = jax.vjp(with_key(mf_c), params,
                                                 inp)
                    # strict: a residual-structure drift between the
                    # eval_shape template and this trace must fail
                    # loudly, not silently stash stale zeros.
                    vleaves, _ = split_by_mask(
                        jax.tree_util.tree_leaves(vjp_fn), res_mask)
                    stash = tuple(
                        jax.lax.dynamic_update_index_in_dim(sb, l, slot, 0)
                        for sb, l in zip(stash, vleaves, strict=True))
                    return y, aux_v, stash
                y, aux_v = with_key(mf_c)(params, inp)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, inp, slot, 0)
                return y, aux_v, stash

            def fwd_skip(inp, stash):
                return jnp.zeros_like(inp), zero_aux, stash

            if bubble == "cond":
                y, aux_v, stash = jax.lax.cond(mf_valid, fwd_run,
                                               fwd_skip, inp, stash)
            else:
                # "where": run unconditionally (collectives inside the
                # stage execute on every rank), select the results.
                y_r, aux_r, stash_r = fwd_run(inp, stash)
                y = _select_tree(mf_valid, y_r, jnp.zeros_like(inp))
                aux_v = _select_tree(mf_valid, aux_r, zero_aux)
                stash = _select_tree(mf_valid, stash_r, stash)
            # Skipped slots contribute exact zeros — plain adds suffice.
            aux_acc = jax.tree_util.tree_map(
                lambda a, b: a + b, aux_acc, aux_v)

            # ---- last-stage loss + cotangent seed for the SAME tick's
            # backward. Branch on (is_last AND valid): non-last stages
            # no longer pay the head's vocab matmul every tick.
            take_head = jnp.logical_and(is_last, mf_valid)

            def head_run(y):
                return head(mf_c, y)

            def head_skip(y):
                return (jnp.zeros((), jnp.float32), zero_met,
                        zero_dlast, jnp.zeros_like(y))

            if bubble == "cond":
                hval, hmet, hdlast, hdy = jax.lax.cond(
                    take_head, head_run, head_skip, y)
            else:
                hval, hmet, hdlast, hdy = _select_tree(
                    take_head, head_run(y),
                    (jnp.zeros((), jnp.float32), zero_met, zero_dlast,
                     jnp.zeros_like(y)))
            val_acc = val_acc + hval
            met_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), met_acc, hmet)
            dlast_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), dlast_acc, hdlast)

            # ---- backward half: stage s runs microbatch t-2(S-1)+s,
            # same real-branch treatment.
            mbk = t - 2 * (S - 1) + s
            b_valid = jnp.logical_and(mbk >= 0, mbk < M)
            mb_c = jnp.clip(mbk, 0, M - 1)

            def bwd_run(stash, hdy, bwd_msg):
                slot = jnp.mod(mb_c, D)
                cot = jnp.where(is_last, hdy, bwd_msg)
                if stash_residuals:
                    stashed = [
                        jax.lax.dynamic_index_in_dim(sb, slot, 0,
                                                     keepdims=False)
                        for sb in stash]
                    vjp_fn = jax.tree_util.tree_unflatten(
                        res_treedef,
                        merge_by_mask(stashed, const_leaves, res_mask))
                    return vjp_fn((cot.astype(xm.dtype), aux_seed))
                x_saved = jax.lax.dynamic_index_in_dim(
                    stash, slot, 0, keepdims=False)
                _, vjp_fn = jax.vjp(with_key(mb_c), params, x_saved)
                return vjp_fn((cot.astype(x_saved.dtype), aux_seed))

            def bwd_skip(stash, hdy, bwd_msg):
                return zero_dp_step, jnp.zeros_like(xm[0])

            if bubble == "cond":
                dp, dx = jax.lax.cond(b_valid, bwd_run, bwd_skip,
                                      stash, hdy, bwd_msg)
            else:
                dp, dx = _select_tree(
                    b_valid, bwd_run(stash, hdy, bwd_msg),
                    (zero_dp_step, jnp.zeros_like(xm[0])))
            dp_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), dp_acc, dp)
            take_dx = jnp.logical_and(b_valid, s == 0)
            prev_dx = jax.lax.dynamic_index_in_dim(dx_buf, mb_c, 0,
                                                   keepdims=False)
            dx_buf = jax.lax.dynamic_update_index_in_dim(
                dx_buf, jnp.where(take_dx, dx.astype(dx_buf.dtype),
                                  prev_dx), mb_c, 0)

            # ---- ring hops: activations down, cotangents up.
            if S > 1:
                fwd_msg = jax.lax.ppermute(y, AXIS_PIPE, down)
                bwd_msg = jax.lax.ppermute(dx, AXIS_PIPE, up)
            return (fwd_msg, bwd_msg, stash, dp_acc, dlast_acc, dx_buf,
                    val_acc, met_acc, aux_acc), None

        zero_x = jnp.zeros_like(xm[0])
        carry0 = (zero_x, zero_x, stash0,
                  zero_dp, zero_dlast,
                  jnp.zeros((M,) + xm[0].shape, x.dtype),
                  jnp.zeros((), jnp.float32), zero_met, zero_aux)
        T = M + 2 * (S - 1)
        (_, _, _, dp_acc, dlast_acc, dx_buf, val_acc, met_acc,
         aux_acc), _ = jax.lax.scan(tick, carry0, jnp.arange(T))

        # Only the owning stage holds real values for dlast (last
        # stage), dx/val/metrics (stage 0 / last) — everyone else holds
        # zeros, so a pipe-psum replicates the true values. Stage aux is
        # real on EVERY stage; its psum is the total over stages.
        dlast_acc = jax.lax.psum(dlast_acc, AXIS_PIPE)
        dx_out = jax.lax.psum(dx_buf, AXIS_PIPE).reshape(B, *x.shape[1:])
        val_acc = jax.lax.psum(val_acc, AXIS_PIPE)
        met_acc = jax.lax.psum(met_acc, AXIS_PIPE)
        aux_out = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, AXIS_PIPE), aux_acc)
        dp_out = jax.tree_util.tree_map(lambda g: g[None], dp_acc)
        return dp_out, dlast_acc, dx_out, val_acc, met_acc, aux_out

    dp, dlast, dx, val, met, aux_sums = jax.shard_map(
        per_pipe, mesh=mesh, axis_names={AXIS_PIPE},
        in_specs=(P(AXIS_PIPE), P(), P(), P(), P()),
        out_specs=(P(AXIS_PIPE), P(), P(), P(), P(), P()),
        check_vma=False)(stage_params, last_params, x, aux,
                         cotangent_scale)
    if stage_aux_cotangent is not None:
        return val, met, aux_sums, (dp, dlast, dx)
    return val, met, (dp, dlast, dx)


def interleaved_pipeline_value_and_grad(
        stage_fn: Callable[..., jax.Array],
        last_fn: Callable[[Any, jax.Array, Any], tuple],
        stage_params: Any, last_params: Any,
        x: jax.Array, aux: Any, mesh: Mesh,
        num_microbatches: int, virtual_stages: int, rng: Any = None,
        cotangent_scale: Any = 1.0, stage_aux_cotangent: Any = None,
        bubble: str = "cond"):
    """Interleaved (virtual-stage) 1F1B: Megatron's chunked layout.

    Each device owns V model CHUNKS instead of one contiguous stage:
    virtual stage j = v*S + s (chunk v on device s) holds layers
    [j*lps, (j+1)*lps) with lps = L/(S*V) — stage_params leaves are
    [S, V, lps, ...] (stack_stage_params with ``virtual``). A
    microbatch crosses the ring V times; because consecutive virtual
    stages j, j+1 sit on consecutive devices (j+1 lives on
    (s+1) mod S), every hop is still the one-position-down ppermute —
    the V in-flight activations ride as ONE stacked [V, ...] message,
    and the ring wrap (device S-1 -> 0) shifts chunk slot v -> v+1
    (``jnp.roll`` on the chunk dim, device-0 side).

    Schedule: the uniform one-chunk-fwd + one-chunk-bwd-per-slot tick
    over T = M + 2(S*V - 1) ticks; at tick t virtual stage j runs
    forward for microbatch t - j and backward for t - 2(S*V-1) + j,
    each slot a real ``lax.cond`` (the V slots per device are
    compile-time unrolled — V is small and static). The loss head
    fires at j = S*V - 1 (chunk V-1, device S-1), seeding the same
    tick's backward exactly like the plain schedule. Utilization of
    this uniform tick form is MV/(MV + 2(SV-1)) — STRICTLY WORSE than
    plain 1F1B's M/(M + 2(S-1)) for V > 1 (bubble_fraction's analysis,
    measured assumptions unchanged); what V buys in Megatron is the
    asymmetric fwd-burst warmup this lockstep scan cannot express.
    This implementation exists for CORRECTNESS of the [S, V, lps]
    regrouping — schedule-level wins stay an explicitly-owed
    measurement (PARITY.md). Backward is "recompute" only (the stash
    variant's per-chunk residual treedefs are a follow-up; recompute
    is the measured-on-chip default).

    Same contract as pipeline_value_and_grad otherwise (including the
    ``bubble`` cond/where predication switch — "where" when the stage
    carries seq collectives); d_stage_params comes back
    [S, V, lps, ...] like stage_params.
    """
    S = mesh.shape[AXIS_PIPE]
    V = virtual_stages
    Sv = S * V
    M = num_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if M < Sv:
        raise ValueError(f"need microbatches >= virtual stages "
                         f"({M} < {Sv} = {S} stages x {V} chunks)")
    if bubble not in ("cond", "where"):
        raise ValueError(f"bubble {bubble!r}; have ('cond', 'where')")
    mb = B // M
    D = min(2 * Sv, M)  # stash depth per chunk >= max in-flight

    def per_pipe(params, last_p, x, aux, scale):
        params = jax.tree_util.tree_map(lambda p: p[0], params)  # [V,...]
        s = jax.lax.axis_index(AXIS_PIPE)
        xm = x.reshape(M, mb, *x.shape[1:])
        auxm = jax.tree_util.tree_map(
            lambda a: a.reshape(M, mb, *a.shape[1:]), aux)
        down = [(i, (i + 1) % S) for i in range(S)]
        up = [((i + 1) % S, i) for i in range(S)]
        is_last = s == S - 1

        aux_on = stage_aux_cotangent is not None

        def chunk_params(v):
            return jax.tree_util.tree_map(lambda p: p[v], params)

        def with_key(v, m):
            # Keys fold over (microbatch, VIRTUAL stage) so no two
            # (mb, chunk) pairs share dropout masks; at V=1 the virtual
            # index j = s matches the plain schedule's fold exactly.
            if rng is None:
                fn = lambda p, xx: stage_fn(p, xx)  # noqa: E731
            else:
                j = v * S + s
                key = jax.random.fold_in(jax.random.fold_in(rng, m), j)
                fn = lambda p, xx: stage_fn(p, xx, key)  # noqa: E731
            return fn if aux_on else (lambda p, xx: (fn(p, xx), ()))

        def head(m, y):
            aux_mb = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, m, 0, keepdims=False), auxm)
            val, vjp_fn, met = jax.vjp(
                lambda lp, yy: last_fn(lp, yy, aux_mb), last_p, y,
                has_aux=True)
            dlast, dy = vjp_fn(jnp.asarray(scale, val.dtype))
            return val, met, dlast, dy

        zero_dp = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero_dlast = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), last_p)
        if aux_on:
            aux_abs = jax.eval_shape(
                lambda: with_key(0, 0)(chunk_params(0), xm[0])[1])
            zero_aux = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), aux_abs)
            aux_seed = jax.tree_util.tree_map(
                lambda w, a: jnp.asarray(w, a.dtype),
                stage_aux_cotangent, zero_aux)
        else:
            zero_aux, aux_seed = (), ()
        met_abs = jax.eval_shape(
            lambda lp, yy, am: last_fn(lp, yy, am)[1], last_p, xm[0],
            jax.tree_util.tree_map(lambda a: a[0], auxm))
        zero_met = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), met_abs)

        def tick(carry, t):
            (fwd_msgs, bwd_msgs, stash, dp_acc, dlast_acc, dx_buf,
             val_acc, met_acc, aux_acc) = carry

            # ---- forward slots: chunk v runs microbatch t - (v*S+s).
            y_stack = jnp.zeros_like(fwd_msgs)
            head_dy = jnp.zeros_like(xm[0])
            for v in range(V):
                mf = t - (v * S + s)
                mf_valid = jnp.logical_and(mf >= 0, mf < M)
                mf_c = jnp.clip(mf, 0, M - 1)
                # Virtual stage 0 (chunk 0, device 0) ingests fresh
                # microbatches; every other virtual stage eats the
                # message its predecessor pushed last tick.
                inp = fwd_msgs[v]
                if v == 0:
                    feed = jax.lax.dynamic_index_in_dim(
                        xm, mf_c, 0, keepdims=False)
                    inp = jnp.where(s == 0, feed, inp)
                cp = chunk_params(v)

                def fwd_run(inp, stash, v=v, mf_c=mf_c, cp=cp):
                    slot = jnp.mod(mf_c, D)
                    y, aux_v = with_key(v, mf_c)(cp, inp)
                    st = jax.lax.dynamic_update_index_in_dim(
                        stash[v], inp, slot, 0)
                    return y, aux_v, st

                def fwd_skip(inp, stash, v=v):
                    return jnp.zeros_like(inp), zero_aux, stash[v]

                if bubble == "cond":
                    y, aux_v, st_v = jax.lax.cond(mf_valid, fwd_run,
                                                  fwd_skip, inp, stash)
                else:
                    y_r, aux_r, st_r = fwd_run(inp, stash)
                    y = _select_tree(mf_valid, y_r,
                                     jnp.zeros_like(inp))
                    aux_v = _select_tree(mf_valid, aux_r, zero_aux)
                    st_v = _select_tree(mf_valid, st_r, stash[v])
                stash = stash.at[v].set(st_v)
                y_stack = y_stack.at[v].set(y)
                aux_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b, aux_acc, aux_v)

                if v == V - 1:
                    # Loss head at the final virtual stage; its dy
                    # seeds the SAME tick's chunk-(V-1) backward.
                    take_head = jnp.logical_and(is_last, mf_valid)

                    def head_run(y, mf_c=mf_c):
                        return head(mf_c, y)

                    def head_skip(y):
                        return (jnp.zeros((), jnp.float32), zero_met,
                                zero_dlast, jnp.zeros_like(y))

                    if bubble == "cond":
                        hval, hmet, hdlast, hdy = jax.lax.cond(
                            take_head, head_run, head_skip, y)
                    else:
                        hval, hmet, hdlast, hdy = _select_tree(
                            take_head, head_run(y),
                            (jnp.zeros((), jnp.float32), zero_met,
                             zero_dlast, jnp.zeros_like(y)))
                    val_acc = val_acc + hval
                    met_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(a.dtype), met_acc,
                        hmet)
                    dlast_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(a.dtype), dlast_acc,
                        hdlast)
                    head_dy = hdy

            # ---- backward slots: chunk v runs t - 2(Sv-1) + (v*S+s).
            dx_stack = jnp.zeros_like(bwd_msgs)
            for v in range(V):
                j = v * S + s
                mbk = t - 2 * (Sv - 1) + j
                b_valid = jnp.logical_and(mbk >= 0, mbk < M)
                mb_c = jnp.clip(mbk, 0, M - 1)
                cot_in = bwd_msgs[v]
                if v == V - 1:
                    cot_in = jnp.where(is_last, head_dy, cot_in)
                cp = chunk_params(v)

                def bwd_run(stash, cot, v=v, mb_c=mb_c, cp=cp):
                    slot = jnp.mod(mb_c, D)
                    x_saved = jax.lax.dynamic_index_in_dim(
                        stash[v], slot, 0, keepdims=False)
                    _, vjp_fn = jax.vjp(with_key(v, mb_c), cp, x_saved)
                    return vjp_fn((cot.astype(x_saved.dtype), aux_seed))

                def bwd_skip(stash, cot, v=v):
                    return (jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, p.dtype),
                        chunk_params(v)), jnp.zeros_like(xm[0]))

                if bubble == "cond":
                    dp, dx = jax.lax.cond(b_valid, bwd_run, bwd_skip,
                                          stash, cot_in)
                else:
                    dp_r, dx_r = bwd_run(stash, cot_in)
                    dp = _select_tree(
                        b_valid, dp_r,
                        jax.tree_util.tree_map(jnp.zeros_like, dp_r))
                    dx = _select_tree(b_valid, dx_r,
                                      jnp.zeros_like(xm[0]))
                dp_acc = jax.tree_util.tree_map(
                    lambda a, b, v=v: a.at[v].add(b.astype(a.dtype)),
                    dp_acc, dp)
                dx_stack = dx_stack.at[v].set(dx)
                if v == 0:
                    take_dx = jnp.logical_and(b_valid, s == 0)
                    prev_dx = jax.lax.dynamic_index_in_dim(
                        dx_buf, mb_c, 0, keepdims=False)
                    dx_buf = jax.lax.dynamic_update_index_in_dim(
                        dx_buf, jnp.where(take_dx,
                                          dx.astype(dx_buf.dtype),
                                          prev_dx), mb_c, 0)

            # ---- ring hops: the stacked activations go down, the
            # stacked cotangents up; the wrap shifts chunk slots
            # (j -> j+1 crosses S-1 -> 0 into the NEXT chunk; the
            # reverse for cotangents).
            if S > 1:
                recv = jax.lax.ppermute(y_stack, AXIS_PIPE, down)
                fwd_msgs = jnp.where(s == 0, jnp.roll(recv, 1, axis=0),
                                     recv)
                recv_up = jax.lax.ppermute(dx_stack, AXIS_PIPE, up)
                bwd_msgs = jnp.where(s == S - 1,
                                     jnp.roll(recv_up, -1, axis=0),
                                     recv_up)
            else:
                # S == 1: every hop is the intra-device chunk handoff.
                fwd_msgs = jnp.roll(y_stack, 1, axis=0)
                bwd_msgs = jnp.roll(dx_stack, -1, axis=0)
            return (fwd_msgs, bwd_msgs, stash, dp_acc, dlast_acc,
                    dx_buf, val_acc, met_acc, aux_acc), None

        zero_msgs = jnp.zeros((V,) + xm[0].shape, xm.dtype)
        stash0 = jnp.zeros((V, D) + xm[0].shape, xm.dtype)
        carry0 = (zero_msgs, zero_msgs, stash0, zero_dp, zero_dlast,
                  jnp.zeros((M,) + xm[0].shape, x.dtype),
                  jnp.zeros((), jnp.float32), zero_met, zero_aux)
        T = M + 2 * (Sv - 1)
        (_, _, _, dp_acc, dlast_acc, dx_buf, val_acc, met_acc,
         aux_acc), _ = jax.lax.scan(tick, carry0, jnp.arange(T))

        dlast_acc = jax.lax.psum(dlast_acc, AXIS_PIPE)
        dx_out = jax.lax.psum(dx_buf, AXIS_PIPE).reshape(B, *x.shape[1:])
        val_acc = jax.lax.psum(val_acc, AXIS_PIPE)
        met_acc = jax.lax.psum(met_acc, AXIS_PIPE)
        aux_out = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, AXIS_PIPE), aux_acc)
        dp_out = jax.tree_util.tree_map(lambda g: g[None], dp_acc)
        return dp_out, dlast_acc, dx_out, val_acc, met_acc, aux_out

    dp, dlast, dx, val, met, aux_sums = jax.shard_map(
        per_pipe, mesh=mesh, axis_names={AXIS_PIPE},
        in_specs=(P(AXIS_PIPE), P(), P(), P(), P()),
        out_specs=(P(AXIS_PIPE), P(), P(), P(), P(), P()),
        check_vma=False)(stage_params, last_params, x, aux,
                         cotangent_scale)
    if stage_aux_cotangent is not None:
        return val, met, aux_sums, (dp, dlast, dx)
    return val, met, (dp, dlast, dx)


def stack_stage_params(layer_params: Any, num_stages: int,
                       virtual: int = 1) -> Any:
    """[n_layers, ...] stacked layer params -> stage-major grouping.

    ``virtual == 1``: [S, layers_per_stage, ...] — stage s owns layers
    [s*Lps, (s+1)*Lps). ``virtual > 1`` (interleaved 1F1B): [S, V,
    Lps, ...] — virtual stage j = v*S + s owns layers [j*Lps,
    (j+1)*Lps), i.e. device s holds V non-contiguous depth chunks
    (Megatron's interleaved assignment). The v-major-in-j order makes
    the [S*V] -> [V, S] reshape direct; the transpose puts the
    device-sharded S dim first."""
    def regroup(p):
        n = p.shape[0]
        if n % (num_stages * virtual):
            raise ValueError(
                f"{n} layers not divisible by {num_stages} stages"
                + (f" x {virtual} virtual chunks" if virtual > 1
                   else ""))
        lps = n // (num_stages * virtual)
        if virtual == 1:
            return p.reshape(num_stages, lps, *p.shape[1:])
        return p.reshape(virtual, num_stages, lps,
                         *p.shape[1:]).swapaxes(0, 1)
    return jax.tree_util.tree_map(regroup, layer_params)
