"""Overlap-aware gradient sync (parallel/overlap.py + its wiring).

Four layers, mirroring the PR:

1. bucket partitioner units — size bound respected, deterministic
   order, dtype keying, and the block-layout round trip bit-identical;
2. overlap-vs-serial step identity on a mesh>1 CPU run: params, Adam
   slots, EMA, and a ``skip_nonfinite``-skipped step all BIT-equal,
   with the per-module health vitals agreeing across formulations;
3. census golden drift gate for the new ``*_train_overlap`` programs
   (trace-only — no compiles);
4. config validation (overlap rejected where the data axis is 1, the
   family is pipelined, the partition isn't zero1, ...) and the
   planner's overlap strategy (enumeration constraints, cli_args
   mapping, roofline overlap discount) — jax-free where the planner
   tier is.
"""

import dataclasses

import numpy as np
import pytest

from tensorflow_distributed_tpu.analysis.planner.candidates import (
    Candidate, ModelFacts, enumerate_candidates)
from tensorflow_distributed_tpu.analysis.planner.score import (
    Hardware, roofline_ms)
from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig

# --- bucket planning (import-light: plan_buckets flattens shapes) -------


def _fake_tree(shapes, dtype="float32"):
    return {f"leaf_{i:02d}": np.zeros(s, dtype=dtype)
            for i, s in enumerate(shapes)}


def test_plan_buckets_size_bound_and_determinism():
    from tensorflow_distributed_tpu.parallel.overlap import plan_buckets

    tree = _fake_tree([(64, 64)] * 6)  # 16 KiB leaves
    plan = plan_buckets(tree, 2, bucket_bytes=40 * 1024,
                        fsdp_min_size=256)
    assert plan.n_leaves == 6
    for bucket in plan.scatter:
        assert sum(lp.nbytes for lp in bucket) <= 40 * 1024
    # Deterministic: same inputs, same plan; leaves keep flatten order.
    again = plan_buckets(tree, 2, bucket_bytes=40 * 1024,
                         fsdp_min_size=256)
    assert plan == again
    order = [lp.index for b in plan.scatter for lp in b]
    assert order == sorted(order)


def test_plan_buckets_oversize_leaf_gets_own_bucket():
    from tensorflow_distributed_tpu.parallel.overlap import plan_buckets

    tree = _fake_tree([(16, 16), (512, 512), (16, 16)])
    plan = plan_buckets(tree, 2, bucket_bytes=8 * 1024,
                        fsdp_min_size=64)
    big = [b for b in plan.scatter if any(lp.shape == (512, 512)
                                          for lp in b)]
    assert len(big) == 1 and len(big[0]) == 1  # alone, over the bound


def test_plan_buckets_dtype_keyed_and_small_leaves_replicated():
    from tensorflow_distributed_tpu.parallel.overlap import plan_buckets

    tree = {"a": np.zeros((64, 64), np.float32),
            "b": np.zeros((64, 64), np.float16),
            "c": np.zeros((64, 64), np.float32),
            "tiny": np.zeros((8,), np.float32),
            "odd": np.zeros((63, 3), np.float32)}  # no dim % 2 == 0
    plan = plan_buckets(tree, 2, bucket_bytes=1 << 20, fsdp_min_size=64)
    for bucket in plan.scatter:
        assert len({lp.dtype for lp in bucket}) == 1
    rep_paths = {lp.path for b in plan.replicated for lp in b}
    assert ("tiny",) in rep_paths      # under fsdp_min_size
    assert ("odd",) in rep_paths       # no divisible dim
    assert all(("a",) != p for p in rep_paths)


def test_comm_bytes_estimate_scales_with_axis():
    from tensorflow_distributed_tpu.parallel.overlap import (
        comm_bytes_per_step, plan_buckets)

    tree = _fake_tree([(64, 64)] * 4)
    total = sum(x.nbytes for x in tree.values())
    p2 = plan_buckets(tree, 2, fsdp_min_size=64)
    p4 = plan_buckets(tree, 4, fsdp_min_size=64)
    assert comm_bytes_per_step(p2) == pytest.approx(2 * total * 1 / 2)
    assert comm_bytes_per_step(p4) == pytest.approx(2 * total * 3 / 4)
    p1 = plan_buckets(tree, 1, fsdp_min_size=64)
    assert comm_bytes_per_step(p1) == 0.0


def test_block_layout_round_trip_bit_identical():
    """leaf -> rows -> per-device flats -> blocks -> gathered rows ->
    leaf reconstructs every value bit-for-bit, for scatter dims 0/1/2."""
    import jax
    from tensorflow_distributed_tpu.parallel.overlap import (
        LeafPlan, _block_to_flat, _flat_to_block, _leaf_to_rows,
        _rows_to_leaf)

    rng = np.random.default_rng(0)
    n = 4
    for shape, dim in [((8, 5), 0), ((5, 8), 1), ((3, 4, 6), 1),
                       ((2, 3, 8), 2)]:
        lp = LeafPlan(index=0, path=("x",), shape=shape,
                      dtype="float32", scatter_dim=dim)
        x = rng.normal(size=shape).astype(np.float32)
        rows = np.asarray(_leaf_to_rows(jax.numpy.asarray(x), dim, n))
        assert rows.shape == (n, x.size // n)
        blocks = [np.asarray(_flat_to_block(
            jax.numpy.asarray(rows[i]), lp, n)) for i in range(n)]
        # Each block is the device's slice along the scatter dim.
        blk = shape[dim] // n
        for i, b in enumerate(blocks):
            sl = [slice(None)] * len(shape)
            sl[dim] = slice(i * blk, (i + 1) * blk)
            np.testing.assert_array_equal(b, x[tuple(sl)])
        flats = np.stack([np.asarray(_block_to_flat(
            jax.numpy.asarray(b), lp)) for b in blocks])
        np.testing.assert_array_equal(flats, rows)
        back = np.asarray(_rows_to_leaf(jax.numpy.asarray(rows), lp, n))
        np.testing.assert_array_equal(back, x)


# --- the identity run (compiles; shares one tiny-gpt setup) ------------

_SEQ, _BATCH, _BUCKET, _MIN = 16, 8, 8192, 256


@pytest.fixture(scope="module")
def overlap_setup(devices8):
    """data=2 mesh, mesh-less tiny gpt, loss/shardings/data — shared
    by every compiling test in this module."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.data.lm import synthetic_clm
    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, mlm_batch_shardings)

    mesh = make_mesh(MeshConfig(data=2), devices8[:2])
    model = transformer.gpt_lm(mesh=None, size="tiny",
                               tp_partitioning=False, dropout_rate=0.0,
                               compute_dtype=jnp.bfloat16, max_len=_SEQ)
    sh = mlm_batch_shardings(mesh)
    ds = synthetic_clm(n=64, seq_len=_SEQ, vocab_size=64)

    def put(i, poison=False):
        b = ds.batch((np.arange(_BATCH) + i * _BATCH)
                     % ds.tokens.shape[0])
        if poison:
            b = dict(b)
            b["mask"] = np.asarray(b["mask"]) * np.nan
        return {k: jax.device_put(np.asarray(v), sh[k])
                for k, v in b.items()}

    return {"mesh": mesh, "model": model, "loss": make_mlm_loss(),
            "sh": sh, "put": put}


def _build(setup, sync, **kw):
    import jax
    import optax

    from tensorflow_distributed_tpu.parallel.overlap import (
        make_explicit_train_step)
    from tensorflow_distributed_tpu.train.state import create_train_state

    overlap = sync == "overlap"
    state = create_train_state(
        setup["model"], optax.adam(1e-3),
        np.zeros((2, _SEQ), np.int32), setup["mesh"], seed=0,
        opt_fsdp=overlap, fsdp_min_size=_MIN, ema=True)
    params_out = (jax.tree_util.tree_map(lambda a: a.sharding,
                                         state.params)
                  if overlap else None)
    step = make_explicit_train_step(
        setup["mesh"], state, loss=setup["loss"],
        batch_shardings=setup["sh"], grad_sync=sync,
        bucket_bytes=_BUCKET, fsdp_min_size=_MIN, donate=False,
        ema_decay=0.999, params_out_shardings=params_out, **kw)
    return state, step


def _bit_equal(a, b):
    import jax

    fa, fb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def test_overlap_matches_serial_bit_identical(overlap_setup):
    """THE identity gate: 3 steps (the middle one NaN-poisoned and
    skipped on device) leave params, Adam slots, and EMA bit-equal
    across the serial-psum and bucketed-overlap formulations — and the
    skipped step really discarded the update on both sides."""
    from tensorflow_distributed_tpu.parallel.overlap import plan_buckets

    ss, serial = _build(overlap_setup, "serial", skip_nonfinite=True,
                        grad_norm_metric=True, health_every=2)
    so, over = _build(overlap_setup, "overlap", skip_nonfinite=True,
                      grad_norm_metric=True, health_every=2)
    plan = plan_buckets(ss.params, 2, bucket_bytes=_BUCKET,
                        fsdp_min_size=_MIN)
    assert len(plan.scatter) > 1  # the bucketed schedule is exercised

    pre_skip = None
    for i in range(3):
        poison = i == 1
        if poison:
            pre_skip = so.params
        ss, ms = serial(ss, overlap_setup["put"](i, poison=poison))
        so, mo = over(so, overlap_setup["put"](i, poison=poison))
        assert float(ms["skipped_nonfinite"]) == float(
            mo["skipped_nonfinite"]) == (1.0 if poison else 0.0)
        if poison:
            assert _bit_equal(so.params, pre_skip)  # update discarded
        if i != 1:
            np.testing.assert_allclose(float(ms["grad_norm"]),
                                       float(mo["grad_norm"]),
                                       rtol=1e-5)
        # Per-module health vitals agree across formulations on the
        # cadence step (psum-reconstructed norms vs full-tree norms:
        # same values modulo summation order).
        if float(ms.get("health_emit", 0.0)) > 0:
            for k in ms:
                if k.startswith("health/"):
                    np.testing.assert_allclose(
                        float(ms[k]), float(mo[k]), rtol=1e-4,
                        err_msg=k)
    assert int(so.step) == 3
    assert _bit_equal(ss.params, so.params)
    assert _bit_equal(ss.opt_state, so.opt_state)
    assert _bit_equal(ss.ema, so.ema)


def test_clip_tree_matches_optax_semantics():
    """_clip_tree fed optax's own global_norm reproduces
    optax.clip_by_global_norm BIT-EXACTLY, on both sides of the
    trigger — the explicit step's clip is the chain clip with the
    norm made pluggable (so the shard_map paths can psum-reconstruct
    it), not a reimplementation with different rounding."""
    import jax
    import optax

    from tensorflow_distributed_tpu.parallel.overlap import _clip_tree

    tree = _fake_tree([(8, 12), (5,), (3, 4, 2)])
    tree = jax.tree_util.tree_map(
        lambda x: jax.numpy.asarray(x - np.mean(x)), tree)
    for max_norm in (0.05, 1e6):   # clipping / not clipping
        clip = optax.clip_by_global_norm(max_norm)
        ref, _ = clip.update(tree, clip.init(tree))
        got = _clip_tree(tree, optax.global_norm(tree), max_norm)
        assert _bit_equal(ref, got), f"max_norm={max_norm}"


def test_overlap_matches_serial_bit_identical_with_clip(overlap_setup):
    """The grad-clip composition gate (ROADMAP item 2's follow-up):
    with clipping ACTIVE on every step (clip << observed grad norms),
    serial+clip and overlap+clip keep params and Adam slots BIT-equal
    — both modes scale by the same psum-reconstructed global-norm
    scalar — and the clip demonstrably changed the trajectory vs the
    unclipped run.

    The EMA is held to 4 ulps of each leaf's largest element, not to
    bit identity (PR 30 traced it: same pre-clip norm, same params and
    slots after every step, EMA differing from step 0 on). Each side
    computes ``d*e + (1-d)*p`` from bit-equal ``e``, ``p`` and ``d``
    with ONE fused multiply-add, and XLA:CPU picks which product it
    rounds first per fusion: the serial program rounds ``(1-d)*p``
    everywhere; the overlap program rounds ``d*e`` instead on exactly
    the leaves it gathers along a non-leading dimension (their
    ``_rows_to_leaf`` transpose lands in the EMA's fusion). Either is
    a correct rounding of the same expression, at most 2 ulps of the
    larger operand apart a step; under the unclipped test's fusions
    both sides happen to choose alike and stay bit-equal."""
    import jax

    ss, serial = _build(overlap_setup, "serial", grad_clip_norm=0.05,
                        grad_norm_metric=True)
    so, over = _build(overlap_setup, "overlap", grad_clip_norm=0.05,
                      grad_norm_metric=True)
    su, unclipped = _build(overlap_setup, "serial",
                           grad_norm_metric=True)
    for i in range(3):
        ss, ms = serial(ss, overlap_setup["put"](i))
        so, mo = over(so, overlap_setup["put"](i))
        su, _ = unclipped(su, overlap_setup["put"](i))
        # The pre-clip norm is the reported metric, identical across
        # formulations (same reconstruction), and far above the bound
        # (the clip genuinely fires every step).
        assert float(ms["grad_norm"]) == float(mo["grad_norm"])
        assert float(ms["grad_norm"]) > 0.05
    assert _bit_equal(ss.params, so.params)
    assert _bit_equal(ss.opt_state, so.opt_state)
    for a, b in zip(jax.tree_util.tree_leaves(ss.ema),
                    jax.tree_util.tree_leaves(so.ema)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 4 * np.spacing(np.abs(a).max())
    assert not _bit_equal(ss.params, su.params)  # clip changed things


def test_overlap_slots_stay_sharded(overlap_setup):
    """The point of ZeRO-1 composition: after an overlap step the
    Adam mirrors keep their data-sharded layout (never gathered), and
    the params keep the replicated layout the constraint pins."""
    import jax

    from tensorflow_distributed_tpu.analysis import runtime as graftcheck

    so, over = _build(overlap_setup, "overlap")
    declared = graftcheck.sharding_tree(so.opt_state)
    so, _ = over(so, overlap_setup["put"](0))
    graftcheck.assert_sharding_contract(so.opt_state, declared,
                                        what="opt_state")
    after = jax.tree_util.tree_map(lambda a: a.sharding, so.opt_state)
    sharded = [s for s in jax.tree_util.tree_leaves(after)
               if "data" in str(s.spec)]
    assert sharded  # some slot really lives sharded
    for p in jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda a: a.sharding, so.params)):
        assert "data" not in str(p.spec)


def test_multistep_overlap_matches_single_steps(overlap_setup):
    """K=2 stacked dispatch of the overlap step == 2 single steps
    (scan-wrapped program; allclose — cross-program elementwise
    rounding is not pinned, the bit gate lives in the identity test)."""
    import jax

    from tensorflow_distributed_tpu.train.multistep import (
        make_multi_step, stacked_batch_shardings)

    s_single, single = _build(overlap_setup, "overlap")
    s_multi, _ = _build(overlap_setup, "overlap")
    multi = make_multi_step(
        overlap_setup["mesh"], loss=overlap_setup["loss"],
        batch_shardings=overlap_setup["sh"], grad_sync="overlap",
        state_template=s_multi, grad_sync_bucket_bytes=_BUCKET,
        grad_sync_min_size=_MIN)
    b0, b1 = overlap_setup["put"](0), overlap_setup["put"](1)
    stacked = jax.tree_util.tree_map(
        lambda a, b, s: jax.device_put(
            np.stack([np.asarray(a), np.asarray(b)]), s),
        b0, b1, stacked_batch_shardings(overlap_setup["mesh"],
                                        overlap_setup["sh"]))
    s_multi, m = multi(s_multi, stacked)
    for b in (b0, b1):
        s_single, ms = single(s_single, b)
    assert int(s_multi.step) == 2
    np.testing.assert_allclose(float(m["loss"]), float(ms["loss"]),
                               rtol=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(s_single.params),
                    jax.tree_util.tree_leaves(s_multi.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


def test_builder_rejections(overlap_setup, devices8):
    import optax

    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.parallel.overlap import (
        make_explicit_train_step)
    from tensorflow_distributed_tpu.train.state import create_train_state

    mesh1 = make_mesh(MeshConfig(data=1), devices8[:1])
    state = create_train_state(overlap_setup["model"],
                               optax.adam(1e-3),
                               np.zeros((2, _SEQ), np.int32), mesh1)
    with pytest.raises(ValueError, match="data"):
        make_explicit_train_step(mesh1, state, grad_sync="overlap")
    with pytest.raises(ValueError, match="unknown grad_sync"):
        make_explicit_train_step(mesh1, state, grad_sync="banana")
    mesh_tp = make_mesh(MeshConfig(data=2, model=2), devices8[:4])
    with pytest.raises(ValueError, match="pure data"):
        make_explicit_train_step(mesh_tp, state, grad_sync="overlap")
    from tensorflow_distributed_tpu.train.step import make_train_step
    with pytest.raises(ValueError, match="state_template"):
        make_train_step(overlap_setup["mesh"], grad_sync="overlap")
    with pytest.raises(ValueError, match="accum_steps"):
        make_train_step(overlap_setup["mesh"], grad_sync="overlap",
                        state_template=state, accum_steps=2)


# --- census drift gate (trace-only) ------------------------------------


def test_overlap_census_matches_golden():
    """The new ``*_train_overlap`` programs trace to exactly the
    committed collective counts — a reduce-scatter or all-gather
    gained/lost per bucket fails here, not in an ICI profile later."""
    from tensorflow_distributed_tpu.analysis import jaxprcheck

    current = jaxprcheck.census(["gpt_train_overlap"])
    drift = jaxprcheck.diff_censuses(jaxprcheck.load_golden(), current,
                                     required=["gpt_train_overlap"])
    assert drift == [], drift


# --- config validation --------------------------------------------------


def _cfg(**kw):
    defaults = dict(model="gpt_lm", model_size="tiny",
                    dataset="synthetic", grad_sync="overlap",
                    param_partition="zero1",
                    mesh=MeshConfig(data=2), batch_size=16)
    defaults.update(kw)
    return TrainConfig(**defaults)


def test_config_overlap_valid():
    _cfg().validate()
    _cfg(grad_sync="serial", param_partition="replicated").validate()
    # grad_clip_norm COMPOSES since the psum-reconstructed pre-scale
    # landed (the old validate-time rejection is lifted).
    _cfg(grad_clip_norm=1.0).validate()
    _cfg(grad_sync="serial", param_partition="replicated",
         grad_clip_norm=1.0).validate()


@pytest.mark.parametrize("kw,match", [
    (dict(mesh=MeshConfig(data=1)), "nothing to synchronize"),
    (dict(mesh=MeshConfig(data=2, model=2)), "pure data"),
    (dict(model="pipelined_lm"), "pipeline"),
    (dict(param_partition="replicated"), "zero1"),
    (dict(param_partition="fsdp"), "zero1"),
    (dict(grad_sync="serial"), "replicated"),
    (dict(optimizer="adafactor"), "ELEMENTWISE"),
    (dict(grad_accum_steps=2, batch_size=16), "microbatch"),
    (dict(ce_chunk=8), "ce_chunk"),
    (dict(mode="serve"), "mode"),
    (dict(grad_sync="banana"), "unknown grad_sync"),
])
def test_config_overlap_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**kw).validate()


def test_config_bucket_knob_needs_overlap():
    with pytest.raises(ValueError, match="grad_sync_bucket_mb"):
        TrainConfig(grad_sync_bucket_mb=8.0).validate()
    # An explicitly-passed DEFAULT value is just as ignored without
    # overlap — the sentinel (None = unset) catches it too.
    with pytest.raises(ValueError, match="grad_sync_bucket_mb"):
        TrainConfig(grad_sync_bucket_mb=4.0).validate()
    _cfg(grad_sync_bucket_mb=8.0).validate()
    _cfg(grad_sync_bucket_mb=4.0).validate()


@pytest.mark.parametrize("kw", [
    dict(optimizer="adafactor"),
    dict(grad_accum_steps=2),
    dict(param_sync_every=2),
    dict(ce_chunk=8),
    dict(shard_vocab=True),
])
def test_overlap_conflict_single_source_of_truth(kw):
    # overlap_grad_sync_conflict (what --plan auto consults) must be
    # EXACTLY the message validate raises for the same knob — the
    # planner and the launch guard can never disagree about whether
    # overlap fits a config.
    cfg = _cfg(**kw)
    msg = cfg.overlap_grad_sync_conflict()
    assert msg
    with pytest.raises(ValueError) as ei:
        cfg.validate()
    assert str(ei.value) == msg
    assert _cfg().overlap_grad_sync_conflict() is None


def test_config_plan_auto_owns_grad_sync():
    # serial + replicated + default mesh passes every grad_sync rule,
    # so the plan-auto ownership guard is what fires.
    with pytest.raises(ValueError, match="plan auto owns the "
                                         "grad-sync"):
        TrainConfig(model="gpt_lm", plan="auto",
                    grad_sync="serial").validate()
    # overlap + plan auto dies earlier (plan auto pins replicated,
    # overlap demands zero1) — still rejected, different guard.
    with pytest.raises(ValueError):
        TrainConfig(model="gpt_lm", plan="auto",
                    grad_sync="overlap").validate()


# --- planner strategy (jax-free like the planner unit tier) -------------


def _stub_infeasible(axes, devices, batch):
    product = 1
    for v in axes.values():
        product *= v
    if product != devices:
        return "product"
    if batch % axes.get("data", 1):
        return "batch"
    return None


def test_planner_enumerates_overlap_pure_data_only():
    facts = ModelFacts(family="gpt", n_heads=4, n_layers=2)
    feasible, pruned = enumerate_candidates(
        facts, devices=4, batch=16, infeasible=_stub_infeasible)
    strategies = {(c.strategy, tuple(sorted(c.mesh.items())))
                  for c in feasible}
    assert ("overlap", (("data", 4), ("expert", 1), ("model", 1),
                        ("pipe", 1), ("seq", 1))) in strategies
    # overlap never appears on a tensor-carrying or data=1 shape
    for c in feasible:
        if c.partition == "overlap":
            assert c.mesh["model"] == 1 and c.mesh["data"] > 1
    reasons = [p.reason for p in pruned
               if p.candidate.partition == "overlap"]
    assert any("pure data" in r for r in reasons)
    pipe_facts = ModelFacts(family="pipelined", n_heads=4, n_layers=4)
    feas_p, pruned_p = enumerate_candidates(
        pipe_facts, devices=4, batch=16, infeasible=_stub_infeasible)
    assert not any(c.partition == "overlap" for c in feas_p)


def test_planner_prunes_overlap_on_knob_conflict():
    facts = ModelFacts(family="gpt", n_heads=4, n_layers=2)
    feasible, pruned = enumerate_candidates(
        facts, devices=4, batch=16, infeasible=_stub_infeasible,
        overlap_conflict="optimizer 'adafactor' is not elementwise")
    assert not any(c.partition == "overlap" for c in feasible)
    reasons = [p.reason for p in pruned
               if p.candidate.partition == "overlap"
               and p.candidate.mesh["data"] == 4]
    assert reasons and "adafactor" in reasons[0]


def test_apply_auto_threads_overlap_conflict(monkeypatch):
    # apply_auto must hand the run's knob conflicts to the enumeration
    # so --plan auto never picks an overlap layout the post-plan
    # re-validate would reject (e.g. --optimizer adafactor).
    from tensorflow_distributed_tpu.analysis.planner import plan as plan_lib
    from tensorflow_distributed_tpu.parallel import mesh as mesh_lib
    seen = {}

    def fake_make_plan(*args, **kw):
        seen.update(kw)
        return {"family": "gpt", "size": "tiny", "devices": 2,
                "batch_size": 16, "candidates": [], "pruned": [],
                "chosen": {"mesh": {"data": 2}, "partition": "zero1",
                           "strategy": "zero1", "step_ms": 1.0,
                           "peak_hbm_bytes": 1}}

    monkeypatch.setattr(plan_lib, "make_plan", fake_make_plan)
    monkeypatch.setattr(mesh_lib, "alive_devices", lambda: [0, 0])
    monkeypatch.setattr(mesh_lib, "is_chief", lambda: False)
    cfg = TrainConfig(model="gpt_lm", model_size="tiny",
                      dataset="synthetic", batch_size=16, plan="auto",
                      optimizer="adafactor")
    plan_lib.apply_auto(cfg)
    assert seen["overlap_conflict"] == cfg.overlap_grad_sync_conflict()
    assert "adafactor" in seen["overlap_conflict"]


def test_planner_overlap_cli_args_and_strategy():
    cand = Candidate.make({"data": 4}, "overlap")
    assert cand.strategy == "overlap"
    args = cand.cli_args()
    assert args[args.index("--param-partition") + 1] == "zero1"
    assert args[args.index("--grad-sync") + 1] == "overlap"


def test_roofline_overlap_discount():
    hw = Hardware(platform="cpu", device_kind="x", peak_flops=1e12,
                  hbm_bw=1e11, ici_bw=1e10)
    costs = {"flops": 2e9, "bytes_accessed": 1e8}  # 2 ms compute, 1 ms mem
    serial = roofline_ms(costs, 3e7, hw)            # 3 ms collective
    over = roofline_ms(costs, 3e7, hw, overlap=True)
    assert serial["step_ms"] == pytest.approx(2.0 + 3.0)
    assert over["step_ms"] == pytest.approx(3.0)    # max, not sum
    small = roofline_ms(costs, 1e7, hw, overlap=True)
    assert small["step_ms"] == pytest.approx(2.0)   # fully hidden


def test_min_latency_probe_helper():
    from tensorflow_distributed_tpu.parallel.collectives import (
        min_latency)

    seen = iter([0.5, 0.2, 0.9])
    assert min_latency(lambda: next(seen), iters=3) == 0.2
    with pytest.raises(ValueError):
        min_latency(lambda: 0.0, iters=0)
