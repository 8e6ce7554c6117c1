"""Runner for configurations of kind ``train``: one ``cli.main`` training
run, clocked by :class:`probes.TrainProbe`, then the comparison with the
plain reference over the run's own first three steps."""

from __future__ import annotations

import gc
import json
import math
import os
import statistics
import time
from typing import Any, Dict, List, Optional

from . import common, probes, traffic
from .common import say
from .loader import Cell

CHECK_STEPS = 3
TRACE_SECONDS = 4.0


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float]
              ) -> List[float]:
    """Every leaf's gap, as :func:`worst_leaf_gap` measures it."""
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: {sorted(set(program) ^ set(reference))[:4]}")
    med = statistics.median(reference.values())
    return [abs(program[k] - reference[k]) / max(reference[k], med)
            for k in reference]


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                   ) -> float:
    """The builder's contract: the gap between the program's norm of a
    leaf and the reference's (not the norm of their difference), against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger; the worst leaf counts."""
    return max(leaf_gaps(program, reference))


def _program_leaves(tree) -> Dict[str, float]:
    """{"layer_3/attn/out/bias": norm} from a program-layout tree of
    scalars (already on the host)."""
    import jax
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def _numbers(losses, grad, delta, ref) -> Dict[str, float]:
    """The numbers ``correct`` compares, of one side against the f32
    reference ``ref``."""
    grad_gaps = leaf_gaps(grad, ref["grad_norms"])
    say(f"gradient norm gaps over {len(grad_gaps)} leaves: worst "
        f"{max(grad_gaps):.6g}, median {statistics.median(grad_gaps):.6g}")
    numbers = {
        "grad_norm_gap": max(grad_gaps),
        "delta_norm_gap": worst_leaf_gap(delta, ref["delta_norms"]),
    }
    for i, (a, b) in enumerate(zip(losses, ref["losses"])):
        numbers[f"loss_rel_step{i + 1}"] = abs(a - b) / abs(b)
    return numbers


def compare_with_reference(probe: probes.TrainProbe, lr: float,
                           limits: Dict[str, float],
                           compared: common.Compared,
                           control: Optional[str] = None) -> Dict[str, Any]:
    """Follow the probe's first steps with its model's plain reference
    (which names its norms as the program names its leaves) and hold the
    program's numbers to the limits. Returns the numbers compared and
    ``ok``. ``control`` names a lower precision: the reference is then
    also put in the program's place at that precision, and its numbers
    are returned beside the program's (for setting the limits)."""
    import jax
    import jax.numpy as jnp

    model, sizes = probe.model, probe.sizes
    t0 = time.perf_counter()
    losses = [float(x) for x in jax.device_get(probe.losses)]
    grad = {k: v / (1.0 - model.ADAM_B1) for k, v in
            _program_leaves(jax.device_get(probe.mu_norms)).items()}
    delta = _program_leaves(jax.device_get(probe.delta_norms))

    dev = jax.devices()[0]
    make_p0 = lambda: jax.jit(  # noqa: E731
        lambda k: model.make_params(k, sizes, stacked=True))(
            jax.device_put(common.root_key(probe.seed), dev))
    batches = [{k: jax.device_put(jnp.asarray(v), dev) for k, v in b.items()}
               for b in probe.batches]
    ref = model.follow_training(make_p0, batches, lr)
    numbers = _numbers(losses, grad, delta, ref)
    ok = True
    for name, value in numbers.items():
        limit = limits["loss_rel" if name.startswith("loss_rel")
                       else name]
        ok = compared(name, value, limit,
                      math.isfinite(value) and value <= limit) and ok
    say(f"correct: reference followed {len(batches)} steps of "
        f"{batches[0]['tokens'].shape} tokens in "
        f"{time.perf_counter() - t0:.1f}s; program losses "
        f"{[round(x, 5) for x in losses]} reference "
        f"{[round(x, 5) for x in ref['losses']]}")
    out = {"ok": ok, "numbers": numbers, "losses": losses,
           "reference_losses": ref["losses"]}
    if control:
        low = model.follow_training(make_p0, batches, lr,
                                    precision=control)
        out["control"] = _numbers(low["losses"], low["grad_norms"],
                                  low["delta_norms"], ref)
        say(f"control at {control}: " + " ".join(
            f"{k}={v:.6g}" for k, v in out["control"].items()))
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, fault: Optional[str] = None,
        require_tpu: bool = True, control: Optional[str] = None
        ) -> Dict[str, Any]:
    dev = common.find_devices(cell.chips, require_tpu and not rehearse)
    cfg = cell.config
    sizes = cell.sizes(rehearse)
    mix = dict(cell.traffic)
    if rehearse:
        mix.update(cfg["rehearsal"]["traffic"])
    shape = traffic.train_shape(mix, cell.chips)
    log_every = int(cfg["log_every"])
    out = common.work_dir(cell.name)
    jsonl = os.path.join(out, "train.jsonl")
    argv = list(cfg["rehearsal"]["program_argv"] if rehearse
                else cfg["program_argv"])
    argv += ["--batch-size", str(shape["global_batch"]),
             "--seq-len", str(shape["seq_len"]),
             "--mesh.data", str(cell.chips),
             "--train-steps", str(10 ** 9),
             "--log-every", str(log_every),
             "--learning-rate", str(cfg["optimizer"]["learning_rate"]),
             # The program bakes --seed into its compiled step (the
             # dropout key), so a seed of its own would compile anew in
             # every run. It stays fixed; the run's seed orders the rows
             # (and makes the weights, in probes.swap_in_weights).
             "--seed", "0", "--shuffle-seed", str(seed % (2 ** 31 - 1)),
             "--observe.metrics-jsonl", jsonl]
    tw = probes.TraceWindow(os.path.join(out, "trace"),
                            min(TRACE_SECONDS, seconds)) if trace else None
    probe = probes.TrainProbe(
        seed, cell.model, sizes, seconds, log_every,
        skip_steps=max(log_every, CHECK_STEPS), check_steps=CHECK_STEPS,
        trace=tw, fault=fault)
    say(f"devices found {time.perf_counter() - common.T_PROCESS_START:.2f}s "
        f"after process start")
    say(f"cell {cell.name}: {cell.chips} chip(s), global batch "
        f"{shape['global_batch']} x {shape['seq_len']}, seed {seed}, "
        f"window {seconds}s, cli.main {' '.join(argv)}")

    from tensorflow_distributed_tpu import cli
    closed = False
    with probes.train_seams(probe):
        try:
            cli.main(argv)
        except probes.WindowClosed:
            closed = True
    if not closed:
        raise RuntimeError("the train loop ended before the window closed")
    w = probe.window()
    setup_s = w["t_start"] - common.T_PROCESS_START
    mem_peak = common.memory_peak_bytes(cell.chips)
    gc.collect()              # the loop's state went with its frames

    tokens = w["steps"] * shape["global_batch"] * shape["seq_len"]
    rate_chip = tokens / w["seconds"] / cell.chips
    say(f"window: {w['steps']} steps ({tokens} tokens) in "
        f"{w['seconds']:.4f}s after {w['first_step']} skipped steps "
        f"({CHECK_STEPS} of them followed by the reference); "
        f"{1e3 * w['seconds'] / w['steps']:.3f} ms a step; set-up "
        f"{setup_s:.2f}s of which {probe.t_first_call - common.T_PROCESS_START:.2f}s "
        f"before the first step call")
    say("set-up timeline (s after process start): " + " ".join(
        f"{label}={t - common.T_PROCESS_START:.2f}"
        for label, t in probe.timeline))
    fpt = cell.model.train_flops_per_token(sizes, shape["seq_len"])
    pk = common.peaks_of(dev)
    if pk is not None:
        say(f"mfu (not a metric of its own): {fpt / 1e9:.4f} GFLOP/token x "
            f"{rate_chip:.1f} tokens/s/chip / {pk.bf16_flops / 1e12:.0f} "
            f"TFLOP/s = {fpt * rate_chip / pk.bf16_flops:.4f}")

    records = common.read_jsonl(jsonl)
    logged = [r["loss"] for r in records if r.get("event") == "step"]
    bad = [x for x in logged if not math.isfinite(x)]
    compared = common.Compared()
    ok = compared("nonfinite_logged_losses", len(bad), 0, not bad)
    if len(logged) >= 2:
        ok = compared(
            "last_logged_loss_minus_first", logged[-1] - logged[0], 0.0,
            logged[-1] < logged[0],
            f"({logged[0]:.4f} -> {logged[-1]:.4f} over {len(logged)} "
            f"logged steps)") and ok
    shards = probe.batch_shards or []
    distinct = len({d for d, _ in shards})
    ok = compared(
        "batch_shard_devices", distinct, cell.chips,
        distinct == cell.chips and all(
            rows == shape["rows_per_chip"] for _, rows in shards),
        f"(rows per shard {[r for _, r in shards]})") and ok
    check = compare_with_reference(
        probe, float(cfg["optimizer"]["learning_rate"]),
        (cfg["rehearsal"] if rehearse else cfg)["correct_limits"], compared,
        control=control)
    ok = ok and check["ok"]

    device = dict(dev, memory_peak_bytes=mem_peak)
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        ctx = common.Ctx(cell=cell, model=cell.model, records=records,
                         trace=common.read_capture(tw.log_dir), window=w,
                         sizes=sizes, shape=shape, peaks=pk,
                         chips=cell.chips, say=say)
        metrics, breakdown = common.traced_result(cell, ctx, device)
    else:
        values = {"train_tokens_per_s_chip": rate_chip, "setup_s": setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    return {"correct": ok, "attempted": w["steps"], "failed": len(bad),
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "check": check, "compared": compared}
