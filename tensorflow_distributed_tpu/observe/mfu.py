"""Model-FLOPs accounting: tokens/s, imgs/s, TFLOP/s, and MFU.

One home for the FLOPs math the train loop's step records use (the
chip benchmark under ``perfbench/`` carries its own counts per model,
``perfbench/models/``). Conventions (the PaLM/MFU accounting, matmuls
only):

- per-token forward = ``2 * N_matmul`` — every matmul parameter is one
  multiply-accumulate per token;
- attention adds ``4 * L * d_model`` per layer forward (QK^T and PV),
  halved for causal because the flash kernel skips masked blocks, and
  window-shaped for sliding-window attention;
- train = 3x forward (the backward pass costs ~2x the forward's
  matmul FLOPs);
- MoE layers count only the ``top_k / num_experts`` fraction of expert
  parameters a token actually routes through — MFU measures useful
  work, not resident weights.

MFU divides achieved model FLOP/s by the chip's bf16 peak. Peaks for
known TPU generations ship in ``PEAK_BF16_FLOPS``; unknown device
kinds (CPU hosts included) report ``None`` rather than a made-up
number — pass an explicit peak (``ObserveConfig.peak_tflops``) to
override.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

# Chip bf16 peaks for MFU. Only kinds we can meet in this environment;
# unknown kinds report mfu as None rather than a made-up number.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e / Trillium
}

# The reference CNN's fixed architecture (models/cnn.py): MACs per
# image, one forward. Convs count kernel x output-position MACs; the
# dense tail counts its weights.
_MNIST_CNN_MACS = (
    5 * 5 * 1 * 32 * 28 * 28        # conv1, SAME, stride 1
    + 5 * 5 * 32 * 64 * 14 * 14     # conv2 after 2x2 pool
    + 3136 * 1024                   # dense 7*7*64 -> 1024
    + 1024 * 10                     # logits
)


def device_peak_flops(device=None) -> Optional[float]:
    """Per-device bf16 peak by device kind; None when unknown."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    return PEAK_BF16_FLOPS.get(dev.device_kind)


def matmul_params(params, moe_experts: int = 0, moe_top_k: int = 2
                  ) -> float:
    """Parameters that participate in matmuls, weighted by how often a
    token uses them: every kernel of ndim >= 2 except embedding tables
    (lookups, not matmuls); MoE expert kernels (the stacked ndim >= 3
    ``wi``/``wo`` tensors inside MoeMlp) count the routed
    ``top_k / num_experts`` fraction only."""
    import jax

    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if leaf.ndim < 2 or "emb" in name:
            continue
        if (moe_experts > 0 and leaf.ndim >= 3
                and "moe" in name.lower()):
            total += leaf.size * min(moe_top_k, moe_experts) / moe_experts
        else:
            total += leaf.size
    return total


def attn_flops_per_token_fwd(cfg, seq_len: Optional[int] = None) -> float:
    """QK^T + PV FLOPs per token, one forward: 4 * d_model * (average
    attended length) per layer. Full bidirectional attends L; causal
    ~L/2 (the kernel skips masked blocks); sliding-window attends
    min(W, pos+1) — the windowed kernel skips out-of-band blocks, so
    MFU keeps counting only useful work. ``seq_len`` overrides
    ``cfg.max_len`` when the data stream trains shorter windows than
    the model's position budget."""
    L = seq_len or cfg.max_len
    per_len = 4.0 * cfg.d_model * cfg.n_layers
    if not cfg.causal:
        return per_len * L
    W = getattr(cfg, "attn_window", 0) or 0
    if W and W < L:
        avg = (W * (W + 1) / 2.0 + (L - W) * W) / L
    else:
        avg = L / 2.0
    return per_len * avg


def flops_per_token(params, cfg, seq_len: Optional[int] = None) -> float:
    """Transformer-family model FLOPs per trained token, fwd + bwd."""
    n = matmul_params(params,
                      moe_experts=getattr(cfg, "moe_experts", 0),
                      moe_top_k=getattr(cfg, "moe_top_k", 2))
    return 3.0 * (2.0 * n + attn_flops_per_token_fwd(cfg, seq_len))


def pipelined_hw_flops_per_token(params, cfg,
                                 seq_len: Optional[int] = None) -> float:
    """HARDWARE FLOPs per token for the 1F1B-recompute schedule: model
    FLOPs charge 3x-forward, but recompute EXECUTES 4x-forward for the
    block stack (each backward tick re-runs the stage forward from the
    stashed input). Reported alongside model MFU so the schedule's
    remat trade isn't misread as MXU inefficiency."""
    blocks_n = matmul_params(params["blocks"],
                             moe_experts=getattr(cfg, "moe_experts", 0),
                             moe_top_k=getattr(cfg, "moe_top_k", 2))
    return (flops_per_token(params, cfg, seq_len)
            + 2.0 * blocks_n + attn_flops_per_token_fwd(cfg, seq_len))


_TRANSFORMER_FAMILIES = ("bert_mlm", "gpt_lm", "moe_lm", "pipelined_lm")


def flops_per_item(model_name: str, params=None, model_cfg=None,
                   seq_len: Optional[int] = None
                   ) -> Tuple[Optional[float], str]:
    """(train FLOPs per item, item unit) for a model family.

    Unit is "token" for the LM families, "image" for vision. Families
    without an estimator (the ResNets — conv FLOPs depend on spatial
    shapes this module doesn't model) return ``(None, unit)``:
    throughput still reports, MFU is omitted rather than invented.
    """
    if model_name == "mnist_cnn":
        return 3.0 * 2.0 * _MNIST_CNN_MACS, "image"
    if model_name in _TRANSFORMER_FAMILIES:
        if params is None or model_cfg is None:
            return None, "token"
        return flops_per_token(params, model_cfg, seq_len), "token"
    return None, "image"


class ThroughputAccountant:
    """Turns (items, seconds) windows into items/s, TFLOP/s, and MFU.

    ``peak_flops_total`` is the AGGREGATE peak across all devices in
    the job (per-device peak x device count); None omits MFU.
    ``hw_flops_per_item`` (optional) adds a parallel hardware-
    utilization number (pipelined recompute executes more FLOPs than
    the model math credits).
    """

    def __init__(self, flops_per_item: Optional[float] = None,
                 unit: str = "item",
                 peak_flops_total: Optional[float] = None,
                 hw_flops_per_item: Optional[float] = None):
        self.flops_per_item = flops_per_item
        self.unit = unit
        self.peak_flops_total = peak_flops_total or None
        self.hw_flops_per_item = hw_flops_per_item

    def rates(self, items: float, seconds: float) -> Dict[str, Any]:
        if seconds <= 0 or items <= 0:
            return {}
        per_sec = items / seconds
        out: Dict[str, Any] = {
            f"{self.unit}s_per_sec": round(per_sec, 2)}
        if self.flops_per_item:
            flops_s = per_sec * self.flops_per_item
            out["model_tflops"] = round(flops_s / 1e12, 4)
            if self.peak_flops_total:
                out["mfu"] = round(flops_s / self.peak_flops_total, 4)
                if self.hw_flops_per_item:
                    out["hw_mfu"] = round(
                        per_sec * self.hw_flops_per_item
                        / self.peak_flops_total, 4)
        return out
