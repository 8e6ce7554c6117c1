"""Model zoo.

``mnist_cnn`` is the reference-parity model (the CNN duplicated across
mnist_python_m.py:93-128, mnist_single.py:55-88 and the notebook — here
it exists exactly once). ResNet and the transformer families extend the
same train-step machinery to the BASELINE.json scale-out configs.
"""

from typing import Optional

import jax.numpy as jnp

from tensorflow_distributed_tpu.config import (
    LATENT_MOE_MODELS, SOURCE_CONFIG_MODELS)
from tensorflow_distributed_tpu.models.cnn import MnistCNN  # noqa: F401

MODEL_NAMES = ("mnist_cnn", "resnet20", "resnet50", "bert_mlm", "gpt_lm",
               "pipelined_lm", "moe_lm") + SOURCE_CONFIG_MODELS

# Families with no training path: a serve/generate run builds their
# state without optimizer slots, and mode=train rejects them.
# (models/glm_moe_dsa.py under both the source ``model_type``s it is
# registered as, models/minicpm_sala.py, models/granitemoehybrid.py,
# models/nemotron_h.py, models/exaone_moe.py and models/jamba.py.)
INFERENCE_ONLY_MODELS = SOURCE_CONFIG_MODELS

# Families whose train state carries mutable variable collections
# (BatchNorm statistics) — maintained HERE, next to the registry, so
# capability checks (e.g. local SGD's no-divergent-stats rule,
# config.validate) track new models; train.local_sgd.stack_state's
# runtime extra-state check is the backstop.
MUTABLE_EXTRA_MODELS = ("resnet20", "resnet50")


def build_model(name: str, mesh=None, dropout_rate: Optional[float] = None,
                init_scheme: str = "improved",
                compute_dtype=jnp.bfloat16, **overrides):
    """Explicit per-family dispatch (no kwargs guessing): each family
    takes what it understands.

    ``init_scheme`` is the CNN's reference-vs-improved switch
    (mnist_python_m.py:185-196); the other families have no reference
    counterpart to be faithful to and ignore it. ``mesh`` matters only
    to the transformer (ring attention needs it); ``overrides`` are
    TransformerConfig fields.
    """
    from tensorflow_distributed_tpu.models import cnn, resnet, transformer

    if name not in ("bert_mlm", "gpt_lm", "pipelined_lm",
                    "moe_lm") + SOURCE_CONFIG_MODELS:
        overrides.pop("size", None)  # presets are transformer-family only
    if name == "mnist_cnn":
        kw = dict(init_scheme=init_scheme, compute_dtype=compute_dtype)
        if dropout_rate is not None:
            kw["dropout_rate"] = dropout_rate
        return cnn.MnistCNN(**kw)
    if name == "resnet20":
        return resnet.resnet20(compute_dtype=compute_dtype, **overrides)
    if name == "resnet50":
        return resnet.resnet50(compute_dtype=compute_dtype, **overrides)
    if name == "bert_mlm":
        if dropout_rate is not None:
            overrides.setdefault("dropout_rate", dropout_rate)
        overrides.setdefault("compute_dtype", compute_dtype)
        return transformer.bert_base_mlm(mesh=mesh, **overrides)
    if name == "gpt_lm":
        if dropout_rate is not None:
            overrides.setdefault("dropout_rate", dropout_rate)
        overrides.setdefault("compute_dtype", compute_dtype)
        return transformer.gpt_lm(mesh=mesh, **overrides)
    if name == "moe_lm":
        if dropout_rate is not None:
            overrides.setdefault("dropout_rate", dropout_rate)
        overrides.setdefault("compute_dtype", compute_dtype)
        return transformer.moe_lm(mesh=mesh, **overrides)
    if name in LATENT_MOE_MODELS:
        from tensorflow_distributed_tpu.models import glm_moe_dsa
        return glm_moe_dsa.glm_moe_dsa_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "minicpm_sala":
        from tensorflow_distributed_tpu.models import minicpm_sala
        return minicpm_sala.minicpm_sala_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "granitemoehybrid":
        from tensorflow_distributed_tpu.models import granitemoehybrid
        return granitemoehybrid.granitemoehybrid_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "nemotron_h":
        from tensorflow_distributed_tpu.models import nemotron_h
        return nemotron_h.nemotron_h_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "exaone_moe":
        from tensorflow_distributed_tpu.models import exaone_moe
        return exaone_moe.exaone_moe_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "jamba":
        from tensorflow_distributed_tpu.models import jamba
        return jamba.jamba_lm(
            mesh=mesh, compute_dtype=compute_dtype, **overrides)
    if name == "pipelined_lm":
        from tensorflow_distributed_tpu.models import pipelined
        if dropout_rate is not None:
            overrides.setdefault("dropout_rate", dropout_rate)
        overrides.setdefault("compute_dtype", compute_dtype)
        if mesh is None:
            raise ValueError("pipelined_lm needs a mesh (pipe axis)")
        return pipelined.pipelined_lm(mesh=mesh, **overrides)
    raise ValueError(f"unknown model {name!r}; have {sorted(MODEL_NAMES)}")
