"""--seq-len / --synthetic-vocab: the long-context path is trainable
from the product surface (round-3 review weak #2 — ring attention,
RoPE theta, and remat existed but _make_lm_task pinned seq to 128).
"""

import jax
import numpy as np
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.train.loop import _build_model_and_state, train
from tensorflow_distributed_tpu.train.tasks import make_task


def _cfg(**kw):
    kw.setdefault("model", "gpt_lm")
    kw.setdefault("model_size", "tiny")
    kw.setdefault("dataset", "synthetic")
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("dropout_rate", 0.0)
    return TrainConfig(**kw)


def test_seq_len_validation():
    _cfg(seq_len=256).validate()
    with pytest.raises(ValueError, match="seq_len"):
        _cfg(seq_len=1).validate()
    with pytest.raises(ValueError, match="no effect"):
        _cfg(model="mnist_cnn", model_size="", seq_len=256).validate()
    with pytest.raises(ValueError, match="divisible"):
        _cfg(seq_len=130, mesh=MeshConfig(seq=4)).validate()
    with pytest.raises(ValueError, match="synthetic_vocab"):
        _cfg(synthetic_vocab=-1).validate()
    with pytest.raises(ValueError, match="byte corpus"):
        _cfg(dataset="text", synthetic_vocab=32).validate()


def test_seq_len_reaches_model_and_data(devices8):
    """The knob lands in BOTH places: the model's max_len/vocab and the
    data stream's window."""
    cfg = _cfg(seq_len=256, synthetic_vocab=32,
               mesh=MeshConfig(data=4, seq=2))
    cfg.validate()
    mesh = make_mesh(cfg.mesh, devices8)
    task = make_task(cfg, mesh)
    assert task.sample_input.shape == (4, 256)  # data-axis-wide batch
    model, state = _build_model_and_state(cfg, mesh, task)
    assert model.cfg.max_len == 256
    assert model.cfg.vocab_size == 32
    batch = next(task.train_stream(0))
    assert batch["tokens"].shape[1] == 256
    assert int(batch["tokens"].max()) < 32


def test_cli_exposes_seq_len():
    from tensorflow_distributed_tpu.config import parse_args

    cfg = parse_args(["--model", "gpt_lm", "--seq-len", "512",
                      "--synthetic-vocab", "128", "--mesh.seq", "2"])
    assert cfg.seq_len == 512 and cfg.synthetic_vocab == 128


@pytest.mark.slow
def test_train_long_context_via_cli_path(devices8):
    """round-3 review done-criterion: train() runs gpt_lm at seq >= 1024
    with mesh.seq > 1 (zigzag ring + RoPE + remat) end-to-end."""
    cfg = _cfg(seq_len=1024, pos_emb="rope", rope_theta=500000.0,
               remat="dots", batch_size=8, train_steps=2,
               eval_every=0, log_every=0, eval_batch_size=128,
               mesh=MeshConfig(data=2, seq=4))
    result = train(cfg)
    assert np.isfinite(result.final_metrics["loss"])
    assert int(jax.device_get(result.state.step)) == 2
