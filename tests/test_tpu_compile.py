"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

The sandbox has no chip, but the TPU compiler is installed and compiles
for a chip that is described and not attached
(``topologies.get_topology_desc``). Interpret-mode tests cannot see
what Mosaic refuses — a misaligned slice, too much VMEM, a kernel that
cannot be partitioned — so the kernels GPT-2 small trains through are
compiled here at their real widths, forward and backward. Nothing runs:
a compile that passes is not a chip run and says nothing about results
or times (``chip_smoke.py`` is the chip's proof).

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never while a
module is imported, so every xdist worker collects the same tests and
only the worker that runs this file loads libtpu; shardings and shapes
are built in fixtures or tests; no child processes, no second file; the
persistent compile cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""

import os
import re

import pytest

B, L, H, D = 8, 1024, 12, 64          # GPT-2 small at the smoke's batch
CELL_H = 16                           # GPT-2 medium: the train cells' heads
D_MODEL, VOCAB, TOKENS = 768, 50257, 8192
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh4(topo):
    """The --chips 4 layout: parallel.mesh.make_mesh over the four
    described devices, all on the data axis."""
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    return make_mesh(MeshConfig(data=4), list(topo.devices))


@pytest.fixture(scope="module")
def cache_off():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """lower+compile for the described chip; the kernel must be in the
    program and the program must fit the chip."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled


def _qkv(sharding, batch=B, seq=L, heads=H):
    import jax
    import jax.numpy as jnp
    return [jax.ShapeDtypeStruct((batch, seq, heads, D), jnp.bfloat16,
                                 sharding=sharding)] * 3


def _fwd_bwd(attend):
    """value_and_grad of a scalar of ``attend(q, k, v)`` wrt q, k, v —
    forward and both backward kernels in one program."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        out = attend(q, k, v)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(x.astype(jnp.float32)) for x in leaves)

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("window", [0, 512], ids=["causal", "window512"])
def test_flash_attention_fwd_bwd(one_chip, cache_off, window):
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention)
    _compile(_fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False)),
        *_qkv(one_chip))


@pytest.mark.parametrize("causal", [True, False],
                         ids=["diagonal", "off_diagonal"])
def test_flash_attention_partial_fwd_bwd(one_chip, cache_off, causal):
    """The ring path's per-shard attend: seq 8192 over a 4-way ring is
    2 * 4 half-blocks of 1024 rows; the diagonal blocks are causal, the
    rotated ones are not."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention_partial)
    _compile(_fwd_bwd(lambda q, k, v: flash_attention_partial(
        q, k, v, causal=causal, interpret=False)),
        *_qkv(one_chip, batch=2, seq=1024))


def _flash_kinds(compiled):
    """The flash calls of a compiled program as the benchmark's
    ``train.flash_roofline_share`` reader would tell them apart in a
    trace: the op's name is its HLO instruction (``harness/trace.py::
    short_name``), the kind is read off the RESULT TYPE (forward
    ``(T, f32[...])``, dq one tensor, dkv two of one dtype)."""
    import sys
    perfbench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from harness import trace as T
    from harness.loader import load_reader
    kind_of = load_reader("train.flash_roofline_share").__globals__["kind_of"]
    names = [T.short_name(line.strip())
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return sorted((re.search(r"flash_(fwd|dq|dkv)", name).group(1),
                   kind_of(name)) for name in names)


def test_flash_attention_at_the_train_cells_shape(one_chip, cache_off):
    """B = 8, H = 16, L = 1024, D = 64 (gpt2m-train-*): one grid step a
    head, 256-tiles inside. Three calls, under the names the ledger's
    breakdown prints, with the result types the roofline reader's
    regexes take: a fused dq+dkv call, or row statistics in another
    dtype, would make the metric null in both train cells."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention, flash_plan)
    import jax.numpy as jnp
    assert flash_plan(L, L, D, jnp.bfloat16, causal=True)[:4] == (
        1024, 1024, 256, 256)
    compiled = _compile(_fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False)),
        *_qkv(one_chip, heads=CELL_H))
    assert _flash_kinds(compiled) == [
        ("dkv", "dkv"), ("dq", "dq"), ("fwd", "fwd")]
    assert f"bf16[{B * CELL_H},{L},{D}]" in compiled.as_text()


# The train cells' three Mosaic kernels as PR 35's tree lowered them
# (sha256 of each payload's assembly WITHOUT debug info: the bytecode in
# a lowered program carries file paths and line numbers), and the whole
# lowered `jit_train_step` of gpt2m-train-dp1 with its payloads replaced
# by those digests and every `loc(...)` stripped (1,699,480 characters).
TRAIN_CELL_KERNELS = {
    "flash_fwd":
        "93cdcac14ee0c27088f3076d58f4a3666dc6dbb4f1ae1ec4d03cff11a5e75c53",
    "flash_dq":
        "000ededc6dc6a87adb1596ac4e8736e9d45de8c43a3df0b85bce4964d2bd8a43",
    "flash_dkv":
        "05a6b28ff00b263ea0e25f75105ec7731b68cdbbdc44eee74bf87fb8c97f8dd8",
}
TRAIN_STEP_DP1 = (
    "2e135100a9b6e64ac8a24a140d2cf8d9b916c5bc5dd4ba48cdd1159818b3c83a")


def _without_debug_info(lowered_text):
    """(a LOWERED program's text with the payload of every
    ``tpu_custom_call`` replaced by the sha256 of its Mosaic module's
    assembly without debug info and every ``loc(...)`` stripped, {kernel
    name: that sha256}): what two checkouts of one program agree on."""
    import base64
    import hashlib
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    kernels = {}

    def digest(m):
        raw = m.group(1).replace("\\22", '"').replace("\\5C", "\\")
        try:
            body = json.loads(raw).get("custom_call_config", {}).get("body")
        except ValueError:
            body = None
        if not body:
            return m.group(0)
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True     # "stable_mosaic"
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=False)
        sha = hashlib.sha256(asm.encode()).hexdigest()
        kernels[re.search(r"module @(\w+)", asm).group(1)] = sha
        return f'backend_config = "MOSAIC:{sha}"'

    text = re.sub(r'backend_config = "((?:[^"\\]|\\.)*)"', digest,
                  lowered_text)
    text = re.sub(r" loc\([^\n]*", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc")), kernels


def test_the_train_cells_kernels_are_the_ones_pr_35_compiled(one_chip,
                                                             cache_off):
    """PR 38 gave the forward kernel a value width, a scale and a
    selection operand for the latent family's prefill; the training
    cells run it at ``D == Dv`` with none of them, and their three
    kernels must lower to the Mosaic modules they were. A PR that MEANS
    to change them records the new digests here with its chip numbers."""
    import jax

    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention)
    lowered = jax.jit(_fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))).lower(
            *_qkv(one_chip, heads=CELL_H))
    assert _without_debug_info(lowered.as_text())[1] == TRAIN_CELL_KERNELS


def test_the_dp1_train_step_lowers_to_the_program_pr_35_lowered(
        topo, cache_off, monkeypatch):
    """The WHOLE ``jit_train_step`` of ``gpt2m-train-dp1`` (the
    configuration file's ``program_argv`` under the cell's batch, length
    and mesh, as ``perfbench/harness/train_runner.py`` passes them),
    lowered for the described chip: the text PR 35's tree lowers, its
    kernels the three above. A PR that leaves the training path alone
    leaves this digest alone; one that MEANS to change the step records
    the new one with its chip numbers."""
    import hashlib
    import json

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.config import MeshConfig, parse_args
    from tensorflow_distributed_tpu.models import build_model
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.optim import make_optimizer
    from tensorflow_distributed_tpu.train.state import abstract_train_state
    from tensorflow_distributed_tpu.train.step import make_train_step
    from tensorflow_distributed_tpu.train.tasks import make_task
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "gpt2-medium-train.json")) as f:
        source = json.load(f)
    cfg = parse_args(source["program_argv"] + [
        "--batch-size", "8", "--seq-len", "1024", "--mesh.data", "1",
        "--learning-rate", str(source["optimizer"]["learning_rate"]),
        "--seed", "0"])
    mesh = make_mesh(MeshConfig(data=1), list(topo.devices[:1]))
    model = build_model("gpt_lm", mesh=mesh, dropout_rate=0.0,
                        init_scheme=cfg.init_scheme,
                        compute_dtype=jnp.bfloat16, size="medium",
                        tie_embeddings=True, max_len=1024)
    task = make_task(cfg, mesh)
    state = abstract_train_state(model, make_optimizer(cfg),
                                 task.sample_input, mesh)
    batch = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        dict(next(iter(task.train_stream(0)))), dict(task.batch_shardings))
    step = make_train_step(mesh, cfg.seed, loss=task.loss,
                           batch_shardings=task.batch_shardings,
                           accum_steps=cfg.grad_accum_steps,
                           grad_norm_metric=cfg.log_grad_norm)
    text, kernels = _without_debug_info(step.lower(state, batch).as_text())
    assert kernels == TRAIN_CELL_KERNELS
    assert hashlib.sha256(text.encode()).hexdigest() == TRAIN_STEP_DP1


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_long_lk_branch(one_chip, cache_off, causal):
    """L = Lk = 4096: K and V of a head no longer fit the plan's byte
    budget beside q and the accumulators, so the grid gets its k-major
    axis and the band is walked block by block."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention, flash_plan)
    import jax.numpy as jnp
    assert flash_plan(4096, 4096, D, jnp.bfloat16, causal=causal)[:4] == (
        1024, 1024, 1024, 1024)
    compiled = _compile(_fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=False)),
        *_qkv(one_chip, batch=2, seq=4096))
    assert _flash_kinds(compiled) == [
        ("dkv", "dkv"), ("dq", "dq"), ("fwd", "fwd")]


@pytest.mark.parametrize("batch,heads", [(B, H), (4 * B, CELL_H)],
                         ids=["gpt2_small", "train_cell_dp4"])
def test_flash_attention_under_shard_map(data_mesh4, cache_off,
                                         monkeypatch, batch, heads):
    """The data-parallel step's attention: the dispatcher wraps the
    kernel in a shard_map over the mesh (Mosaic has no GSPMD rule).
    The dispatcher asks jax.default_backend(), which is the CPU here —
    steer it in the test, as it would answer on the chip. The second
    case is gpt2m-train-dp4's: 8 rows of 16 heads on each device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflow_distributed_tpu.ops.flash_attention import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = NamedSharding(data_mesh4, P("data"))
    compiled = _compile(_fwd_bwd(lambda q, k, v: attention(
        q, k, v, causal=True, mesh=data_mesh4)),
        *_qkv(rows, batch=batch, heads=heads))
    # Each device runs the kernel on ITS rows: the packed [B*H, L, D]
    # operand is the device's shard, not the global batch.
    text = compiled.as_text()
    assert f"bf16[{batch // 4 * heads},{L},{D}]" in text
    assert f"bf16[{batch * heads},{L},{D}]" not in text
    assert [kind for _, kind in _flash_kinds(compiled)] == [
        "dkv", "dq", "fwd"]


@pytest.mark.parametrize("w_dtype,w_vocab_axis", [
    ("float32", 1),    # the untied lm_head param as the loss receives it
    ("bfloat16", 0),   # a tied embedding table in compute dtype
], ids=["f32_head", "bf16_tied_table"])
def test_fused_ce_kernel_fwd_bwd(one_chip, cache_off, w_dtype,
                                 w_vocab_axis):
    """The fused-loss kernel at the train step's shapes. The f32 head
    is the case the compiler refused before the dw kernel sized its
    vocab block to the scoped-VMEM stack (ops/fused_ce_kernel.py)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.ops.fused_ce_kernel import (
        fused_ce_sums_kernel)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w_shape = ((VOCAB, D_MODEL) if w_vocab_axis == 0
               else (D_MODEL, VOCAB))

    def loss(x, w, bias, targets, mask):
        ce, _, n = fused_ce_sums_kernel(
            x, w, bias, targets, mask, VOCAB,
            w_vocab_axis=w_vocab_axis, interpret=False)
        return ce / n

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
             sds((TOKENS, D_MODEL), jnp.bfloat16),
             sds(w_shape, jnp.dtype(w_dtype)),
             sds((VOCAB,), jnp.float32),
             sds((TOKENS,), jnp.int32), sds((TOKENS,), jnp.float32))


def test_serve_decode_step_updates_the_cache_in_place(one_chip, cache_off,
                                                      monkeypatch):
    """The serve cells' engine programs (GPT-2 large, 16 slots of 1024)
    as the chip compiles them: the donated cache is aliased, so the
    decode step plans one cache and not two (9.2 GB before PR 26), the
    row insert holds nothing beside the cache and the row, and the
    step writes each token through the Pallas kernel — no scatter loop
    and no whole-leaf copy (the kernel's transposed view must stay a
    bitcast). The guard that a later PR cannot quietly lose either."""
    import re

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve import engine

    slots, max_len = 16, 1024
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = gpt_lm(None, size="large", max_len=max_len, dropout_rate=0.0,
                   tie_embeddings=True, compute_dtype=jnp.bfloat16)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    def cache_of(params, rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    trained = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    # What serve_run hands the engine (serve/params.py, PR 49): each
    # matmul weight once, in the dtype its product reads.
    params = described(jax.eval_shape(model.serving_params, trained))
    assert _bytes(trained) == 3_096_120_320
    assert _bytes(params) == 1_808_371_200 < 1.9e9
    cache, row = cache_of(params, slots), cache_of(params, 1)
    kv = [c for c in jax.tree_util.tree_leaves(cache) if c.ndim]
    cache_bytes = sum(c.size * c.dtype.itemsize for c in kv)
    assert len(kv) == 72 and cache_bytes == 3_019_898_880
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def plan(fn, *args):
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        return compiled.as_text(), peak

    step = engine._compiled_step.__wrapped__(model)
    text, peak = plan(step, params, cache, vec, host)
    # Re-pinned on purpose in PR 49 (8e9 before: parameters 3.1 + ONE
    # cache 3.02): the serving tree's 1.81 + the cache.
    assert peak < 5.2e9, peak
    # No matmul weight is read in float32 and rounded inside the step:
    # the four kernels of a block and the tied head's table arrive in
    # bfloat16 (the lookup's float32 table feeds a gather, not a convert).
    rounded = (r"convert\(f32\[(1280,3,20,64|1280,3840|20,64,1280|1280,1280"
               r"|1280,5120|5120,1280|50257,1280)\]")
    assert not re.search(rounded, text)
    assert "bf16[50257,1280]" in text and "f32[50257,1280]" in text
    def calls(kernel):               # by the instruction's own name
        return len(re.findall(rf'%{kernel}[.\d]* = \S+ custom-call\(', text))

    assert calls("kv_token_write") == 72
    # ... and attends each layer's K and V through ONE Pallas call that
    # walks the live rows' blocks (ops/kv_attend.py, PR 48): no whole-leaf
    # reduction is left in the step.
    assert calls("kv_decode_attend") == 36
    assert " while(" not in text
    leaf = r"bf16\[16,(1024,20,64|20,64,1024)\]\S* (copy|transpose)\("
    assert not re.search(leaf, text)

    text, peak = plan(engine._insert_row, cache, row, scalar)
    assert peak < cache_bytes + 0.25e9, peak
    assert not re.search(leaf, text)


# -- glm_moe_dsa (PR 28): the serve programs of the long-context cell --------

GLM_SLOTS, GLM_CONFIG = 32, "perfbench/configs/glm-5.2-serve.json"


@pytest.fixture(scope="module")
def glm(one_chip):
    """GLM-5.2 as the benchmark's cell runs it (published widths, one
    chip's share), its parameters and caches as described shapes."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import glm_moe_dsa

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = glm_moe_dsa.glm_moe_dsa_lm(
        source=os.path.join(root, GLM_CONFIG), compute_dtype=jnp.bfloat16)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of


def _bytes(tree):
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def test_glm_shapes_are_the_published_widths(glm):
    """The program's parameter shapes show every published width, the
    share held (8 experts, 19,360 rows), bfloat16 storage, and the
    two-kind cache: 5 latent leaves, index keys on the 2 full layers."""
    import jax
    import jax.numpy as jnp

    model, params, cache_of = glm
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert shape("layer_0", "attn", "q_a", "kernel") == (6144, 2048)
    assert shape("layer_0", "attn", "q_b", "kernel") == (2048, 64, 256)
    assert shape("layer_0", "attn", "kv_a", "kernel") == (6144, 576)
    assert shape("layer_0", "attn", "kv_b", "kernel") == (512, 64, 448)
    assert shape("layer_0", "attn", "o", "kernel") == (64, 256, 6144)
    assert shape("layer_0", "attn", "indexer", "wq_b", "kernel") == (
        2048, 32, 128)
    assert shape("layer_0", "mlp", "gate", "kernel") == (6144, 12288)
    assert shape("layer_1", "moe", "router", "kernel") == (6144, 256)
    assert shape("layer_1", "moe", "experts_gate", "kernel") == (
        8, 6144, 2048)
    assert shape("layer_1", "moe", "shared_down", "kernel") == (2048, 6144)
    assert shape("lm_head", "kernel") == (6144, 19360)
    assert [("indexer" in params[f"layer_{i}"]["attn"],
             "moe" in params[f"layer_{i}"]) for i in range(5)] == [
        (True, False), (False, True), (False, True), (False, True),
        (True, True)]
    assert _bytes(params) == 5_347_117_056           # 2.67 B bfloat16
    assert all(x.dtype == jnp.bfloat16 or x.shape == (256,)
               for x in jax.tree_util.tree_leaves(params))
    cache = cache_of(GLM_SLOTS)
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    # 576 numbers a token, stored in rows of 640 (whole lane tiles: the
    # leaf then stays row-major and is never copied whole)
    assert (model.cfg.latent_dim, model.cfg.latent_row) == (576, 640)
    assert kinds == {"latent": [(32, 16384, 640)] * 5,
                     "index_keys": [(32, 16384, 128)] * 2}
    assert _bytes(cache) == 32 * 16384 * (5 * 1280 + 2 * 256)


def _leaf_at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@pytest.fixture(scope="module")
def glm_decode_step(glm, one_chip, cache_off):
    """The decode program of the long-context cell (32 slots) as the chip
    compiles it: (HLO text, memory analysis, the cache's bytes)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    model, params, cache_of = glm
    cache = cache_of(GLM_SLOTS)
    vec = jax.ShapeDtypeStruct((GLM_SLOTS,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, GLM_SLOTS), jnp.int32,
                                sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = engine._compiled_step.__wrapped__(model).lower(
            params, cache, vec, host).compile()
    return compiled.as_text(), compiled.memory_analysis(), _bytes(cache)


def _planned(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _pallas_calls(text):
    return re.findall(r"%([a-z_][a-z0-9_]*)[.0-9]* = [^\n]*custom_call_target="
                      r'"tpu_custom_call"', text)


def test_glm_decode_step_reads_selected_rows_through_named_kernels(
        glm_decode_step):
    """The decode program as the chip compiles it: the donated two-kind
    cache is aliased (one cache in the plan), and the parts a step is
    made of are the named kernels a capture can attribute: 2 index-score
    calls (the full layers), 5 latent attends, 12 grouped matmuls (gate,
    up, down of the 4 expert layers)."""
    import re

    text, mem, cache_bytes = glm_decode_step
    assert mem.alias_size_in_bytes >= cache_bytes
    assert _planned(mem) < 10.5e9     # parameters 5.35 + ONE cache 3.62
    names = _pallas_calls(text)
    assert names.count("dsa_index_scores") == 2
    assert names.count("mla_latent_attend") == 5
    assert names.count("gmm") == 12
    assert names.count("latent_row_write") == 7      # one a cache leaf
    # no cache leaf is copied or re-laid-out whole (the 576-wide leaf
    # was: 1.96 ms a layer a step on the chip, PR 28)
    assert not re.search(
        r"bf16\[32,16384,(640|128)\]\S* (copy|transpose)\(", text)
    # the attend reads gathered rows, never a [slots, max_len] score
    assert not re.search(r"f32\[32,64,16384\]", text)


def test_glm_decode_step_gathers_one_slots_rows_a_turn_from_the_leaf(
        glm_decode_step):
    """The gather of the selected rows (PR 34) as the chip compiles it: no
    gather of all 32 slots' rows is left, each of the 5 layers gathers ONE
    slot's 2,048 rows a turn of a loop straight from the [32, 16384, 640]
    leaf (no copy or slice of it: PR 28 paid 1.96 ms a layer for a copy),
    into a block the attend kernel takes whole, with the kernels' calls
    and the plan (9.07 GB at the parent) as they were."""
    text, mem, _ = glm_decode_step
    names = _pallas_calls(text)
    assert (names.count("mla_latent_attend"), names.count("dsa_index_scores"),
            names.count("latent_row_write")) == (5, 2, 7)
    assert not re.search(r"bf16\[32,16384,640\]\S* copy\(", text)
    assert not re.search(r"bf16\[1,16384,640\]", text)
    assert not re.search(r"bf16\[32,2048,640\]\S* gather\(", text)
    turns = re.findall(
        r"bf16\[2048,640\]\S* gather\(%\S+, %\S+\), [^\n]*"
        r"slice_sizes=\{1,1,640\}[^\n]*mla_decode_attend/while/body", text)
    assert len(turns) == 5
    assert len(re.findall(r"mla_latent_attend[.0-9]* = f32\[32,64,512\]"
                          r"[^\n]*bf16\[32,2048,640\]", text)) == 5
    assert _planned(mem) <= 9.07e9 + 0.1e9, _planned(mem)


# What the parent's prefill programs planned without the cache (the XLA
# loop in place of the fused attend: this compile on the parent tree, PR
# 38): GLM's 14,336 bucket 10,442,262,528 bytes, A.X-K1's 8,192 bucket
# 10,248,428,032, the "10.44 GB" and "10.25 GB" of PERF.md section 4.
# With the kernel 10,441,681,920 and 10,249,911,808 (1.5 MB over the
# parent's in a plan of 10.25 GB: held to the recorded 10.25).
GLM_PREFILL_14336_PARENT, AXK1_PREFILL_8192_RECORDED = 10_442_262_528, 10.25e9


# The temporaries of the routed families' prefill programs since PR 44 (the
# held experts' combine one kernel over the held rows, its result updated
# in place: no gathered [tokens, k, D] rows), beside what the parent
# planned (PR 43, this compile on its tree). Pinned ON PURPOSE: a program
# that plans more has grown a buffer.
PREFILL_TEMPORARIES = {                  # (model, bucket): bytes  [parent]
    ("glm", 3072): 900_487_680,          # [854,071,296: the one that grew,
                                         #  46 MB in a plan of 6.36 GB]
    ("glm", 14336): 3_970_189_824,       # [3,970,673,664]
    ("axk1", 8192): 1_677_190_144,       # [1,678,770,688]
    ("granite", 512): 112_184_832,       # [164,495,360]
    ("granite", 3072): 789_923_840,      # [795,028,992]
    ("nemotron", 1024): 278_507_520,     # [282,539,520]
    ("nemotron", 4096): 1_077_611_520,   # [1,082,224,128]
}


def _no_score_block_in_memory(text):
    """The XLA loop's [heads, 512, 512] float32 score block (and its
    bfloat16 probabilities) of one turn: gone from a program whose
    attend is the fused kernel."""
    return not re.search(r"(f32|bf16)\[(1,)?64,512,512\]", text)


@pytest.mark.parametrize("bucket, most", [
    (3072, 0.9 * HBM_BYTES - 3.63e9), (14336, GLM_PREFILL_14336_PARENT)])
def test_glm_prefill_fits_beside_weights_and_cache(glm, one_chip, cache_off,
                                                   monkeypatch, bucket, most):
    """The smallest and the largest bucket's prefill program (the
    largest about half a minute here): the grouped matmuls are in it,
    the masked attend is the fused kernel under its own name, one call a
    layer, the selection an operand of it; no [L, L] score tensor per
    head and no score block of the XLA loop exists; the largest plans no
    more than the parent's 10.44 GB and fits beside the 3.62 GB cache."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, _ = glm
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"glm prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak <= most, peak
    assert mem.temp_size_in_bytes <= PREFILL_TEMPORARIES["glm", bucket]
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("gmm") >= 12
    assert names.count("mla_prefill_attend") == 5
    assert set(names) == {"gmm", "mla_prefill_attend", "moe_combine_held"}
    assert names.count("moe_combine_held") * 3 == names.count("gmm")
    assert f"s8[{bucket},{bucket}]" in text          # the selection's tiles
    assert not re.search(rf"f32\[(64|32),{bucket},{bucket}\]", text)
    assert _no_score_block_in_memory(text)
    # only the last position's logits are computed
    assert not re.search(rf"f32\[1,{bucket},19360\]", text)


# -- axk1 (PR 33): the same family without a selection, at 48 slots -----------

AXK1_SLOTS, AXK1_CONFIG = 48, "perfbench/configs/ax-k1-serve.json"


@pytest.fixture(scope="module")
def axk1(one_chip):
    """A.X-K1 as the benchmark's cell runs it (published widths, one
    chip's share), its parameters and caches as described shapes."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model("axk1", source=os.path.join(root, AXK1_CONFIG),
                        compute_dtype=jnp.bfloat16, max_len=10240)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of


def test_axk1_shapes_are_the_published_widths(axk1):
    """Every published width, the share held (12 of 192 experts, 20,480
    rows), no indexer leaf anywhere, and a cache of ONE kind."""
    import jax

    model, params, cache_of = axk1
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert shape("layer_0", "attn", "q_a", "kernel") == (7168, 1536)
    assert shape("layer_0", "attn", "q_b", "kernel") == (1536, 64, 192)
    assert shape("layer_0", "attn", "kv_a", "kernel") == (7168, 576)
    assert shape("layer_0", "attn", "kv_b", "kernel") == (512, 64, 256)
    assert shape("layer_0", "attn", "o", "kernel") == (64, 128, 7168)
    assert shape("layer_0", "mlp", "gate", "kernel") == (7168, 18432)
    assert shape("layer_1", "moe", "router", "kernel") == (7168, 192)
    assert shape("layer_1", "moe", "experts_gate", "kernel") == (
        12, 7168, 2048)
    assert shape("layer_4", "moe", "shared_down", "kernel") == (2048, 7168)
    assert shape("lm_head", "kernel") == (7168, 20480)
    assert [("indexer" in params[f"layer_{i}"]["attn"],
             "moe" in params[f"layer_{i}"]) for i in range(5)] == [
        (False, False)] + [(False, True)] * 4
    # 3,491,258,112 parameters, of which 4 x 192 router biases float32
    assert _bytes(params) == 2 * 3_491_258_112 + 2 * 4 * 192
    cfg = model.cfg
    assert (cfg.n_group, cfg.topk_group, cfg.router_experts,
            len(cfg.experts_held), cfg.num_experts_per_tok) == (
        8, 4, 192, 12, 8)
    assert cfg.rope_scaling.factor == 32
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            cache_of(AXK1_SLOTS)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"latent": [(48, 10240, 640)] * 5}


def test_axk1_decode_step_attends_the_cache_in_place(
        axk1, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, 5 dense latent attends under their own name (a prefix of
    which the step's split matches), 12 grouped matmuls, 5 row writes;
    no gather of cache rows, no [slots, heads, max_len] score array, no
    whole-leaf copy."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of = axk1
    cache = cache_of(AXK1_SLOTS)
    vec = jax.ShapeDtypeStruct((AXK1_SLOTS,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, AXK1_SLOTS), jnp.int32,
                                sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"axk1 decode step plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak < 10.6e9, peak        # parameters 6.98 + ONE cache 3.15
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("mla_latent_attend_dense") == 5
    assert names.count("gmm") == 12
    assert names.count("latent_row_write") == 5
    assert "dsa_index_scores" not in names
    assert not re.search(r"bf16\[48,10240,640\]\S* (copy|transpose)\(",
                         text)
    assert not re.search(r"f32\[48,64,10240\]", text)
    assert " gather(" not in text or not re.search(
        r"bf16\[48,\d+,640\]\S* gather\(", text)


def test_axk1_largest_prefill_fits_beside_weights_and_cache(
        axk1, one_chip, cache_off, monkeypatch):
    """The 8,192 bucket's prefill program: the attend is the fused
    kernel under its own name, one call a layer, causal by block index
    (no [L, L] array of any type, no score block of the XLA loop), the
    grouped matmuls in it, only the last position's logits, and a plan
    no larger than the 10.25 GB recorded of the parent's that fits beside the 3.15 GB
    cache."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, _ = axk1
    prompt = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, 8192).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"axk1 prefill 8192 plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak <= AXK1_PREFILL_8192_RECORDED, peak
    assert mem.temp_size_in_bytes <= PREFILL_TEMPORARIES["axk1", 8192]
    assert peak + 48 * 10240 * 6400 < 15e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("gmm") >= 12
    assert names.count("mla_prefill_attend") == 5
    assert set(names) == {"gmm", "mla_prefill_attend", "moe_combine_held"}
    assert names.count("moe_combine_held") * 3 == names.count("gmm")
    assert not re.search(r"\[(64,)?8192,8192\]", text)
    assert _no_score_block_in_memory(text)
    assert not re.search(r"f32\[1,8192,20480\]", text)


# -- minicpm_sala (PR 35): linear layers with a recurrent state beside
# -- block-sparse grouped-query layers, one 8-layer pipeline stage ------------

SALA_SLOTS, SALA_CONFIG = 32, "perfbench/configs/minicpm-sala-serve.json"


@pytest.fixture(scope="module")
def sala(one_chip):
    """MiniCPM-SALA as the benchmark's cell runs it (published widths,
    published layers 9-16), its parameters and caches as described
    shapes."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model("minicpm_sala",
                        source=os.path.join(root, SALA_CONFIG),
                        compute_dtype=jnp.bfloat16, max_len=25600)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of


def test_sala_shapes_are_the_published_widths(sala):
    """Every published width, two key-value heads for 32 queries, and a
    cache of three kinds: rows a position, rows a pooled window, and a
    state with no position axis."""
    import jax

    model, params, cache_of = sala
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert shape("layer_0", "mixer", "q", "kernel") == (4096, 32, 128)
    assert shape("layer_0", "mixer", "k", "kernel") == (4096, 2, 128)
    assert shape("layer_0", "mixer", "g", "kernel") == (4096, 32, 128)
    assert shape("layer_1", "mixer", "k", "kernel") == (4096, 32, 128)
    assert shape("layer_1", "mixer", "o", "kernel") == (32, 128, 4096)
    assert shape("layer_1", "mixer", "o_norm", "scale") == (4096,)
    assert shape("layer_3", "mlp", "gate", "kernel") == (4096, 16384)
    assert shape("lm_head", "kernel") == (4096, 73448)
    assert ["o_norm" in params[f"layer_{i}"]["mixer"]
            for i in range(8)] == [False] + [True] * 6 + [False]
    assert _bytes(params) == 2 * 2_820_569_088
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            cache_of(SALA_SLOTS)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"kv": [(32, 25600, 512)] * 2,
                     "pooled_keys": [(32, 1664, 256)] * 2,
                     "state": [(32, 32, 128, 128)] * 6,
                     "state_pos": [(32,)]}
    assert _bytes(cache_of(SALA_SLOTS)) == 2_134_900_864


def test_sala_decode_step_moves_states_and_blocks_in_place(
        sala, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, every new kernel under its own name (6 state steps, 2 score
    kernels, 2 block attends, 4 row writes), no whole-leaf copy of a
    cache leaf and no gather of K or V rows."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of = sala
    cache = cache_of(SALA_SLOTS)
    vec = jax.ShapeDtypeStruct((SALA_SLOTS,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, SALA_SLOTS), jnp.int32,
                                sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"sala decode step plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak < 7.9e9, peak         # parameters 5.64 + ONE cache 2.13
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("lightning_state_step") == 6
    assert names.count("sparse_block_scores") == 2
    assert names.count("sparse_block_attend") == 2
    assert names.count("latent_row_write") == 4
    for leaf in (r"bf16\[32,25600,512\]", r"f32\[32,32,128,128\]",
                 r"bf16\[32,1664,256\]"):
        assert not re.search(leaf + r"\S* (copy|transpose|gather)\(", text)


@pytest.mark.parametrize("bucket", [24576])
def test_sala_largest_prefill_fits_beside_weights_and_cache(
        sala, one_chip, cache_off, monkeypatch, bucket):
    """The 24,576 bucket's prefill program: the chunked scan under its
    name in every linear layer, the sparse attend an XLA loop by tiles (no
    [L, L] array of any type), only the last position's logits, and a plan
    under 15 GB with and without the 2.13 GB cache beside it."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of = sala
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"sala prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak < 15e9 and peak + _bytes(cache_of(SALA_SLOTS)) < 15e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("lightning_chunk_scan") == 6
    assert set(names) == {"lightning_chunk_scan"}
    assert not re.search(rf"\[(32,|2,16,)?{bucket},{bucket}\]", text)
    assert not re.search(rf"f32\[1,{bucket},73448\]", text)


# -- granitemoehybrid (PR 41): Mamba-2 state-space layers with a convolution
# -- ring beside one NoPE grouped-query layer, 36 of 72 routed experts -------

GRANITE_SLOTS = 64
GRANITE_CONFIG = "perfbench/configs/granite-4.0-h-small-serve.json"


@pytest.fixture(scope="module")
def granite(one_chip):
    """granite-4.0-h-small as the benchmark's cell runs it (published
    widths, published layers 0-9, experts 0-35, vocabulary rows
    0-50,175), its parameters and caches as described shapes."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model("granitemoehybrid",
                        source=os.path.join(root, GRANITE_CONFIG),
                        compute_dtype=jnp.bfloat16, max_len=4096)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of


def test_granite_shapes_are_the_published_widths(granite):
    """Every published width, 8 key-value heads for 32 queries, 36 experts
    of 768 under a router of 72, and a cache of four kinds: rows a
    position, a state with no position axis, a ring of four rows, and the
    state's stamp."""
    import jax

    model, params, cache_of = granite
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert shape("layer_0", "mixer", "in_proj", "kernel") == (4096, 16768)
    assert shape("layer_0", "mixer", "out_proj", "kernel") == (8192, 4096)
    assert shape("layer_0", "mixer", "conv1d", "kernel") == (4, 8448)
    assert shape("layer_0", "mixer", "A_log", "value") == (128,)
    assert shape("layer_5", "mixer", "q", "kernel") == (4096, 32, 128)
    assert shape("layer_5", "mixer", "k", "kernel") == (4096, 8, 128)
    assert shape("layer_3", "moe", "router", "kernel") == (4096, 72)
    assert shape("layer_3", "moe", "experts_gate", "kernel") == (
        36, 4096, 768)
    assert shape("layer_3", "moe", "shared_down", "kernel") == (1536, 4096)
    assert shape("tok_emb") == (50176, 4096)
    assert ["in_proj" in params[f"layer_{i}"]["mixer"]
            for i in range(10)] == [True] * 5 + [False] + [True] * 4
    assert _bytes(params) == 2 * 4_757_211_776
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            cache_of(GRANITE_SLOTS)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"kv": [(64, 4096, 2048)],
                     "state": [(64, 128, 8192)] * 9,
                     "conv": [(64, 4, 8448)] * 9,
                     "state_pos": [(64,)]}
    assert _bytes(cache_of(GRANITE_SLOTS)) == 3_528_589_568


def test_granite_decode_step_moves_states_in_place(
        granite, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, 9 state steps and 30 grouped matmuls under their names, the
    K and V row written in place, and no whole-leaf copy of a state."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of = granite
    cache = cache_of(GRANITE_SLOTS)
    vec = jax.ShapeDtypeStruct((GRANITE_SLOTS,), jnp.int32,
                               sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, GRANITE_SLOTS), jnp.int32,
                                sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"granite decode step plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak < 13.4e9, peak        # parameters 9.51 + ONE cache 3.53
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("ssd_state_step") == 9
    assert names.count("gmm") == 30
    assert names.count("latent_row_write") == 1
    # the attention layer's attend walks the live rows' blocks (PR 48)
    assert names.count("gqa_dense_attend") == 1
    assert not re.search(r"f32\[64,128,8192\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("bucket", [512, 3072])
def test_granite_largest_prefill_fits_beside_weights_and_cache(
        granite, one_chip, cache_off, monkeypatch, bucket):
    """The 3,072 bucket's prefill program (and the median prompt's, 512):
    the chunked scan under its name in every state-space layer, the
    attention layer one flash forward, only the last position's logits,
    and a plan under 15 GB with the 3.53 GB cache beside it, under the
    block rows ``moe_plan`` gives the held experts (8,192 of the 3,072
    bucket's 30,720 pairs a trip; the static worst case, every pair in
    one block, is not materialised)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of = granite
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"granite prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}")
    assert peak + _bytes(cache_of(GRANITE_SLOTS)) < 15e9, peak
    assert mem.temp_size_in_bytes <= PREFILL_TEMPORARIES["granite", bucket]
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("ssd_chunk_scan") == 9
    assert names.count("mla_prefill_attend") == 1
    assert set(names) == {"ssd_chunk_scan", "mla_prefill_attend", "gmm",
                          "moe_combine_held"}
    assert names.count("gmm") == 30     # one block's three, ten layers
    assert names.count("moe_combine_held") == 10
    assert not re.search(rf"f32\[{10 * bucket},4096\]", text)
    assert not re.search(rf"\[(32,)?{bucket},{bucket}\]", text)
    assert not re.search(rf"f32\[1,{bucket},50176\]", text)


# -- nemotron_h (PR 43): single-part layers, Mamba-2 with 8 groups of B and C,
# -- one NoPE grouped-query layer, 128 of 512 ungated experts in a latent ----

NEMOTRON_CONFIG = "perfbench/configs/nemotron-3-super-serve.json"


@pytest.fixture(scope="module")
def nemotron(one_chip):
    """Nemotron 3 Super as the benchmark's cell runs it (published widths,
    published layers 0-10, experts 0-127, vocabulary rows 0-32,767), its
    parameters and caches as described shapes, and the cell's slots."""
    import json

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, NEMOTRON_CONFIG)
    with open(path) as f:
        slots = json.load(f)["serve"]["num_slots"]
    model = build_model("nemotron_h", source=path,
                        compute_dtype=jnp.bfloat16, max_len=6144)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of, slots


def test_nemotron_shapes_are_the_published_widths(nemotron):
    """Every published width, 2 key-value heads for 32 queries, 8 groups
    of B and C in the convolution's 10,240 channels, 128 ungated experts
    of 2,688 in a 1,024 latent under a router of 512, untied embedding and
    head, and a cache in which only 6 of the 11 layers keep anything."""
    import jax

    model, params, cache_of, slots = nemotron
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert model.cfg.layers == ("mamba", "moe") * 3 + (
        "mamba", "attention", "moe", "mamba", "moe")
    assert shape("layer_0", "mixer", "in_proj", "kernel") == (4096, 18560)
    assert shape("layer_0", "mixer", "out_proj", "kernel") == (8192, 4096)
    assert shape("layer_0", "mixer", "conv1d", "kernel") == (4, 10240)
    assert shape("layer_7", "mixer", "q", "kernel") == (4096, 32, 128)
    assert shape("layer_7", "mixer", "k", "kernel") == (4096, 2, 128)
    assert shape("layer_1", "moe", "router", "kernel") == (4096, 512)
    assert shape("layer_1", "moe", "latent_down", "kernel") == (4096, 1024)
    assert shape("layer_1", "moe", "experts_up", "kernel") == (
        128, 1024, 2688)
    assert shape("layer_1", "moe", "experts_down", "kernel") == (
        128, 2688, 1024)
    assert "experts_gate" not in params["layer_1"]["moe"]
    assert shape("layer_1", "moe", "shared_down", "kernel") == (5376, 4096)
    assert shape("tok_emb") == (32768, 4096)
    assert shape("lm_head", "kernel") == (4096, 32768)
    assert _bytes(params) == 2 * 4_648_163_712 + 5 * 512 * 2
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache_of(slots)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"kv": [(slots, 6144, 512)],
                     "state": [(slots, 128, 8192)] * 5,
                     "conv": [(slots, 4, 10240)] * 5,
                     "state_pos": [(slots,)]}
    assert _bytes(cache_of(slots)) == slots * 27_672_580
    assert not any("moe" in layer for layer in cache_of(slots).values()
                   if isinstance(layer, dict))


def test_nemotron_decode_step_runs_its_experts_as_grouped_matmuls(
        nemotron, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, 5 state steps under their name, 10 grouped matmuls (two an
    expert layer: the experts are ungated, and slots x 22 pair rows are
    whole row tiles, so none left megablox for a ragged dot), the K and V
    row written in place, and no whole-leaf copy of a state."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = nemotron
    cache = cache_of(slots)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"nemotron decode step plan at {slots} slots: {peak} bytes, "
          f"temporaries {mem.temp_size_in_bytes}")
    assert peak < _bytes(params) + _bytes(cache) + 0.3e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("ssd_state_step") == 5
    assert names.count("gmm") == 10
    assert names.count("latent_row_write") == 1
    # the attention layer's attend walks the live rows' blocks (PR 48)
    assert names.count("gqa_dense_attend") == 1
    assert "ragged" not in text
    assert not re.search(rf"f32\[{slots},128,8192\]\S* (copy|transpose)\(",
                         text)


@pytest.mark.parametrize("bucket", [1024, 4096])
def test_nemotron_largest_prefill_fits_beside_weights_and_cache(
        nemotron, one_chip, cache_off, monkeypatch, bucket):
    """The 4,096 bucket's prefill program (and the median prompt's,
    1,024): the chunked scan under its name in every state-space layer,
    the attention layer one flash forward, only the last position's
    logits, and a plan under 15 GB with the cache beside it, under the
    block rows ``moe_plan`` gives the held experts (the static worst case,
    every one of the bucket's 22 pairs a token in one block, is not
    materialised)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = nemotron
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"nemotron prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}; with {slots} slots of cache "
          f"{peak + _bytes(cache_of(slots))}")
    assert peak + _bytes(cache_of(slots)) < 15e9, peak
    assert mem.temp_size_in_bytes <= PREFILL_TEMPORARIES["nemotron", bucket]
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("ssd_chunk_scan") == 5
    assert names.count("mla_prefill_attend") == 1
    assert set(names) == {"ssd_chunk_scan", "mla_prefill_attend", "gmm",
                          "moe_combine_held"}
    assert names.count("gmm") == 10     # one block's two, five layers
    assert names.count("moe_combine_held") == 5
    assert "ragged" not in text
    assert not re.search(rf"f32\[{22 * bucket},1024\]", text)
    # no score square of the 32 heads (a [bucket, bucket] alone is the
    # 1,024 bucket's latent rows, or W_q at 4,096)
    assert not re.search(rf"\[(32|2,16),{bucket},{bucket}\]", text)
    assert not re.search(rf"f32\[1,{bucket},32768\]", text)


# -- exaone_moe (PR 47): three rotary 128-token window layers to one full
# -- layer, a ring of K and V beside the whole rows, 16 of 128 gated experts -

KEXAONE_CONFIG = "perfbench/configs/k-exaone-236b-serve.json"


@pytest.fixture(scope="module")
def kexaone(one_chip):
    """K-EXAONE as the benchmark's cell runs it (published widths,
    published layers 0-4, experts 0-15, vocabulary rows 0-19,199), its
    parameters and caches as described shapes, and the cell's slots."""
    import json

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, KEXAONE_CONFIG)
    with open(path) as f:
        slots = json.load(f)["serve"]["num_slots"]
    model = build_model("exaone_moe", source=path,
                        compute_dtype=jnp.bfloat16, max_len=16384)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of, slots


def test_kexaone_shapes_are_the_published_widths(kexaone):
    """Every published width, 8 key-value heads for 64 queries of 128, a
    dense MLP of 18,432 in layer 0 and 16 gated experts of 2,048 under a
    router of 128 after, untied embedding and head, and a cache of two
    kinds of K and V leaf: four rings of 128 rows and one full row."""
    import jax

    model, params, cache_of, slots = kexaone
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert [a for a, _ in model.cfg.layers] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert shape("layer_0", "mixer", "q", "kernel") == (6144, 64, 128)
    assert shape("layer_0", "mixer", "k", "kernel") == (6144, 8, 128)
    assert shape("layer_3", "mixer", "o", "kernel") == (64, 128, 6144)
    assert shape("layer_3", "mixer", "q_norm", "scale") == (128,)
    assert shape("layer_0", "mlp", "gate", "kernel") == (6144, 18432)
    assert shape("layer_1", "moe", "router", "kernel") == (6144, 128)
    assert shape("layer_1", "moe", "experts_gate", "kernel") == (
        16, 6144, 2048)
    assert shape("layer_1", "moe", "experts_down", "kernel") == (
        16, 2048, 6144)
    assert shape("layer_1", "moe", "shared_down", "kernel") == (2048, 6144)
    assert shape("tok_emb") == (19200, 6144)
    assert shape("lm_head", "kernel") == (6144, 19200)
    assert _bytes(params) == 2 * 3_712_028_416 + 4 * 128 * 2
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache_of(slots)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"kv": [(slots, 16384, 2048)],
                     "kv_ring": [(slots, 128, 2048)] * 4}
    assert _bytes(cache_of(slots)) == slots * (67_108_864 + 2_097_152)


def test_kexaone_decode_step_attends_the_full_layer_through_its_kernel(
        kexaone, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, ONE ``gqa_dense_attend`` (the full layer; the four rings'
    attends are XLA), 12 grouped matmuls (three an expert layer, none left
    megablox for a ragged dot), five rows of K and V written in place, and
    no float32 score block over the full layer's 16,384 positions."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = kexaone
    cache = cache_of(slots)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"kexaone decode step plan at {slots} slots: {peak} bytes, "
          f"temporaries {mem.temp_size_in_bytes}")
    assert peak < _bytes(params) + _bytes(cache) + 0.3e9, peak
    assert peak < 15e9
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("gqa_dense_attend") == 1
    assert names.count("gmm") == 12
    assert names.count("latent_row_write") == 5
    assert set(names) == {"gqa_dense_attend", "gmm", "latent_row_write"}
    assert "ragged" not in text
    assert not re.search(rf"f32\[{slots},(64|8,8),16384\]", text)


@pytest.mark.parametrize("bucket", [3072, 12288])
def test_kexaone_largest_prefill_fits_beside_weights_and_cache(
        kexaone, one_chip, cache_off, monkeypatch, bucket):
    """The 12,288 bucket's prefill program (and the median prompt's,
    3,072): five fused attends, four of them banded (a grid whose key axis
    is as long as a band) and one causal, only the last position's logits,
    and a plan under 15 GB with the cache beside it."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.ops import latent_attention as lat_ops
    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = kexaone
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"kexaone prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}; with {slots} slots of cache "
          f"{peak + _bytes(cache_of(slots))}")
    assert peak + _bytes(cache_of(slots)) < 15e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("mla_prefill_attend") == 5
    assert set(names) == {"mla_prefill_attend", "gmm", "moe_combine_held"}
    trips = {3072: 1, 12288: 1}[bucket]   # one block's three, four layers
    assert names.count("gmm") == 12 * trips
    assert names.count("moe_combine_held") == 4
    assert "ragged" not in text
    band = lat_ops.prefill_attend_plan(bucket, 128, 128, jnp.bfloat16, 128)
    describe = model.prefill_attend_plan([bucket])[str(bucket)]
    assert describe["window"]["form"] == describe["full"]["form"] == "kernel"
    assert describe["window"]["tiles_computed"] == band.tiles_computed
    full, win = describe["full"], describe["window"]
    # the band's call has the causal call's blocks and fewer of them
    assert (win["block_q"], win["block_k"]) == (full["block_q"],
                                                full["block_k"])
    assert win["tiles_computed"] < full["tiles_computed"]
    assert win["keys_per_query"] == (win["tiles_computed"] * win["block_q"]
                                     * win["block_k"] / bucket)
    # no score square of the 64 heads, no [bucket, vocabulary] logits
    assert not re.search(rf"\[(64|8,8),{bucket},{bucket}\]", text)
    assert not re.search(rf"f32\[1,{bucket},19200\]", text)


# What the mixer's generalisation (a rotation, a norm a head, a window: all
# off for these two families) must leave as it was: the compiled 1,024
# prefill of granite and Nemotron, HLO opcode by opcode, as PR 46's tree
# compiled it. The two DECODE steps were re-pinned on purpose by PR 48,
# which gave their attention layer the depth-bounded ``gqa_dense_attend``
# K-EXAONE's has and deleted the flag that kept the slot-blind XLA attend
# (counted by this file's ``_op_counts`` on that PR's tree: the einsums,
# masks and softmax over ``[slots, max_len]`` went, one custom call and the
# schedule's few scalar ops came).
PARENT_OP_COUNTS = {     # HLO opcode: count  [prefills PR 46, decodes PR 48]
    "granite_decode": {
        "abs": 9, "add": 1105, "and": 303, "bitcast": 518,
        "bitcast-convert": 60, "broadcast": 1939, "clamp": 75,
        "compare": 1321, "concatenate": 1, "constant": 1694,
        "convert": 333, "convolution": 92, "copy": 215, "copy-done":
        189, "custom-call": 157, "divide": 67, "dynamic-slice": 40,
        "dynamic-update-slice": 40, "exponential": 94, "fusion": 1003,
        "gather": 34, "get-tuple-element": 718, "iota": 131,
        "is-finite": 11, "log-plus-one": 9, "maximum": 23, "minimum":
        11, "multiply": 320, "negate": 334, "or": 24, "pad": 269,
        "parameter": 3627, "reduce": 260, "reduce-window": 112,
        "remainder": 30, "reshape": 257, "rsqrt": 30, "scatter": 60,
        "select": 1188, "shift-right-logical": 102, "sign": 92,
        "slice": 398, "slice-done": 204, "subtract": 123, "transpose":
        188, "xor": 60,
    },
    "granite_prefill1024": {
        "abs": 9, "add": 839, "and": 148, "bitcast": 357,
        "bitcast-convert": 40, "broadcast": 1201, "clamp": 73, "compare": 668,
        "constant": 1329, "convert": 344, "convolution": 62, "copy": 183,
        "copy-done": 198, "custom-call": 149, "divide": 77,
        "dynamic-slice": 74, "dynamic-update-slice": 21, "exponential": 85,
        "fusion": 774, "gather": 51, "get-tuple-element": 674, "iota": 82,
        "log-plus-one": 9, "maximum": 49, "minimum": 10, "multiply": 353,
        "negate": 219, "or": 12, "pad": 251, "parameter": 2564, "reduce": 188,
        "reduce-window": 78, "remainder": 30, "reshape": 221, "rsqrt": 30,
        "scatter": 30, "select": 593, "shift-right-logical": 60, "sign": 60,
        "slice": 316, "slice-done": 148, "subtract": 112, "transpose": 162,
        "xor": 40,
    },
    "nemotron_decode": {
        "abs": 5, "add": 427, "and": 118, "bitcast": 331,
        "bitcast-convert": 25, "broadcast": 1193, "clamp": 55,
        "compare": 653, "concatenate": 6, "constant": 882, "convert":
        184, "convolution": 55, "copy": 123, "copy-done": 149,
        "custom-call": 83, "divide": 28, "dynamic-slice": 10,
        "dynamic-update-slice": 10, "exponential": 33, "fusion": 450,
        "gather": 19, "get-tuple-element": 346, "iota": 68,
        "is-finite": 6, "log-plus-one": 5, "maximum": 19, "minimum":
        6, "multiply": 163, "negate": 102, "or": 14, "pad": 88,
        "parameter": 1770, "reduce": 105, "reduce-window": 42,
        "remainder": 10, "reshape": 124, "rsqrt": 17, "scatter": 15,
        "select": 575, "shift-left": 10, "shift-right-logical": 42,
        "sign": 27, "slice": 282, "slice-done": 168, "subtract": 48,
        "transpose": 68, "xor": 25,
    },
    "nemotron_prefill1024": {
        "abs": 5, "add": 469, "and": 103, "bitcast": 253,
        "bitcast-convert": 20, "broadcast": 711, "clamp": 48, "compare": 384,
        "concatenate": 5, "constant": 769, "convert": 200, "convolution": 39,
        "copy": 94, "copy-done": 147, "custom-call": 72, "divide": 30,
        "dynamic-slice": 40, "dynamic-update-slice": 11, "exponential": 30,
        "fusion": 445, "gather": 31, "get-tuple-element": 340, "iota": 43,
        "log-plus-one": 5, "maximum": 31, "minimum": 5, "multiply": 174,
        "negate": 119, "or": 12, "pad": 140, "parameter": 1388, "reduce": 74,
        "reduce-window": 50, "remainder": 10, "reshape": 138, "rsqrt": 17,
        "scatter": 15, "select": 345, "shift-left": 10,
        "shift-right-logical": 45, "sign": 30, "slice": 204, "slice-done": 65,
        "subtract": 47, "transpose": 92, "xor": 20,
    },
    # K-EXAONE's, as PR 49's tree compiled them (counted there by PR 50)
    "kexaone_decode": {
        "add": 501, "and": 145, "bitcast": 303, "bitcast-convert": 24,
        "broadcast": 896, "clamp": 40, "compare": 517, "concatenate": 5,
        "constant": 958, "convert": 322, "convolution": 112, "copy": 89,
        "copy-done": 108, "cosine": 1, "custom-call": 74, "divide": 49,
        "dynamic-slice": 16, "dynamic-update-slice": 48, "exponential": 45,
        "fusion": 673, "gather": 18, "get-tuple-element": 377, "iota": 55,
        "is-finite": 5, "maximum": 75, "minimum": 6, "multiply": 199,
        "negate": 137, "or": 16, "pad": 190, "parameter": 2147, "power": 1,
        "reduce": 140, "reduce-window": 77, "remainder": 8, "reshape": 116,
        "rsqrt": 21, "scatter": 24, "select": 496, "shift-left": 8,
        "shift-right-logical": 54, "sign": 38, "sine": 1, "slice": 251,
        "slice-done": 72, "subtract": 81, "transpose": 84, "xor": 24,
    },
    "kexaone_prefill1024": {
        "add": 330, "and": 88, "bitcast": 189, "bitcast-convert": 16,
        "broadcast": 582, "clamp": 39, "compare": 314, "concatenate": 4,
        "constant": 646, "convert": 179, "convolution": 39, "copy": 79,
        "copy-done": 109, "cosine": 1, "custom-call": 79, "divide": 17,
        "dynamic-slice": 36, "dynamic-update-slice": 9, "exponential": 13,
        "fusion": 360, "gather": 25, "get-tuple-element": 276, "iota": 40,
        "maximum": 29, "minimum": 4, "multiply": 173, "negate": 98, "or":
        10, "pad": 123, "parameter": 1087, "power": 1, "reduce": 63,
        "reduce-window": 24, "remainder": 4, "reshape": 100, "rsqrt": 21,
        "scatter": 12, "select": 291, "shift-left": 8,
        "shift-right-logical": 40, "sign": 24, "sine": 1, "slice": 80,
        "slice-done": 100, "subtract": 46, "transpose": 74, "xor": 16,
    },
}


def _op_counts(text):
    import collections
    return dict(collections.Counter(re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z][a-z\-]*)\(", text, re.M)))


@pytest.mark.parametrize("family", ["granite", "nemotron", "kexaone"])
def test_the_shared_mixer_lowers_to_what_the_parent_ran(
        family, request, one_chip, cache_off, monkeypatch):
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fixture = request.getfixturevalue(family)
    model, params, cache_of = fixture[:3]
    slots = fixture[3] if len(fixture) > 3 else GRANITE_SLOTS
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip)
    step = engine._compiled_step.__wrapped__(model).lower(
        params, cache_of(slots), vec, host).compile()
    assert _op_counts(step.as_text()) == PARENT_OP_COUNTS[family + "_decode"]
    prompt = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    prefill = engine._compiled_prefill.__wrapped__(model, 1024).lower(
        params, prompt, n).compile()
    assert _op_counts(prefill.as_text()) == PARENT_OP_COUNTS[
        family + "_prefill1024"]


# -- jamba (PR 50): the whole model, 26 Mamba-1 selective-scan layers with a
# -- per-channel state, 2 multi-query layers, a tied head over 65,536 rows ----

JAMBA_CONFIG = "perfbench/configs/jamba2-3b-serve.json"


@pytest.fixture(scope="module")
def jamba(one_chip):
    """AI21-Jamba2-3B as the benchmark's cell runs it (every published
    width, all 28 layers, the whole vocabulary), its parameters and caches
    as described shapes, and the cell's slots."""
    import json

    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import build_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, JAMBA_CONFIG)
    with open(path) as f:
        slots = json.load(f)["serve"]["num_slots"]
    model = build_model("jamba", source=path, compute_dtype=jnp.bfloat16)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))

    def cache_of(rows):
        at = jnp.zeros((rows, 1), jnp.int32)
        return described(jax.eval_shape(
            lambda p: model.apply({"params": p}, at, decode=True,
                                  positions=at,
                                  mutable=["cache"])[1]["cache"], params))

    return model, params, cache_of, slots


def test_jamba_shapes_are_the_published_widths(jamba):
    """Every published width, one key-value head for 20 queries of 128, 28
    layers of which 7 and 21 attend, a tied table of 65,536 rows held
    once, and a cache of three kinds in one tree: 26 states with no
    position axis and channels minor, 26 rings of four rows, 2 rows a
    position, and the states' stamp."""
    import jax

    model, params, cache_of, slots = jamba
    shape = lambda *path: _leaf_at(params, path).shape  # noqa: E731
    assert shape("layer_0", "mixer", "in_proj", "kernel") == (2560, 10240)
    assert shape("layer_0", "mixer", "x_proj", "kernel") == (5120, 192)
    assert shape("layer_0", "mixer", "dt_proj", "kernel") == (160, 5120)
    assert shape("layer_0", "mixer", "A_log") == (16, 5120)
    assert shape("layer_0", "mixer", "conv1d", "kernel") == (4, 5120)
    assert shape("layer_0", "mixer", "out_proj", "kernel") == (5120, 2560)
    assert shape("layer_7", "mixer", "q", "kernel") == (2560, 20, 128)
    assert shape("layer_7", "mixer", "k", "kernel") == (2560, 1, 128)
    assert shape("layer_3", "mlp", "gate", "kernel") == (2560, 8192)
    assert shape("tok_emb") == (65536, 2560)
    assert "lm_head" not in params
    assert [i for i in range(28)
            if "q" in params[f"layer_{i}"]["mixer"]] == [7, 21]
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(int(_bytes(x) // x.dtype.itemsize)
               for x in leaves) == 3_029_337_472
    # A_log, D, dt's bias and the norms' scales are float32
    small = 26 * (16 * 5120 + 2 * 5120 + 192) + 57 * 2560
    assert _bytes(params) == 2 * 3_029_337_472 + 2 * small
    kinds = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache_of(slots)):
        kinds.setdefault(path[-1].key, []).append(leaf.shape)
    assert kinds == {"kv": [(slots, 10240, 256)] * 2,
                     "state": [(slots, 16, 5120)] * 26,
                     "conv": [(slots, 4, 5120)] * 26,
                     "state_pos": [(slots,)]}
    assert _bytes(cache_of(slots)) == slots * (
        26 * 16 * 5120 * 4 + 26 * 4 * 5120 * 2 + 2 * 10240 * 256 * 2 + 4)


def test_jamba_decode_step_moves_states_in_place(
        jamba, one_chip, cache_off, monkeypatch):
    """The decode program as the chip compiles it: one donated cache in
    the plan, 26 state steps and 2 attends under their names, the K and V
    rows written in place, no whole-leaf copy of a state, no ``[5120,
    16]``-minor buffer and no copy of the table for the head."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = jamba
    cache = cache_of(slots)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    host = jax.ShapeDtypeStruct((3, slots), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_step.__wrapped__(model).lower(
        params, cache, vec, host).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _bytes(cache)
    peak = _planned(mem)
    print(f"jamba decode step plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}; cache {_bytes(cache)}")
    assert peak < _bytes(params) + _bytes(cache) + 0.5e9, peak
    assert peak < 15e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("s6_state_step") == 26
    assert names.count("gqa_dense_attend") == 2
    assert names.count("latent_row_write") == 2
    assert set(names) == {"s6_state_step", "gqa_dense_attend",
                          "latent_row_write"}
    assert not re.search(rf"f32\[{slots},16,5120\]\S* (copy|transpose)\(",
                         text)
    assert not re.search(r"\[\d+,5120,16\]", text)
    assert not re.search(r"bf16\[65536,2560\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("bucket", [512, 8192])
def test_jamba_largest_prefill_fits_beside_weights_and_cache(
        jamba, one_chip, cache_off, monkeypatch, bucket):
    """The 8,192 bucket's prefill program (and the median prompt's, 512):
    the scan under its name in every state-space layer, each attention
    layer one flash forward, only the last position's logits, no ``[L,
    5120, 16]`` buffer, and a plan under 15 GB with the cache beside
    it."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, cache_of, slots = jamba
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._compiled_prefill.__wrapped__(model, bucket).lower(
        params, prompt, n).compile()
    mem = compiled.memory_analysis()
    peak = _planned(mem)
    print(f"jamba prefill {bucket} plan: {peak} bytes, temporaries "
          f"{mem.temp_size_in_bytes}; with {slots} slots of cache "
          f"{peak + _bytes(cache_of(slots))}")
    assert peak + _bytes(cache_of(slots)) < 15e9, peak
    text = compiled.as_text()
    names = _pallas_calls(text)
    assert names.count("s6_chunk_scan") == 26
    assert names.count("mla_prefill_attend") == 2
    assert set(names) == {"s6_chunk_scan", "mla_prefill_attend"}
    assert not re.search(rf"\[(1,)?{bucket},(5120,16|16,5120)\]", text)
    # no score square of the 20 heads ([8192, 8192] alone is the
    # feed-forward's hidden at this bucket), no [bucket, vocabulary] logits
    assert not re.search(rf"\[20,{bucket},{bucket}\]", text)
    assert not re.search(rf"f32\[1,{bucket},65536\]", text)
