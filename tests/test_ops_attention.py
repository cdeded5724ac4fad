"""The fused attention kernel (ops/attention.py) on the CPU, in Pallas'
interpret mode, held to the scan it replaces on a TPU
(models/transformer.py::blocked_attention): the same contract, the same
masks (causal, windowed, and the three-part mask of training by diffusion
over blocks, each within documents), forward and in all three gradients,
and both against the dense three-part mask; the tiles its mask tables
visit against a count from the dense mask; the choice between the two
paths; and what ``train()`` says of it at start-up."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.sdar_moe import mask as dense_mask
from tpu_resnet.config import load_config
from tpu_resnet.data.tokens import write_tokens
from tpu_resnet.models import afmoe, transformer
from tpu_resnet.ops import attention

S, D, KV, G, B = 512, 128, 2, 2, 2
BLOCK = 128
# a window that is no multiple of the block, and the full layer
WINDOWS = pytest.mark.parametrize("window", [200, 0],
                                  ids=["sliding", "full"])


def _inputs():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (B, S, KV, G, D), jnp.float32)
    k, v = (jax.random.normal(key, (B, S, KV, D), jnp.float32)
            for key in keys[1:3])
    weight = jax.random.normal(keys[3], (B, S, KV, G, D), jnp.float32)
    # documents that begin inside blocks (5, 300, 257, 500), at a block's
    # first position (128, 384, 256) and at its last (383)
    starts = np.zeros((B, S), np.int32)
    starts[0, [0, 5, 128, 300, 383, 384]] = 1
    starts[1, [0, 256, 257, 500]] = 1
    return q, k, v, jnp.asarray(np.cumsum(starts, axis=1)), weight


def _both(window, dtype):
    """``(output, (dq, dk, dv))`` of the scan and of the kernel, the
    gradients those of one weighted sum of the output."""
    q, k, v, doc, weight = _inputs()

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return np.asarray(out, np.float32), [np.asarray(g) for g in grads]

    return (run(lambda q, k, v: afmoe.blocked_attention(
                q, k, v, doc, window, BLOCK, dtype)),
            run(lambda q, k, v: attention.fused_attention(
                q, k, v, doc, window, dtype, interpret=True,
                blocks=attention.block_sizes(BLOCK, BLOCK, BLOCK))))


@WINDOWS
def test_kernel_equals_the_scan_in_float32(window):
    (out_s, grads_s), (out_k, grads_k) = _both(window, jnp.float32)
    assert out_k.shape == (B, S, KV, G, D)
    np.testing.assert_allclose(out_k, out_s, atol=2e-5)
    for got, want in zip(grads_k, grads_s):
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


@WINDOWS
def test_kernel_equals_the_scan_in_bf16_within_its_rounding(window):
    """bf16 keeps 8 bits: the outputs (sums of values near 1 under weights
    that add up to 1) may part by a few units of 2**-8, the gradients by a
    percent of their largest entry. The scan rounds ``q`` and scales the
    float32 scores; the kernel scales ``q`` and rounds it once."""
    (out_s, grads_s), (out_k, grads_k) = _both(window, jnp.bfloat16)
    np.testing.assert_allclose(out_k, out_s, atol=4 * 2.0 ** -8)
    for got, want in zip(grads_k, grads_s):
        np.testing.assert_allclose(got, want, atol=0.02 * np.abs(want).max())


@pytest.mark.parametrize("window", [2048, 1000, 0],
                         ids=["sliding", "odd_window", "full"])
def test_key_blocks_visited_is_the_dense_masks_count(window):
    seq = 4096
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    dense = (j <= i) & ((i - j < window) if window else True)
    bq, bkv = attention.BLOCKS.block_q, attention.BLOCKS.block_kv
    tiles = dense.reshape(seq // bq, bq, seq // bkv, bkv).any(axis=(1, 3))
    visited, total = attention.key_blocks(seq, window, 8)
    assert (visited, total) == (int(tiles.sum()), tiles.size)
    assert visited < total


def test_the_scans_blocks_are_its_uniform_span():
    # 16 blocks of 256 queries, each 2,304 keys on a sliding layer and
    # all 4,096 on the full one (PERF.md section 5)
    arch = afmoe.Arch(layers=("dense_sliding", "moe_full"))
    assert [(row["path"], row["key_blocks_visited"], row["key_blocks_total"])
            for row in afmoe.attention_paths(arch, 4096, "cpu", 8)] == [
        ("scan", 16 * 9, 256), ("scan", 256, 256)]
    tiny = afmoe.Arch(layers=("dense_sliding",), window=8, attn_block=8)
    assert afmoe.attention_paths(tiny, 32, "tpu", 1)[0][
        "key_blocks_visited"] == 4 * 2


@pytest.mark.parametrize("backend, devices, head_dim, seq_len, path", [
    ("tpu", 1, 128, 4096, "kernel"),      # the cell
    ("tpu", 1, 128, 8192, "kernel"),
    ("cpu", 1, 128, 4096, "scan"),        # the float32 comparison
    ("cpu", 8, 128, 4096, "scan"),        # every test here
    ("gpu", 1, 128, 4096, "scan"),
    ("tpu", 4, 128, 4096, "scan"),        # one jit over four chips
    ("tpu", 1, 16, 32, "scan"),           # the tiny preset
    ("tpu", 1, 64, 4096, "scan"),         # heads under the 128 lanes
    ("tpu", 1, 128, 4000, "scan"),        # a length the blocks do not divide
    ("tpu", 1, 128, 256, "scan"),
])
def test_path_is_a_function_of_backend_devices_and_shapes(
        backend, devices, head_dim, seq_len, path):
    assert attention.attention_path(backend, devices, head_dim,
                                    seq_len) == path


def test_attention_paths_names_every_layer():
    arch = afmoe.Arch(layers=("dense_sliding", "moe_sliding", "moe_full"))
    on_chip = afmoe.attention_paths(arch, 4096, "tpu", 1)
    assert [(row["layer"], row["kind"], row["path"]) for row in on_chip] == [
        (0, "dense_sliding", "kernel"), (1, "moe_sliding", "kernel"),
        (2, "moe_full", "kernel")]
    assert on_chip[0]["key_blocks_visited"] < on_chip[2][
        "key_blocks_visited"] < on_chip[2]["key_blocks_total"]
    here = afmoe.attention_paths(arch, 4096, "cpu", 8)
    assert {row["path"] for row in here} == {"scan"}
    assert here == [dict(row, path="scan") for row in here] == \
        afmoe.attention_paths(arch, 4096, "tpu", 4)   # one jit, four chips
    assert here[2]["key_blocks_visited"] == here[2]["key_blocks_total"]


def test_a_tiny_run_says_its_attention_path_once(tmp_path):
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 128, 16 * 32 + 1)
    ids[::13] = 0
    write_tokens(str(tmp_path / "data"), ids)
    train(load_config("trinity_mini_ep16", overrides=[
        'afmoe.layers=["dense_sliding","dense_full"]', "afmoe.hidden=32",
        "afmoe.heads=4", "afmoe.kv_heads=2", "afmoe.head_dim=16",
        "afmoe.window=8", "afmoe.dense_width=48", "data.seq_len=32",
        "data.vocab_size=128", "model.compute_dtype=float32",
        "train.global_batch_size=8", "mesh.data=1",
        f"data.data_dir={tmp_path}/data", f"train.train_dir={tmp_path}/run",
        "train.train_steps=2", "train.steps_per_call=1",
        "train.mfu_accounting=false",
        "train.memory_ledger=false", "train.comms_ledger=false"]))
    with open(tmp_path / "run" / "events.jsonl") as f:
        events = [r for r in map(json.loads, f)
                  if r.get("span") == "attention_path"]
    assert len(events) == 1
    assert events[0]["layers"] == [
        {"layer": 0, "kind": "dense_sliding", "path": "scan",
         "key_blocks_visited": 1, "key_blocks_total": 1},
        {"layer": 1, "kind": "dense_full", "path": "scan",
         "key_blocks_visited": 1, "key_blocks_total": 1}]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_model_through_the_kernel_equals_the_model_through_the_scan(
        monkeypatch, remat):
    """Steered in the test, as a chip would choose: the whole model's loss
    and gradients (the scale folded into ``q``, the layouts, the name
    ``model.remat`` keeps) with each layer's attention through the kernel
    at its own blocks, against the scan the CPU takes."""
    arch = afmoe.Arch(layers=("dense_sliding", "dense_full"), hidden=64,
                      heads=2, kv_heads=1, head_dim=128, window=300,
                      dense_width=96, vocab_rows=64, remat=remat,
                      dtype=jnp.float32)
    seq = attention.BLOCKS.block_q
    rng = np.random.default_rng(1)
    ids = rng.integers(1, 64, (1, seq))
    ids[0, [0, 77, 512, 900]] = 0
    ids = jnp.asarray(ids, jnp.int32)
    model = afmoe.Afmoe(arch)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(params):
        logits = model.apply({"params": params}, ids)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 3])

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss)(params)
        monkeypatch.setattr(transformer, "attention_path",
                            lambda *_: "kernel")
        got = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler without a TPU
    (on-chip-measurement guide, section 2). Only here, never at import."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says there is none
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("window", [2048, 0], ids=["sliding", "full"])
def test_the_cells_kernels_compile_for_a_v5e(one_chip, window):
    """Mosaic takes the forward and the backward kernel at the cell's
    shapes and the blocks fixed in the module (what interpret mode cannot
    show: tiling, VMEM). A compile, not a run."""
    b, s, kv, g, d = 2, 4096, 4, 8, 128

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, doc):
        return jnp.sum(attention.fused_attention(
            q, k, v, doc, window, jnp.bfloat16, interpret=False
        ).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shaped(b, s, kv, g, d), shaped(b, s, kv, d), shaped(b, s, kv, d),
        shaped(b, s, dtype=jnp.int32)).compile().as_text()
    assert "splash_mqa_fwd_segmented_residuals" in text
    assert "splash_mqa_dkv_segmented_no_residuals" in text


# ------------------------------------- the three-part mask (block diffusion)
L_CLEAN, BLK = 256, 4          # 512 positions: a noised copy, a clean one
DIFFUSION = attention.BlockDiffusion(L_CLEAN, BLK)


def _dense_attention(q, k, v, doc, ok):
    """Every score of the ``S x S`` square under the dense mask ``ok``
    and the documents, in float32."""
    see = ok[None] & (doc[:, :, None] == doc[:, None, :])
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / np.sqrt(q.shape[-1])
    pr = jax.nn.softmax(jnp.where(see[:, None, None], sc, -jnp.inf), -1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", pr, v)


def test_allows_is_the_definition_and_leaves_its_count_live():
    q, k = np.arange(2 * L_CLEAN)[:, None], np.arange(2 * L_CLEAN)[None, :]
    want = dense_mask(L_CLEAN, BLK)
    np.testing.assert_array_equal(DIFFUSION.allows(q, k), want)
    np.testing.assert_array_equal(
        np.asarray(DIFFUSION.allows(jnp.asarray(q), jnp.asarray(k))), want)
    assert want.sum() == L_CLEAN * L_CLEAN + L_CLEAN * BLK
    # nothing sees a noisy key of another block; the clean copy none
    assert not want[L_CLEAN:, :L_CLEAN].any()
    assert want[:L_CLEAN, :L_CLEAN].sum() == L_CLEAN * BLK


@pytest.mark.parametrize("path", ["kernel", "scan"])
def test_both_paths_equal_the_dense_three_part_mask_with_documents(path):
    q, k, v, _, weight = _inputs()
    # both copies carry the clean text's documents, which begin inside
    # blocks (5, 130), at a block's first position (128) and last (63)
    starts = np.zeros((B, L_CLEAN), np.int32)
    starts[0, [0, 5, 63, 128, 130]] = 1
    starts[1, [0, 200]] = 1
    doc = jnp.asarray(np.tile(np.cumsum(starts, axis=1), (1, 2)))
    ok = jnp.asarray(dense_mask(L_CLEAN, BLK))

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return np.asarray(out, np.float32), [np.asarray(g) for g in grads]

    with jax.default_matmul_precision("highest"):
        want_out, want_grads = run(
            lambda q, k, v: _dense_attention(q, k, v, doc, ok))
        if path == "kernel":
            out, grads = run(lambda q, k, v: attention.fused_attention(
                q, k, v, doc, DIFFUSION, jnp.float32, interpret=True,
                blocks=attention.block_sizes(BLOCK, BLOCK, BLOCK)))
        else:
            out, grads = run(lambda q, k, v: transformer.blocked_attention(
                q, k, v, doc, DIFFUSION, BLOCK, jnp.float32))
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_key_blocks_of_the_three_part_mask_at_the_cells_shapes():
    """At 4,096 clean ids and tiles of 1,024 the kernel visits 24 of 64
    tiles: 10 block causal, 10 offset block causal, 4 on the noisy
    diagonal (ISSUE 34), which is the dense mask's count."""
    length = 4096
    mask = attention.BlockDiffusion(length, 4)
    assert attention.key_blocks(2 * length, mask, 8) == (24, 64)
    bq = attention.BLOCKS.block_q
    tiles = dense_mask(length, 4).reshape(
        2 * length // bq, bq, 2 * length // bq, bq).any(axis=(1, 3))
    assert int(tiles.sum()) == 24
    with pytest.raises(ValueError, match="positions, not 4096"):
        attention.key_blocks(length, mask, 8)
