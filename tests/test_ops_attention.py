"""The fused attention kernel (ops/attention.py) on the CPU, in Pallas'
interpret mode, held to the scan it replaces on a TPU
(models/transformer.py::blocked_attention): the same contract, the same
masks (causal, windowed, and the three-part mask of training by diffusion
over blocks, each within documents), forward and in all three gradients,
and both against the dense three-part mask (under it the kernel path is
two parts: the kernel over the clean keys, the noisy diagonal beside it);
the tiles its mask tables visit against a count from the dense mask; the
choice between the two paths; that a causal mask and a window still trace
the program they traced; and what ``train()`` says of it at start-up."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu import splash_attention as splash

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


def _run(fn, q, k, v, weight):
    """``(output, [dq, dk, dv])`` of ``fn(q, k, v)``, the gradients those
    of one weighted sum of the output."""
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return np.asarray(out, np.float32), [np.asarray(g) for g in grads]


def _both(window, dtype):
    """``(output, (dq, dk, dv))`` of the scan and of the kernel."""
    q, k, v, doc, weight = _inputs()
    return (_run(lambda q, k, v: afmoe.blocked_attention(
                q, k, v, doc, window, BLOCK, dtype), q, k, v, weight),
            _run(lambda q, k, v: attention.fused_attention(
                q, k, v, doc, window, dtype, interpret=True,
                blocks=attention.block_sizes(BLOCK, BLOCK, BLOCK)),
                q, k, v, weight))


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
    ("tpu", 1, 64, 4096, "kernel"),       # heads of half the lanes
    ("tpu", 4, 64, 4096, "scan"),
    ("tpu", 1, 32, 4096, "scan"),         # a quarter: never run on the chip
    ("tpu", 1, 192, 4096, "scan"),
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
         "inputs": "composed", "key_blocks_visited": 1,
         "key_blocks_total": 1},
        {"layer": 1, "kind": "dense_full", "path": "scan",
         "inputs": "composed", "key_blocks_visited": 1,
         "key_blocks_total": 1}]


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


@pytest.mark.parametrize("batch, mask, heads, seq", [
    (2, 2048, (4, 8, 128), 4096), (2, 0, (4, 8, 128), 4096),
    (1, attention.BlockDiffusion(4096, 4), (4, 8, 128), 8192),
    (2, 0, (8, 4, 64), 4096), (1, 4096, (4, 7, 128), 16384)],
    ids=["sliding", "full", "block_diffusion", "full_heads_of_64",
         "window_16k_groups_of_7"])
def test_the_cells_kernels_compile_for_a_v5e(one_chip, batch, mask, heads,
                                             seq):
    """Mosaic takes the forward and the backward kernel at the cells'
    shapes and the blocks fixed in the module (what interpret mode cannot
    show: tiling, VMEM); under the three-part mask the kernel over the
    clean keys with its bound a row, beside the diagonal; at heads of 64
    the kernel as it is, nothing padded; at 16,384 positions a window of
    4,096 over groups of 7 query heads. A compile, not a run."""
    kv, g, d = heads
    s = seq

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, doc):
        return jnp.sum(attention.fused_attention(
            q, k, v, doc, mask, jnp.bfloat16, interpret=False
        ).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shaped(batch, s, kv, g, d), shaped(batch, s, kv, d),
        shaped(batch, s, kv, d), shaped(batch, s, dtype=jnp.int32)
    ).compile().as_text()
    assert "splash_mqa_fwd_segmented_residuals" in text
    assert "splash_mqa_dkv_segmented_no_residuals" in text


@pytest.mark.parametrize("batch, seq, heads, rotary, normed", [
    (1, 8192, (32, 4), "by_ids", True),
    (2, 4096, (32, 4), "from_start", True), (2, 4096, (32, 4), None, True),
    (1, 16384, (28, 4), "from_start", False),
    (1, 16384, (28, 4), None, False)],
    ids=["block_diffusion", "sliding", "full", "window_16k_no_norm",
         "global_16k_no_norm"])
def test_the_attention_inputs_kernels_compile_for_a_v5e(
        one_chip, monkeypatch, batch, seq, heads, rotary, normed):
    """Mosaic takes the pass that prepares attention's inputs
    (``ops/attention_inputs.py``), forward and backward, at the cells'
    shapes: the projections as the products write them, rotary by position
    ids, from the sequence's start, or none; with the q/k norms or, at
    groups of 7 and 16,384 positions, without. A compile, not a run; the
    chip's backend is named in the test, as a chip would name it."""
    from tpu_resnet.ops.attention_inputs import attention_inputs

    h, kv = heads
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, q_scale, k_scale, positions):
        rot = {"by_ids": (1e6, positions), "from_start": (1e4, None),
               None: None}[rotary]
        outs = attention_inputs(
            q.reshape(batch, seq, h, 128), k.reshape(batch, seq, kv, 128),
            v.reshape(batch, seq, kv, 128), q_scale if normed else None,
            k_scale if normed else None, rot, jnp.bfloat16, 1e-6)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shaped(batch, seq, h * 128), shaped(batch, seq, kv * 128),
        shaped(batch, seq, kv * 128), shaped(128, dtype=jnp.float32),
        shaped(128, dtype=jnp.float32), shaped(1, seq, dtype=jnp.int32)
    ).compile().as_text()
    assert "attention_inputs_fwd" in text and "attention_inputs_bwd" in text


def _the_parents_path(q, k, v, doc, window, dtype, block):
    """``fused_attention`` under an ``int`` mask as it stood before the
    three-part mask had a path of its own (PR 31), written out."""
    b, s, kv, g, d = q.shape
    shape = (s, s)
    one = (splash.LocalMask(shape, (window - 1, 0), 0) if window
           else splash.CausalMask(shape))
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * g),
            block_sizes=attention.block_sizes(block, block, block),
            interpret=True)
    q = (q * (1.0 / math.sqrt(d))).astype(dtype)
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))
    out = jax.vmap(per_head)(
        jnp.transpose(q, (0, 2, 3, 1, 4)),
        jnp.transpose(k.astype(dtype), (0, 2, 1, 3)),
        jnp.transpose(v.astype(dtype), (0, 2, 1, 3)),
        splash.SegmentIds(doc, doc))
    return jnp.transpose(out, (0, 3, 1, 2, 4))


@WINDOWS
def test_an_int_mask_traces_the_program_it_traced(window):
    """The causal mask and the window keep the one kernel call through
    JAX's own ``custom_vjp``: forward and gradients, the jaxpr is the
    parent's to the letter."""
    q, k, v, doc, weight = _inputs()

    def traced(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return str(jax.make_jaxpr(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v))

    got = traced(lambda q, k, v: attention.fused_attention(
        q, k, v, doc, window, jnp.bfloat16, interpret=True,
        blocks=attention.block_sizes(BLOCK, BLOCK, BLOCK)))
    want = traced(lambda q, k, v: _the_parents_path(
        q, k, v, doc, window, jnp.bfloat16, BLOCK))
    assert "splash_mqa_fwd" in want and "splash_mqa_dkv" in want
    assert got == want


# ------------------------------------------------------------ heads of 64
@pytest.mark.parametrize("path", ["kernel", "scan"])
def test_heads_of_64_equal_dense_attention_with_documents(path):
    """At a head of 64 (``lfm2_moe``'s) the kernel as it is, in interpret
    mode, and the scan both give dense attention's numbers within
    documents, forward and in the three gradients."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    kv, g, d = 4, 2, 64
    q = jax.random.normal(keys[0], (B, S, kv, g, d), jnp.float32)
    k, v = (jax.random.normal(key, (B, S, kv, d), jnp.float32)
            for key in keys[1:3])
    weight = jax.random.normal(keys[3], (B, S, kv, g, d), jnp.float32)
    doc = _inputs()[3]
    pos = np.arange(S)
    causal = jnp.asarray(pos[None, :] <= pos[:, None])
    with jax.default_matmul_precision("highest"):
        want_out, want_grads = _run(
            lambda q, k, v: _dense_attention(q, k, v, doc, causal),
            q, k, v, weight)
        if path == "kernel":
            out, grads = _run(lambda q, k, v: attention.fused_attention(
                q, k, v, doc, 0, jnp.float32, interpret=True,
                blocks=attention.block_sizes(BLOCK, BLOCK, BLOCK)),
                q, k, v, weight)
        else:
            out, grads = _run(lambda q, k, v: transformer.blocked_attention(
                q, k, v, doc, 0, BLOCK, jnp.float32), q, k, v, weight)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


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
        return _run(fn, q, k, v, weight)

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


def _documents(length, starts):
    """Document ids of the clean text with a document begun at each of
    ``starts`` (a row a batch entry), for both copies."""
    begun = np.zeros((len(starts), length), np.int32)
    for row, at in enumerate(starts):
        begun[row, [0, *at]] = 1
    return np.tile(np.cumsum(begun, axis=1), (1, 2))


# (clean ids, block of the diffusion, tile, where documents begin): blocks
# of 4, of 8 and of 3 (no power of two); documents that begin on a block's
# first position (a noisy row there sees no clean key at all), inside
# blocks and on a block's last; one document over the whole sequence; and
# tiles of which some are empty, some partial and some whole (at 256 clean
# ids and tiles of 128: the noisy rows 0-127 see part of the clean keys
# 0-127 and none of 128-255, the rows 128-255 all of the first and part of
# the second).
TWO_PARTS = pytest.mark.parametrize("length, block, tile, starts", [
    (256, 4, 128, [[5, 63, 128, 130], [200]]),
    (256, 4, 128, [[], []]),
    (256, 8, 128, [[8, 100, 128, 135], [64]]),
    (384, 3, 128, [[3, 100, 129, 255, 256], [300]]),
    (512, 4, 256, [[4, 255, 256, 300], []]),
    (512, 8, 128, [[128, 256, 384], [7, 8, 9]]),
], ids=["blocks_of_4", "one_document", "blocks_of_8", "blocks_of_3",
        "tiles_of_256", "documents_on_tile_edges"])


@TWO_PARTS
def test_two_parts_equal_the_scan_output_and_gradients(length, block, tile,
                                                       starts):
    """The kernel over the clean keys joined with the noisy diagonal,
    under its own backward pass, against ``blocked_attention`` with
    ``BlockDiffusion.allows``: output and all three gradients, every
    number finite (a noisy row in a document's first block sees no clean
    key: the join's weight removes what the kernel says of it)."""
    mask = attention.BlockDiffusion(length, block)
    keys = jax.random.split(jax.random.PRNGKey(length + block), 4)
    shape = (len(starts), 2 * length, KV, G, D)
    q, weight = (jax.random.normal(key, shape, jnp.float32)
                 for key in keys[:2])
    k, v = (jax.random.normal(key, shape[:3] + (D,), jnp.float32)
            for key in keys[2:])
    doc = jnp.asarray(_documents(length, starts))

    def run(fn):
        return _run(fn, q, k, v, weight)

    with jax.default_matmul_precision("highest"):
        want_out, want_grads = run(
            lambda q, k, v: transformer.blocked_attention(
                q, k, v, doc, mask, tile, jnp.float32))
        out, grads = run(lambda q, k, v: attention.fused_attention(
            q, k, v, doc, mask, jnp.float32, interpret=True,
            blocks=attention.block_sizes(tile, tile, tile)))
    assert all(np.isfinite(a).all() for a in (out, *grads))
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


@TWO_PARTS
def test_the_bound_mask_is_allows_on_the_clean_columns(length, block, tile,
                                                       starts):
    """The kernel's mask, one comparison with a bound a row, in its dense
    form: ``allows`` on the clean keys; the rest of ``allows`` is the noisy
    diagonal, ``block`` entries a noisy row; and the kernel's own table
    visits the tiles the dense form leaves non-empty."""
    mask = attention.BlockDiffusion(length, block)
    rows = np.arange(2 * length)[:, None]
    want = mask.allows(rows, np.arange(2 * length)[None, :])
    bound = attention._CleanKeysMask(mask)
    assert bound.shape == (2 * length, length)
    dense = bound[:, :]
    np.testing.assert_array_equal(dense, want[:, length:])
    np.testing.assert_array_equal(
        dense, np.arange(length)[None, :] < attention.clean_bounds(mask)[
            :, None])
    assert not want[length:, :length].any()
    assert (want[:length, :length].sum(axis=1) == block).all()
    assert attention.diagonal_rows(mask) == length
    table = np.asarray(attention._kernel(
        2 * length, mask, G, attention.block_sizes(tile, tile, tile), True
    ).fwd_mask_info.block_mask)[0]
    tiles = dense.reshape(2 * length // tile, tile, length // tile, tile)
    assert np.count_nonzero(table) == int(tiles.any(axis=(1, 3)).sum())
    assert 0 < np.count_nonzero(table) < tiles.shape[0] * tiles.shape[2]
    assert tiles.all(axis=(1, 3)).any()       # empty, partial and whole


def test_key_blocks_of_the_three_part_mask_at_the_cells_shapes():
    """At 4,096 clean ids and tiles of 1,024 the kernel steps through 20
    of the 32 tiles of every query by the clean keys (10 for the noisy
    rows, 10 for the clean), which is the dense mask's count there; the 4
    tiles of the noisy diagonal that the whole square's 24 of 64 held
    (PR 34) are ``diagonal_rows`` outside the kernel."""
    length = 4096
    mask = attention.BlockDiffusion(length, 4)
    assert attention.key_blocks(2 * length, mask, 8) == (20, 32)
    assert attention.diagonal_rows(mask) == length
    assert attention.diagonal_rows(2048) == attention.diagonal_rows(0) == 0
    bq = attention.BLOCKS.block_q
    tiles = dense_mask(length, 4).reshape(
        2 * length // bq, bq, 2 * length // bq, bq).any(axis=(1, 3))
    assert int(tiles.sum()) == 24
    assert int(tiles[:, length // bq:].sum()) == 20
    assert int(np.trace(tiles[:length // bq, :length // bq])) == 4
    with pytest.raises(ValueError, match="positions, not 4096"):
        attention.key_blocks(length, mask, 8)
