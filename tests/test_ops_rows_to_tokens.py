"""The sum of an expert buffer's rows into their tokens and its transpose
(ops/rows_to_tokens.py) on the CPU: the kernel in Pallas' interpret mode
and the scatter path held to a count by hand at routings that hold every
assignment, none, all on one expert, and tokens on several experts, with
NaN in every row no assignment holds; the tiers beyond the buffer; both
gradients against the parent's ``.at[token].add`` and ``take``; the path;
the families' startup events; and the three token families' tiny
programs through the kernel against the parent's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpu_resnet.config import load_config
from tpu_resnet.models import build_model, family, transformer
from tpu_resnet.ops import rows_to_tokens as rt

N, K, TOTAL, D = 48, 4, 16, 32
# whatever the kernel leaves unwritten reads NaN, as stale HBM may
INTERPRET = pltpu.InterpretParams(uninitialized_memory="nan")
# (first, count) held, and the experts each token may choose from
ROUTINGS = {"some_held": ((4, 6), None),
            "all_held": ((0, TOTAL), None),      # a token on K experts here
            "none_held": ((12, 4), range(12)),
            "one_expert": ((4, 3), "expert_4")}


def _chosen(routing, seed=0):
    (first, count), allowed = ROUTINGS[routing]
    rng = np.random.default_rng(seed)
    if allowed == "expert_4":      # every token's one held choice is 4
        others = [e for e in range(TOTAL) if not first <= e < first + count]
        return np.stack([np.concatenate([[4], rng.choice(others, K - 1,
                                                         replace=False)])
                         for _ in range(N)])
    pool = list(allowed or range(TOTAL))
    return np.stack([rng.choice(pool, K, replace=False) for _ in range(N)])


def _buffer(chosen, held, rows, lo=0):
    """What ``dispatch_experts`` sorts, as numpy: each assignment's held
    expert, the stable sort by it, and this tier's rows."""
    first, count = held
    local = chosen.reshape(-1) - first
    expert = np.where((local >= 0) & (local < count), local, count)
    order = np.argsort(expert, kind="stable")
    load = np.bincount(expert, minlength=count + 1)[:count]
    ends = np.cumsum(load)
    here = int(ends[-1]) if count else 0
    tiers = -(-N * K // rows)
    at = np.pad(order, (0, tiers * rows - N * K))[lo:lo + rows]
    return dict(token=jnp.asarray(at // K, jnp.int32),
                expert=jnp.asarray(expert, jnp.int32),
                ends=jnp.asarray(ends, jnp.int32),
                load=jnp.asarray(load, jnp.int32), lo=lo,
                here=jnp.int32(here)), at, here


def _plan(buf, path, **kw):
    return rt.plan(buf["token"], N, path, expert=buf["expert"],
                   ends=buf["ends"], load=buf["load"], lo=buf["lo"],
                   here=buf["here"], interpret=INTERPRET, **kw)


def _rows(rows, valid, fill, seed=1, dtype=np.float32):
    y = np.random.default_rng(seed).normal(size=(rows, D)).astype(dtype)
    y[~valid] = fill
    return y


def _by_hand(token, y, valid):
    out = np.zeros((N, D))
    np.add.at(out, np.asarray(token)[valid], np.asarray(y, np.float64)[valid])
    return out


@pytest.mark.parametrize("path, tile", [("kernel", 8), ("kernel", 16),
                                        ("kernel", 48), ("scatter", None)])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_both_paths_sum_the_held_rows_into_their_tokens(routing, path,
                                                        tile):
    """``combine`` forward and ``dispatch``'s gradient against a count by
    hand; on the kernel's path the rows past the held ones are NaN and
    none reaches a token (on the scatter path they are 0, as the grouped
    products leave them)."""
    held = ROUTINGS[routing][0]
    rows = transformer.buffer_rows(N, K, held[1], TOTAL, 2.0, 16)
    buf, at, here = _buffer(_chosen(routing), held, rows)
    valid = np.arange(rows) < min(here, rows)
    fill = np.nan if path == "kernel" else 0.0
    plan = _plan(buf, path, tile=tile)
    y = _rows(rows, valid, fill)
    got = np.asarray(rt.combine(jnp.asarray(y), plan))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _by_hand(buf["token"], y, valid),
                               atol=1e-5)
    g = _rows(rows, valid, fill, seed=2)
    d_x = jax.grad(lambda x: jnp.sum(rt.dispatch(x, plan) * g))(
        jnp.zeros((N, D)))
    np.testing.assert_allclose(d_x, _by_hand(buf["token"], g, valid),
                               atol=1e-5)


def test_the_kernel_reads_no_row_past_the_held_ones():
    """Every row past the held ones NaN, and the held ones' neighbours in
    their blocks too: each token's sum is finite and the hand count's,
    and a token no held expert took reads 0."""
    held = (4, 6)
    rows = transformer.buffer_rows(N, K, 6, TOTAL, 2.0, 16)
    buf, _, here = _buffer(_chosen("some_held", seed=3), held, rows)
    assert 16 < here < rows - 16
    valid = np.arange(rows) < here
    y = _rows(rows, valid, np.inf)
    y[~valid] = np.nan
    got = np.asarray(rt.combine(jnp.asarray(y),
                                _plan(buf, "kernel", tile=16)))
    want = _by_hand(buf["token"], y, valid)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5)
    untouched = np.setdiff1d(np.arange(N), np.asarray(buf["token"])[valid])
    assert untouched.size and not got[untouched].any()


@pytest.mark.parametrize("path", ["kernel", "scatter"])
def test_the_tiers_beyond_the_buffer_take_their_own_rows(path):
    """A buffer of 32 rows for 96 held assignments: each tier at ``lo``
    sums the sorted positions ``lo .. lo + 32`` it holds, the last one in
    part, and the tiers together give every held row once."""
    held, rows = (0, TOTAL), 32
    total = np.zeros((N, D))
    chosen = _chosen("all_held", seed=4)
    for lo in range(0, N * K, rows):
        buf, at, here = _buffer(chosen, held, rows, lo)
        valid = lo + np.arange(rows) < here
        y = _rows(rows, valid, np.nan if path == "kernel" else 0.0, seed=lo)
        got = np.asarray(rt.combine(jnp.asarray(y),
                                    _plan(buf, path, tile=16)))
        want = _by_hand(buf["token"], y, valid)
        np.testing.assert_allclose(got, want, atol=1e-5)
        total += got
    assert here == N * K and np.abs(total).sum() > 0


@pytest.mark.parametrize("routing", ["some_held", "one_expert"])
def test_the_gradients_are_the_parents(routing):
    """``jax.grad`` through ``combine`` (with respect to the rows) and
    through ``dispatch`` (with respect to the tokens), on the kernel's
    path against the parent's ``.at[token].add`` and ``take``, with the
    rows past the held ones 0 as the grouped products leave them."""
    held = ROUTINGS[routing][0]
    rows = transformer.buffer_rows(N, K, held[1], TOTAL, 2.0, 16)
    buf, _, here = _buffer(_chosen(routing, seed=5), held, rows)
    valid = np.arange(rows) < here
    y = jnp.asarray(_rows(rows, valid, 0.0))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(N, D)),
                    jnp.float32)
    w_tok = jnp.asarray(np.random.default_rng(7).normal(size=(N, D)),
                        jnp.float32)
    w_row = jnp.asarray(_rows(rows, valid, 0.0, seed=8))
    token = buf["token"]

    def parent(y, x):
        out = jnp.zeros((N, D), jnp.float32).at[token].add(y)
        return (jnp.sum(out * w_tok)
                + jnp.sum(jnp.take(x, token, axis=0) * w_row))

    def change(y, x):
        plan = _plan(buf, "kernel", tile=16)
        return (jnp.sum(rt.combine(y, plan) * w_tok)
                + jnp.sum(rt.dispatch(x, plan) * w_row))

    want = jax.grad(parent, argnums=(0, 1))(y, x)
    got = jax.jit(jax.grad(change, argnums=(0, 1)))(y, x)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_a_bf16_gradient_is_summed_in_float32_and_rounded_once():
    """``dispatch``'s gradient in bf16: the rows of a token summed in
    float32 and rounded to bf16 once, where a scatter-add in bf16 rounds
    after every row."""
    held = (0, TOTAL)
    rows = transformer.buffer_rows(N, K, TOTAL, TOTAL, 1.0, 16)
    buf, _, here = _buffer(_chosen("all_held", seed=9), held, rows)
    valid = np.arange(rows) < here
    g = _rows(rows, valid, 0.0, seed=10)
    g_bf16 = jnp.asarray(g, jnp.bfloat16)
    plan = _plan(buf, "kernel", tile=16)
    d_x = jax.grad(lambda x: jnp.sum(
        rt.dispatch(x, plan).astype(jnp.float32)
        * g_bf16.astype(jnp.float32)))(jnp.zeros((N, D), jnp.bfloat16))
    assert d_x.dtype == jnp.bfloat16
    exact = _by_hand(buf["token"], np.asarray(g_bf16, np.float32), valid)
    once = np.asarray(jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16),
                      np.float32)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(once), 1e-30))) - 7)
    assert np.all(np.abs(np.asarray(d_x, np.float32) - once) <= ulp)


@pytest.mark.parametrize("backend,devices,path", [
    ("tpu", 1, "kernel"), ("tpu", 4, "scatter"), ("cpu", 1, "scatter"),
    ("cpu", 8, "scatter"), ("gpu", 1, "scatter")])
def test_the_path_is_a_pure_function_of_backend_and_devices(
        backend, devices, path):
    assert rt.rows_path(backend, devices) == path
    assert transformer.expert_paths(backend, devices) == {
        "products": "kernel" if path == "kernel" else "ragged",
        "rows_to_tokens": path}


@pytest.mark.parametrize("n, want", [(8192, 512), (48, 48), (24, 24),
                                     (1000, 200), (36, 36), (4, 4)])
def test_a_token_tile_divides_the_tokens(n, want):
    """``TOKEN_TILE`` where it divides, the largest multiple of 8 under it
    that does, and the tokens whole where none does."""
    assert rt.token_tile(n) == want and n % want == 0


def test_the_plan_counts_a_block_list_that_covers_each_run():
    """At the block-diffusion cell's routing shape, cut down: each held
    row lies in exactly one block's run, within its tile's blocks, and
    each block's first row is a multiple of the block."""
    held = (0, 8)
    rows = transformer.buffer_rows(N, K, 8, TOTAL, 2.0, 16)
    buf, _, here = _buffer(_chosen("some_held", seed=11), held, rows)
    plan = _plan(buf, "kernel", tile=8)
    bounds, starts = np.asarray(plan.bounds), np.asarray(plan.starts)
    firsts, lasts = np.asarray(plan.firsts), np.asarray(plan.lasts)
    token = np.asarray(buf["token"])
    seen = np.zeros(rows, int)
    for i in range(N // 8):
        for q in range(bounds[i], bounds[i + 1]):
            assert starts[q] % plan.block == 0
            rows_of_run = starts[q] + np.arange(firsts[q], lasts[q])
            assert ((token[rows_of_run] // 8) == i).all()
            seen[rows_of_run] += 1
    assert (seen[:here] == 1).all() and not seen[here:].any()


PRESETS = {
    "trinity_mini_ep16": [
        "afmoe.hidden=64", "afmoe.heads=4", "afmoe.kv_heads=2",
        "afmoe.head_dim=16", "afmoe.window=8", "afmoe.dense_width=96",
        "afmoe.expert_width=32", "afmoe.experts_total=16",
        "afmoe.experts_first=4", "afmoe.experts_held=4", "afmoe.top_k=4",
        "data.vocab_size=128"],
    "sdar_30b_a3b_chat": [
        "sdar_moe.layers=2", "sdar_moe.hidden=64", "sdar_moe.heads=4",
        "sdar_moe.kv_heads=2", "sdar_moe.head_dim=16",
        "sdar_moe.expert_width=32", "sdar_moe.experts_total=16",
        "sdar_moe.experts_first=4", "sdar_moe.experts_held=4",
        "sdar_moe.top_k=4", "data.vocab_size=128"],
    "lfm2_24b_a2b_ep8": [
        "lfm2_moe.hidden=64", "lfm2_moe.heads=4", "lfm2_moe.kv_heads=2",
        "lfm2_moe.head_dim=16", "lfm2_moe.dense_width=96",
        "lfm2_moe.expert_width=32", "lfm2_moe.experts_total=16",
        "lfm2_moe.experts_first=4", "lfm2_moe.experts_held=4",
        "lfm2_moe.top_k=4", "data.vocab_size=128"]}


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_the_families_say_their_expert_path(preset):
    """Each token family's ``startup_events`` names both paths of its
    expert layers, beside ``attention_path``, from the one helper."""
    cfg = load_config(preset)
    events = family(cfg).startup_events(build_model(cfg), cfg)
    assert "attention_path" in events
    assert events["expert_path"] == transformer.expert_paths(
        jax.default_backend(), jax.device_count()) == {
        "products": "ragged", "rows_to_tokens": "scatter"}


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_a_tiny_program_through_the_kernel_is_the_parents(preset,
                                                          monkeypatch):
    """Steered in the test, as a chip would choose: a tiny model of each
    token family, its loss and every gradient with each expert layer's
    rows summed by the kernel (interpret mode), against the parent's
    scatter-add the CPU takes."""
    cfg = load_config(preset, overrides=PRESETS[preset] + [
        "data.seq_len=32", "model.compute_dtype=float32"])
    model = build_model(cfg)
    rng = np.random.default_rng(12)
    length = 64 if preset.startswith("sdar") else 32  # noised + clean
    ids = rng.integers(1, 127, (2, length))
    ids[:, [0, 13, 21]] = 0
    ids = jnp.asarray(ids, jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    weight = None

    def loss(params):
        logits = model.apply(dict(variables, params=params), ids)
        nonlocal weight
        if weight is None:
            weight = jnp.asarray(np.random.default_rng(13).normal(
                size=logits.shape), jnp.float32)
        return jnp.mean(logits * weight)

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss)(variables["params"])
        monkeypatch.setattr(transformer, "expert_paths", lambda *_: {
            "products": "ragged", "rows_to_tokens": "kernel"})
        got = jax.value_and_grad(loss)(variables["params"])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())
