"""The grouped product (ops/grouped.py) on the CPU: the megablox kernels in
Pallas' interpret mode held to ``lax.ragged_dot`` and to a loop over the
groups, forward and in both gradients, with NaN in every row no group
owns; the choice between the two paths; and the expert layer that feeds
it (models/afmoe.py::ExpertLayer): the sorted dispatch against a count by
hand, the tiers beyond the buffer, and the counters of the mechanism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpu_resnet.models import afmoe, transformer
from tpu_resnet.ops import grouped

# rows, contraction and columns that the tiles (32, 16, 16) do not divide
# (the rows must: the kernel asks for whole row tiles)
M, K, N, G = 96, 40, 24, 4
TILING = (32, 16, 16)
# whatever the kernel leaves unwritten reads NaN, as stale HBM may
INTERPRET = pltpu.InterpretParams(uninitialized_memory="nan")
SIZES = {"short": [20, 7, 30, 11],          # sum 68 of 96 rows
         "empty_groups": [0, 41, 0, 23],
         "nothing": [0, 0, 0, 0],
         "full": [32, 1, 40, 23]}


def _operands(sizes):
    """Operands with NaN planted in the rows of ``lhs`` past the sum, and
    the weights of the sum whose gradients are compared (NaN-free: a
    caller's cotangent is finite, the kernel's own garbage is what the
    interpreter's NaN stands for)."""
    rng = np.random.default_rng(0)
    lhs = rng.normal(size=(M, K)).astype(np.float32)
    lhs[sum(sizes):] = np.nan
    return (jnp.asarray(lhs),
            jnp.asarray(rng.normal(size=(G, K, N)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(M, N)).astype(np.float32)))


def _by_loop(lhs, rhs, weight, sizes):
    """Group by group in numpy: the result, and the gradients of
    ``sum(result * weight)``."""
    lhs, rhs, weight = (np.asarray(a, np.float64) for a in (lhs, rhs, weight))
    out, d_lhs, d_rhs = (np.zeros((M, N)), np.zeros((M, K)),
                         np.zeros((G, K, N)))
    lo = 0
    for g, size in enumerate(sizes):
        rows = slice(lo, lo + size)
        out[rows] = lhs[rows] @ rhs[g]
        d_lhs[rows] = weight[rows] @ rhs[g].T
        d_rhs[g] = lhs[rows].T @ weight[rows]
        lo += size
    return out, d_lhs, d_rhs


def _run(path, lhs, rhs, weight, sizes):
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(lhs, rhs):
        out = grouped.grouped_dot(lhs, rhs, sizes, jnp.float32, path,
                                  tiling=TILING, interpret=INTERPRET)
        return jnp.sum(out * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(lhs, rhs)
    return [np.asarray(a) for a in (out,) + grads]


@pytest.mark.parametrize("path", ["kernel", "ragged"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_both_paths_equal_a_loop_over_the_groups(case, path):
    """Forward and both gradients, at sizes the tiles do not divide; no
    NaN of the rows past the sum reaches anything, and those rows are 0."""
    sizes = SIZES[case]
    lhs, rhs, weight = _operands(sizes)
    with jax.default_matmul_precision("highest"):
        got = _run(path, lhs, rhs, weight, sizes)
    for name, a, b in zip(("out", "d_lhs", "d_rhs"), got,
                          _by_loop(lhs, rhs, weight, sizes)):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    assert not got[0][sum(sizes):].any() and not got[1][sum(sizes):].any()


def test_the_kernel_rounds_as_the_ragged_product_does_in_bf16():
    """bf16 operands, float32 accumulation, a bf16 and a float32 result,
    bf16 gradients: the two paths differ by a rounding of what they hand
    on at most."""
    sizes = SIZES["short"]
    lhs, rhs, weight = _operands(sizes)
    lhs = jnp.nan_to_num(lhs).astype(jnp.bfloat16)
    rhs = rhs.astype(jnp.bfloat16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    for out_dtype, tol in ((jnp.bfloat16, 2 ** -7), (jnp.float32, 1e-5)):
        def both(path):
            def loss(lhs, rhs):
                out = grouped.grouped_dot(lhs, rhs, group_sizes, out_dtype,
                                          path, tiling=TILING,
                                          interpret=True)
                return jnp.sum(out.astype(jnp.float32) * weight), out

            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(lhs, rhs)
            assert out.dtype == out_dtype
            assert grads[0].dtype == grads[1].dtype == jnp.bfloat16
            return [np.asarray(a, np.float32) for a in (out,) + grads]

        for a, b, within in zip(both("kernel"), both("ragged"),
                                (tol, 2 ** -7, 2 ** -7)):
            assert np.max(np.abs(a - b)) <= within * np.max(np.abs(b)) * 2


@pytest.mark.parametrize("backend,devices,path", [
    ("tpu", 1, "kernel"), ("tpu", 4, "ragged"), ("cpu", 1, "ragged"),
    ("cpu", 8, "ragged"), ("gpu", 1, "ragged")])
def test_the_path_is_a_pure_function_of_backend_and_devices(
        backend, devices, path):
    assert grouped.grouped_path(backend, devices) == path
    assert grouped.row_tile(path) == (grouped.TILING[0] if path == "kernel"
                                      else 8)


# ------------------------------------------------- the layer that feeds it
D, WIDTH, TOTAL, TOP_K = 16, 8, 8, 2


def _layer(held, slack):
    return afmoe.ExpertLayer(WIDTH, TOTAL, held, TOP_K, 0, 2.826, 0.001,
                             slack, jnp.float32)


def _apply(layer, x, bias=None):
    v = layer.init(jax.random.PRNGKey(1), x, False)
    out, state = layer.apply(
        {"params": v["params"], "batch_stats": {
            "expert_bias": jnp.zeros((TOTAL,)) if bias is None else bias}},
        x, False, mutable=["counters"])
    return out, {k: float(c) for k, c in state["counters"].items()}, \
        v["params"]


def _by_hand(params, x, held, bias=None):
    """Token by token, expert by expert: the layer's sum, and the held
    assignments in the order the buffer has to hold them."""
    first, count = held
    x = np.asarray(x, np.float64).reshape(-1, D)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    scores = 1 / (1 + np.exp(-(x @ p["router"])))
    biased = scores + (0 if bias is None else np.asarray(bias, np.float64))
    out, held_list = np.zeros_like(x), []
    for t in range(len(x)):
        chosen = np.argsort(-biased[t], kind="stable")[:TOP_K]
        weights = scores[t, chosen] / (scores[t, chosen].sum() + 1e-20) \
            * 2.826
        for e, w in zip(chosen, weights):
            if first <= e < first + count:
                i = e - first
                gate, up = x[t] @ p["gate"][i], x[t] @ p["up"][i]
                out[t] += w * ((gate / (1 + np.exp(-gate)) * up)
                               @ p["down"][i])
                held_list.append((i, t))
    return out, sorted(held_list)     # by expert, then by token: stable


def test_every_assignment_here_fills_the_whole_buffer_and_none_is_dropped():
    """All experts held: ``N * k`` rows, one tier, nothing beyond it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8, D))
    out, counters, params = _apply(_layer((0, TOTAL), 2.0), x)
    want, held = _by_hand(params, x, (0, TOTAL))
    assert len(held) == 24 * TOP_K
    np.testing.assert_allclose(out.reshape(-1, D), want, atol=2e-5)
    assert counters == {
        "moe_dropped_frac": 0.0, "moe_here_frac": 1.0,
        "moe_overflow_frac": 0.0, "moe_rows_filled_frac": 1.0,
        "moe_load_max_over_mean": counters["moe_load_max_over_mean"]}


@pytest.mark.parametrize("slack,tiers", [(2.0, 1), (0.5, 4)])
def test_the_counters_read_what_a_hand_count_gives(slack, tiers):
    """3 of 8 experts held, 64 tokens x top-2: an even routing sends 48
    assignments here, so the buffer holds 96 rows at slack 2 and 24 at
    0.5; a bias that draws every token to expert 2 sends 64 + the other
    two's share, past either."""
    held = (1, 3)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, D))
    bias = jnp.zeros((TOTAL,)).at[2].set(10.0)
    layer = _layer(held, slack)
    rows = transformer.buffer_rows(64, TOP_K, 3, TOTAL, slack, 8)
    assert rows == (96 if slack == 2.0 else 24)
    for b in (None, bias):
        out, counters, params = _apply(layer, x, b)
        want, assignments = _by_hand(params, x, held, b)
        np.testing.assert_allclose(out.reshape(-1, D), want, atol=2e-5)
        here = len(assignments)
        load = np.bincount([e for e, _ in assignments], minlength=3)
        assert counters["moe_dropped_frac"] == 0.0
        assert counters["moe_here_frac"] == pytest.approx(here / 128)
        assert counters["moe_overflow_frac"] == float(here > rows)
        assert counters["moe_rows_filled_frac"] == pytest.approx(
            min(here, rows) / rows)
        assert counters["moe_load_max_over_mean"] == pytest.approx(
            load.max() * 3 / here)
    assert here > 64 and (here > rows) == (tiers > 1)


def test_the_sorted_dispatch_keeps_token_order_within_an_expert():
    """The buffer's rows are the held assignments by expert and, within an
    expert, by token: the ranks of a running count, not a sort's whim."""
    held = (1, 3)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, D))
    layer = _layer(held, 2.0)
    params = layer.init(jax.random.PRNGKey(1), x, False)["params"]
    _, assignments = _by_hand(params, x, held)
    seen = []

    def spy(lhs, rhs, sizes, out, path):
        seen.append((np.asarray(lhs), np.asarray(sizes)))
        return grouped.grouped_dot(lhs, rhs, sizes, out, path)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "grouped_dot", spy)
        with jax.disable_jit():
            layer.apply({"params": params, "batch_stats": {
                "expert_bias": jnp.zeros((TOTAL,))}}, x, False)
    rows, sizes = seen[0]          # the gate product of the first tier
    flat = np.asarray(x).reshape(-1, D)
    np.testing.assert_array_equal(
        sizes, np.bincount([e for e, _ in assignments], minlength=3))
    np.testing.assert_array_equal(
        rows[:len(assignments)], flat[[t for _, t in assignments]])


def test_an_empty_tier_runs_no_product():
    """At slack 0.5 the 128 assignments make 6 tiers of 24 rows (the last
    padded); only the tiers that hold an assignment run their three
    products, counted by a callback in each product's place."""
    held = (1, 3)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, D))
    layer = _layer(held, 0.5)
    params = layer.init(jax.random.PRNGKey(1), x, False)["params"]
    _, assignments = _by_hand(params, x, held)
    calls = []

    def counted(lhs, rhs, sizes, out, path):
        jax.debug.callback(lambda s: calls.append(int(s)), jnp.sum(sizes))
        return grouped.grouped_dot(lhs, rhs, sizes, out, path)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "grouped_dot", counted)
        out = jax.jit(lambda p: layer.apply(
            {"params": p, "batch_stats": {
                "expert_bias": jnp.zeros((TOTAL,))}}, x, False))(params)
        jax.block_until_ready(out)
        jax.effects_barrier()
    here = len(assignments)
    assert 24 < here < 128 - 24            # some tiers run, some do not
    assert len(calls) == 3 * -(-here // 24)
    assert sorted(calls)[-1] == 24 and sum(calls) == 3 * here


@pytest.mark.parametrize("k, n, forward, d_lhs, d_rhs", [
    (2048, 1024, (256, 1024, 1024), (256, 1024, 1024), (256, 1024, 1024)),
    (1024, 2048, (256, 1024, 1024), (256, 1024, 1024), (256, 1024, 1024)),
    # experts 768 wide: no tile of 1,024 a quarter of which is padding
    (2048, 768, (256, 1024, 768), (256, 768, 1024), (256, 1024, 768)),
    (768, 2048, (256, 768, 1024), (256, 1024, 768), (256, 768, 1024)),
    # experts 1,536 wide: one tile of half again the tiling's, where two of
    # 1,024 would be a quarter padding and a masked remainder
    (2048, 1536, (256, 1024, 1536), (256, 1536, 1024), (256, 1024, 1536)),
    (1536, 2048, (256, 1536, 1024), (256, 1024, 1536), (256, 1536, 1024)),
])
def test_tiles_take_a_smaller_contraction_or_width_whole(k, n, forward,
                                                         d_lhs, d_rhs):
    """The three kernels' tiles for ``(rows, k) x (groups, k, n)``: the
    Trinity cell's shapes keep the swept tiling; the backward product for
    ``lhs`` contracts over ``n`` and writes ``k`` columns."""
    assert grouped._fit(grouped.TILING, k, n) == forward == d_rhs
    assert grouped._fit(grouped.TILING, n, k) == d_lhs


@pytest.mark.parametrize("size, tile", [
    (1024, 1024), (2048, 1024), (768, 768), (1536, 1536), (2560, 1280),
    (3072, 1024), (11776, 512), (1100, 1024), (64, 64)])
def test_a_tile_divides_the_size_it_is_cut_to(size, tile):
    """``_fit_one``: whole under the tile, the tile where it divides, else
    the largest multiple of 128 that divides and is no more than half
    again the tile; where nothing divides (1,100), the tile, masked."""
    assert grouped._fit_one(1024, size) == tile


@pytest.mark.parametrize("k, n", [(128, 1536), (1536, 128)],
                         ids=["columns", "contraction"])
def test_experts_1536_wide_equal_a_loop_over_the_groups(k, n):
    """The kernel (interpret mode) at the width of ``lfm2_moe``'s experts,
    as columns and as contraction, under the module's own tiling: the
    result and both gradients against a loop, NaN in every row no group
    owns."""
    sizes, rows = [20, 0, 30], 64
    rng = np.random.default_rng(1)
    lhs = rng.normal(size=(rows, k)).astype(np.float32)
    lhs[sum(sizes):] = np.nan
    rhs = rng.normal(size=(3, k, n)).astype(np.float32)
    weight = rng.normal(size=(rows, n)).astype(np.float32)
    assert grouped._fit(grouped.TILING, k, n)[1:] == (k, n)

    def loss(lhs, rhs):
        out = grouped.grouped_dot(
            lhs, rhs, jnp.asarray(sizes, jnp.int32), jnp.float32, "kernel",
            tiling=(32,) + grouped.TILING[1:], interpret=INTERPRET)
        return jnp.sum(out * weight), out

    with jax.default_matmul_precision("highest"):
        (_, out), (d_lhs, d_rhs) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(jnp.asarray(lhs),
                                                 jnp.asarray(rhs))
    lo = 0
    for g, size in enumerate(sizes):
        at = slice(lo, lo + size)
        np.testing.assert_allclose(out[at], lhs[at] @ rhs[g], rtol=1e-4,
                                   atol=1e-3)
        np.testing.assert_allclose(d_lhs[at], weight[at] @ rhs[g].T,
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(d_rhs[g], lhs[at].T @ weight[at],
                                   rtol=1e-4, atol=1e-3)
        lo += size
    assert not np.asarray(out[lo:]).any() and not np.asarray(d_lhs[lo:]).any()
