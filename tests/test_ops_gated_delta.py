"""Gated DeltaNet's recurrence in the chunked form (ops/gated_delta.py):
the kernels under Pallas' interpreter (under ``jax.checkpoint`` too, the
inverses kept) and the scan, each against the recurrence written out token
by token, forward and every input's gradient, on the CPU at a tiny size (2
sequences of 32 or 40, 2 key heads and 4 value heads of 16, chunks of 8);
the inverse kernel against the function it runs, to the bit; which kernel
holds the float32 products; and the kernels compiled for a described v5e at
the cell's shapes."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core

from tpu_resnet.ops import gated_delta

B, HK, HV, D, C = 2, 2, 4, 16, 8


def token_by_token(q, k, v, beta, g, reset):
    """``S = 0`` where a document begins; ``S = exp(g) S``; ``S += beta k
    (v - S^T k)^T``; ``o = S^T q``: elementwise products and sums."""
    q, k = (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))

    def step(state, x):
        qt, kt, vt, bt, gt, new = x
        state = jnp.where(new[:, None, None, None], 0.0, state) \
            * jnp.exp(gt)[..., None, None]
        read = jnp.sum(kt[..., :, None] * state, axis=-2)
        state = state + bt[..., None, None] * kt[..., :, None] \
            * (vt - read)[..., None, :]
        return state, jnp.sum(qt[..., :, None] * state, axis=-2)

    _, out = jax.lax.scan(step, jnp.zeros((B, HV, D, D)),
                          [jnp.moveaxis(x, 1, 0)
                           for x in (q, k, v, beta, g, reset)])
    return jnp.moveaxis(out, 0, 1).reshape(B, q.shape[1], HV * D)


def inputs(length=32, starts=(), decay=3.0, seed=0):
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.normal(size=shape).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    reset = np.zeros((B, length), bool)
    for b, t in starts:
        reset[b, t] = True
    return [jnp.asarray(x) for x in (
        unit(B, length, HK, D), unit(B, length, HK, D),
        rng.normal(size=(B, length, HV, D)).astype(np.float32),
        rng.uniform(size=(B, length, HV)).astype(np.float32),
        -rng.uniform(0, decay, size=(B, length, HV)).astype(np.float32),
        reset)]


CASES = {
    # documents begun inside a chunk, on its first position, and in two
    # consecutive positions (a document of one position)
    "mid_chunk": dict(starts=[(0, 0), (0, 5), (1, 11), (1, 29)]),
    "on_edges": dict(starts=[(0, 8), (0, 16), (1, 24)]),
    "consecutive": dict(starts=[(0, 3), (0, 4), (0, 5), (1, 15), (1, 16)]),
    # a decay of 200 a position: G reaches -1,600 in a chunk, so that
    # exp(-G) would overflow float32 fifty times over
    "strong_decay": dict(starts=[(1, 13)], decay=200.0),
    # not a power of two of chunks: 5 of 8
    "five_chunks": dict(length=40, starts=[(0, 21)]),
}


@pytest.mark.parametrize("path", ["scan", "kernel", "kernel_remat"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_form_is_the_recurrence_token_by_token(path, case):
    """``kernel_remat``: the kernels under ``jax.checkpoint`` keeping only
    the inverses, as the model's remat does; the backward pass runs the
    forward kernel again on the kept inverses."""
    q, k, v, beta, g, reset = inputs(**CASES[case])

    def run(q, k, v, beta, g):
        return gated_delta.gated_delta(q, k, v, beta, g, reset,
                                       dtype=jnp.float32, chunk=C,
                                       path=path.split("_")[0])

    if path == "kernel_remat":
        run = jax.checkpoint(run, policy=jax.checkpoint_policies
                             .save_only_these_names(gated_delta.INVERSE))

    def want(q, k, v, beta, g):
        return token_by_token(q, k, v, beta, g, reset)

    got, ref = run(q, k, v, beta, g), want(q, k, v, beta, g)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, ref, atol=2e-5 * float(jnp.max(
        jnp.abs(ref))))
    cot = jax.random.normal(jax.random.PRNGKey(1), ref.shape)
    grads = jax.grad(lambda *a: jnp.sum(run(*a) * cot),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, beta, g)
    wants = jax.grad(lambda *a: jnp.sum(want(*a) * cot),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, beta, g)
    for name, a, b in zip(("q", "k", "v", "beta", "g"), grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.max(
            jnp.abs(b))), err_msg=name)


@pytest.mark.parametrize("units", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_inverse_kernel_is_the_unit_lower_inverse_to_the_bit(case,
                                                                 units):
    """``gated_delta_fwd_inverse``, ``units`` chunks a grid step, writes for
    every sequence, value head and chunk the bf16 cast of
    ``_unit_lower_inverse`` of ``N`` from ``_masks``, bit for bit."""
    q, k, v, beta, g, reset = inputs(**CASES[case])
    got = np.asarray(gated_delta.inverses(k, beta, g, reset,
                                          dtype=jnp.bfloat16, chunk=C,
                                          units=units))
    b, length, hv = beta.shape
    assert got.shape == (b, hv, length // C, C, C)

    def chunked(x):
        return jnp.cumsum(x.reshape(b, length // C, C, *x.shape[2:]),
                          axis=2).reshape(x.shape)

    g_cum, r_cum = chunked(g), chunked(reset.astype(jnp.float32))
    kb = k.astype(jnp.bfloat16)

    @jax.jit
    def want(k, beta, gc, rc):
        _, strict, gam, *_ = gated_delta._masks(gc[:, None], gc[None, :],
                                                rc[:, None], rc[None, :], D)
        kk = gated_delta._mm(k, k, gated_delta._NT, jnp.bfloat16)
        return gated_delta._unit_lower_inverse(beta[:, None] * jnp.where(
            strict, kk * gam, 0.0)).astype(jnp.bfloat16)

    for i in range(b):
        for h in range(hv):
            for c in range(length // C):
                at = slice(c * C, (c + 1) * C)
                np.testing.assert_array_equal(
                    got[i, h, c], want(kb[i, at, h // (HV // HK)],
                                       beta[i, at, h], g_cum[i, at, h],
                                       r_cum[i, at]),
                    err_msg=f"sequence {i}, head {h}, chunk {c}")


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(x, (core.Jaxpr, core.ClosedJaxpr)):
                    yield from _eqns(getattr(x, "jaxpr", x))


def _kernel_bodies(jaxpr):
    """``{name: [the precision of each product in its body]}`` of every
    ``pallas_call`` under ``jaxpr``."""
    found = {}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).extend(
                e.params["precision"] for e in _eqns(eqn.params["jaxpr"])
                if e.primitive.name == "dot_general")
    return found


def test_only_the_inverse_kernel_holds_float32_products():
    """Forward and backward, the step holds one call of each kernel; the
    products at ``HIGHEST`` (the inverse's) are the inverse kernel's alone:
    the forward and the backward kernel read the inverse it wrote."""
    q, k, v, beta, g, reset = inputs(starts=[(0, 5)])

    def loss(q, k, v, beta, g):
        return jnp.sum(gated_delta.gated_delta(q, k, v, beta, g, reset,
                                               dtype=jnp.bfloat16, chunk=C,
                                               path="kernel"))

    found = _kernel_bodies(jax.make_jaxpr(jax.grad(
        loss, argnums=(0, 1, 2, 3, 4)))(q, k, v, beta, g).jaxpr)
    assert set(found) == {"gated_delta_fwd_inverse", "gated_delta_fwd",
                          "gated_delta_bwd"}
    top = (jax.lax.Precision.HIGHEST,) * 2

    def highest(name):
        return sum(p == top for p in found[name])

    # log2(8) - 1 = 2 squarings, two products each, for each chunk of a
    # grid step (the module's units, of the 4 chunks)
    units = math.gcd(gated_delta.UNITS, 32 // C)
    assert highest("gated_delta_fwd_inverse") == 4 * units
    assert highest("gated_delta_fwd") == highest("gated_delta_bwd") == 0
    assert len(found["gated_delta_fwd"]) == 6       # K K^T no longer among them


def test_a_document_begun_mid_chunk_reads_nothing_of_the_one_before():
    """The state is 0 at a document's first position: the outputs from
    there on are those of the document fed alone."""
    q, k, v, beta, g, reset = inputs(starts=[(0, 0), (1, 0), (0, 13),
                                             (1, 13)])
    packed = gated_delta.gated_delta(q, k, v, beta, g, reset,
                                     dtype=jnp.float32, chunk=C,
                                     path="kernel")
    tail = [x[:, 13:29] for x in (q, k, v, beta, g, reset)]
    alone = gated_delta.gated_delta(*tail, dtype=jnp.float32, chunk=C,
                                    path="kernel")
    np.testing.assert_allclose(packed[:, 13:29], alone, atol=1e-5)


def test_a_sequence_of_no_whole_number_of_chunks_is_refused():
    q, k, v, beta, g, reset = inputs(length=36)
    with pytest.raises(ValueError, match="not a whole number of chunks"):
        gated_delta.gated_delta(q, k, v, beta, g, reset, dtype=jnp.float32,
                                chunk=C)


def test_doc_chunks_frac_counts_chunks_cut_inside():
    """A document begun on a chunk's first position does not count; one
    begun after it does, once a chunk however many begin there."""
    reset = np.zeros((2, 32), bool)
    reset[0, [0, 8, 11, 12]] = True      # chunk 0 edge, chunk 1 twice inside
    reset[1, [1, 31]] = True             # chunks 0 and 3
    got = float(gated_delta.doc_chunks_frac(jnp.asarray(reset), 8))
    assert got == pytest.approx(3 / 8)


def test_the_path_follows_the_backend_and_the_devices():
    assert gated_delta.recurrence_path("tpu", 1) == "kernel"
    assert gated_delta.recurrence_path("tpu", 4) == "scan"
    assert gated_delta.recurrence_path("cpu", 1) == "scan"


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler without a TPU, through
    a topology description. Only here, never at import."""
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


def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes(one_chip,
                                                           monkeypatch):
    """Mosaic takes the inverse, the forward and the backward kernel at 2 x
    4,096 positions, 16 key and 32 value heads of 128, the module's chunk
    (what interpret mode cannot show: layouts, broadcasts, VMEM). A
    compile, not a run; the chip's backend is named in the test, as a chip
    would name it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, beta, g, reset):
        return jnp.sum(gated_delta.gated_delta(
            q, k, v, beta, g, reset, dtype=jnp.bfloat16,
            path="kernel").astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shaped(2, 4096, 16, 128), shaped(2, 4096, 16, 128),
        shaped(2, 4096, 32, 128), shaped(2, 4096, 32, dtype=jnp.float32),
        shaped(2, 4096, 32, dtype=jnp.float32),
        shaped(2, 4096, dtype=jnp.bool_)).compile().as_text()
    for name in ("gated_delta_fwd_inverse", "gated_delta_fwd",
                 "gated_delta_bwd"):
        assert re.search(rf"\b{name}\b", text), name
