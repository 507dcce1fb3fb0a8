"""The one exact expert layer (``models/moe.grouped_experts``): equal to the
every-expert-on-every-token mask form and to the plain reference's loop
(``benchmarks/reference/joyai.py``) for few and many experts, one token and
a thousand, under uneven routing, on both of its paths (``lax.ragged_dot``
and the ``moe_grouped`` kernel, interpret mode here), with no assignment
dropped by construction and no ``[T, E, I]`` temporary in its program; and
the routing plan in front of the kernel, built a tile at a time, equal entry
for entry to the row-wise plan it replaced (kept here as the plain form)."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.program_check import expert_temporaries
from dynamo_tpu.models.moe import (_sorted_picks, _tile_plan,
                                   grouped_experts)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, I = 256, 128


def _reference():
    spec = importlib.util.spec_from_file_location(
        "ref_joyai", os.path.join(REPO, "benchmarks", "reference",
                                  "joyai.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _case(E, k, T, dtype=jnp.float32, uneven=True):
    ks = jax.random.split(jax.random.PRNGKey(E * 1000 + T), 5)
    xt = jax.random.normal(ks[0], (T, H), jnp.float32)
    wg = jax.random.normal(ks[1], (E, H, I), jnp.float32) * H ** -0.5
    wu = jax.random.normal(ks[2], (E, H, I), jnp.float32) * H ** -0.5
    wd = jax.random.normal(ks[3], (E, I, H), jnp.float32) * I ** -0.5
    logits = jax.random.normal(ks[4], (T, E), jnp.float32)
    if uneven:
        # one expert takes (nearly) every token, a handful share the rest,
        # most stay empty
        logits = logits.at[:, 0].add(8.0).at[:, max(k, E // 8):].add(-8.0)
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True) * 2.5
    cast = [a.astype(dtype) for a in (xt, wg, wu, wd)]
    return cast[0], top_w, top_i, cast[1], cast[2], cast[3]


def mask_form(xt, top_w, top_i, wg, wu, wd):
    """Every expert on every token, the routing weights as a mask: the
    layer the grouped one replaced, kept here as its oracle."""
    E = wg.shape[0]
    w = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                * top_w[..., None], axis=1)                      # [T, E]
    act = (jax.nn.silu(jnp.einsum("th,ehi->tei", xt, wg))
           * jnp.einsum("th,ehi->tei", xt, wu))
    return jnp.einsum("te,teh->th", w, jnp.einsum("tei,eih->teh", act, wd))


def reference_loop(xt, top_w, top_i, wg, wu, wd):
    """``reference/joyai.py``'s plain loop over the experts, in its blocks."""
    ref = _reference()
    T, E = xt.shape[0], wg.shape[0]
    weight = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], top_i].set(top_w)
    carry = (xt, xt, weight, jnp.zeros_like(xt))
    for first in range(0, E, ref.EXPERT_BLOCK):
        cut = slice(first, first + ref.EXPERT_BLOCK)
        carry = ref.moe_block({}, {"w_gate": wg[cut], "w_up": wu[cut],
                                   "w_down": wd[cut],
                                   "first": jnp.asarray(first)}, carry)
    return carry[3]


# float32 everywhere: the forms differ by summation order alone. Outputs
# are of order 1 (weights scaled by fan-in, routing weights summing to
# 2.5), sums of at most 8 x 128 products: rounding is a few 1e-6. The same
# layer in bfloat16 differs by about 1e-2 (test below), so 5e-5 tells
# float32 from anything coarser.
TOL = 5e-5


@pytest.mark.parametrize("T", [1, 16, 1000])
@pytest.mark.parametrize("E,k", [(8, 2), (64, 6), (256, 8)])
@pytest.mark.parametrize("path", ["ragged_dot", "moe_grouped"])
def test_grouped_equals_mask_form_and_reference_loop(path, E, k, T):
    args = _case(E, k, T)
    with jax.default_matmul_precision("highest"):
        out, aux = jax.jit(
            lambda *a: grouped_experts(
                *a, use_pallas=path == "moe_grouped"))(*args)
        touched, assigned = aux["moe_experts_touched"], aux["moe_assignments"]
        want = mask_form(*args)
        loop = reference_loop(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(loop),
                               atol=TOL, rtol=0)
    # no capacity, so nothing can drop: every assignment was computed
    assert int(assigned) == T * k
    assert int(touched) == len(np.unique(np.asarray(args[2])))
    if T >= 16:
        # uneven by construction: expert 0 holds a row of nearly every
        # token, most experts none
        assert np.mean(np.asarray(args[2]) == 0) * k > 0.9
        assert int(touched) <= max(k, E // 8)


def test_bfloat16_would_fail_the_float32_tolerance():
    args = _case(64, 6, 16)
    low = [a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1
           and i not in (1,) else a for i, a in enumerate(args)]
    out, _ = grouped_experts(*low)
    with jax.default_matmul_precision("highest"):
        want = mask_form(*args)
    assert float(jnp.max(jnp.abs(out - want))) > 20 * TOL


@pytest.mark.parametrize("path", ["ragged_dot", "moe_grouped"])
def test_slots_without_a_token_route_nowhere(path):
    """Padding of a ``[B, S]`` step and dead rows of a fused block: no
    expert is touched for them, they come back zero, and the others are
    what they would be alone."""
    xt, top_w, top_i, wg, wu, wd = _case(64, 6, 48, uneven=False)
    valid = jnp.arange(48) % 3 == 0
    use = path == "moe_grouped"
    with jax.default_matmul_precision("highest"):
        out, aux = grouped_experts(
            xt, top_w, top_i, wg, wu, wd, valid=valid, use_pallas=use)
        alone, aux_alone = grouped_experts(
            xt[valid], top_w[valid], top_i[valid], wg, wu, wd,
            use_pallas=use)
        none = grouped_experts(xt, top_w, top_i, wg, wu, wd,
                               valid=jnp.zeros(48, bool), use_pallas=use)
    assert int(aux["moe_assignments"]) == 16 * 6
    assert int(aux["moe_experts_touched"]) == int(
        aux_alone["moe_experts_touched"])
    np.testing.assert_allclose(np.asarray(out[valid]), np.asarray(alone),
                               atol=TOL, rtol=0)
    assert not np.asarray(out[~valid]).any()
    assert not np.asarray(none[0]).any()
    assert int(none[1]["moe_experts_touched"]) == 0


@pytest.mark.parametrize("path", ["ragged_dot", "moe_grouped"])
def test_stacked_experts_are_read_by_layer(path):
    """Under the scan over layers the experts come stacked ``[L, E, ...]``
    with the layer's index, never as a slice."""
    xt, top_w, top_i, wg, wu, wd = _case(8, 2, 16)
    stack = [jnp.stack([w * 0, w, w * 2]) for w in (wg, wu, wd)]
    with jax.default_matmul_precision("highest"):
        out, _ = jax.jit(lambda layer: grouped_experts(
            xt, top_w, top_i, *stack, layer=layer,
            use_pallas=path == "moe_grouped"))(jnp.int32(1))
        want = mask_form(xt, top_w, top_i, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL, rtol=0)


def test_the_grouped_program_holds_no_token_by_expert_temporary():
    """``(E=256, k=8, T=16)``, the decode shape of the benchmark's cell:
    the mask form's program writes ``[T, E, I]`` arrays, the grouped one
    none (and, having no capacity, has nothing to drop)."""
    E, k, T = 256, 8, 16
    args = _case(E, k, T)

    def text(fn):
        return jax.jit(fn).lower(*args).compile().as_text()

    assert expert_temporaries(text(mask_form), T, E, I)
    grouped = text(lambda *a: grouped_experts(*a))
    assert expert_temporaries(grouped, T, E, I) == []
    assert int(grouped_experts(*args)[1]["moe_assignments"]) == T * k


# -- the routing plan in front of the kernel ---------------------------------

def row_wise_plan(flat_e, k, E, tm, n_tiles):
    """The plan as it was built before PR 44, from the ROW's side, kept as
    the plain form: five lookups a row of the grouped call (the tile's
    expert, where its group's rows start, how many it has, where its
    assignments start in the sorted list, the sorted assignment there) and
    two an assignment."""
    i32 = jnp.int32
    A, M = flat_e.shape[0], n_tiles * tm
    order = jnp.argsort(flat_e, stable=True).astype(i32)
    sorted_e = flat_e[order]
    sorted_t = order // k
    first = jnp.searchsorted(sorted_e, jnp.arange(E + 1, dtype=i32))
    counts = first[1:] - first[:-1]
    tiles = -(-counts // tm)
    tile_end = jnp.cumsum(tiles)
    num_tiles = tile_end[-1]
    row_first = (tile_end - tiles) * tm
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(n_tiles, dtype=i32), side="right"), E - 1)
    row = jnp.arange(M, dtype=i32)
    row_e = tile_expert[row // tm]
    row_rank = row - row_first[row_e]
    live = (row // tm < num_tiles) & (row_rank < counts[row_e])
    row_tok = jnp.where(live, sorted_t[jnp.minimum(
        first[row_e] + row_rank, A - 1)], 0)
    tile_expert = jnp.where(
        jnp.arange(n_tiles) < num_tiles, tile_expert,
        tile_expert[jnp.maximum(num_tiles - 1, 0)])
    safe_e = jnp.minimum(sorted_e, E - 1)
    pos = jnp.zeros(A, i32).at[order].set(
        row_first[safe_e] + jnp.arange(A, dtype=i32) - first[safe_e])
    return row_tok, live, pos, tile_expert, num_tiles


def _picks(T, k, width, seed=0):
    """``k`` distinct picks a token among ``width`` router columns."""
    logits = jax.random.normal(jax.random.PRNGKey(seed), (T, width))
    return jax.lax.top_k(jax.nn.softmax(logits), k)


def _on_a_boundary(T, k, width):
    """Expert 0 takes one pick of every token - ``T`` rows, whole tiles of
    16 exactly - the other picks spread over experts 1 and up."""
    top_w, _ = _picks(T, k, width)
    spread = 1 + (jnp.arange(T)[:, None] + jnp.arange(k - 1)[None]) % (
        width - 1)
    return top_w, jnp.concatenate(
        [jnp.zeros((T, 1), jnp.int32), spread.astype(jnp.int32)], axis=1)


# (T, k, experts held, first held, computing experts, router width,
#  valid slots, picks)
PLAN_CASES = {
    "tiles of 16": (16, 8, 256, 0, None, 256, None, _picks),
    "tiles of 128": (300, 8, 64, 0, None, 64, None, _picks),
    "a held range from 8": (48, 6, 8, 8, 32, 32, None, _picks),
    "zero-compute picks": (48, 6, 8, 0, 32, 48, None, _picks),
    "dead slots": (48, 6, 64, 0, None, 64,
                   lambda T: jnp.arange(T) % 3 != 1, _picks),
    "fewer experts than picks": (128, 4, 3, 2, 16, 16, None, _picks),
    "a group ends on a tile boundary": (32, 3, 8, 0, None, 8, None,
                                        _on_a_boundary),
    "no pick is held": (40, 4, 4, 8, 16, 8, None, _picks),
    "no slot holds a token": (24, 4, 16, 0, None, 16,
                              lambda T: jnp.zeros(T, bool), _picks),
    "the long-document step": (1152, 10, 128, 0, 512, 512,
                               lambda T: jnp.arange(T) < 1100, _picks),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_the_plan_built_a_tile_at_a_time_equals_the_row_wise_plan(case):
    """``_tile_plan`` (per-tile lookups, a broadcast, whole windows of the
    sorted list) names the same token in every row, the same live rows, the
    same row for every assignment and the same expert for every tile as the
    row-wise plan; and the layer on the kernel computes what the mask form
    does, held range, zero-compute experts and dead slots included."""
    T, k, E, first, routed, width, valid, picks = PLAN_CASES[case]
    top_w, top_i = picks(T, k, width)
    valid = None if valid is None else valid(T)
    A = T * k
    tm = 16 if A <= 2048 else 128
    held = T * min(k, E)
    n_tiles = -(-held // tm) + min(E, held)

    local = np.asarray(top_i).reshape(A) - first
    flat_e = np.where((local >= 0) & (local < E), local, E)
    if valid is not None:
        flat_e = np.where(np.repeat(np.asarray(valid), k), flat_e, E)
    flat_e = jnp.asarray(flat_e, jnp.int32)
    want = row_wise_plan(flat_e, k, E, tm, n_tiles)
    sorted_t, order, firsts = _sorted_picks(flat_e, E, k)
    got = jax.jit(lambda *a: _tile_plan(*a, tm=tm, n_tiles=n_tiles))(
        sorted_t, order, firsts)
    for name, g, w in zip(("row_tok", "row_live", "pos", "tile_expert",
                           "num_tiles"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    if case == "a group ends on a tile boundary":
        assert int(firsts[1]) == T and T % tm == 0
        assert bool(got[1][T - 1]) and int(got[3][T // tm]) == 1
    if case.startswith("no "):
        assert int(got[4]) == 0 and not np.asarray(got[1]).any()

    Hs, Is = 128, 128                       # the least the kernel tiles
    ks = jax.random.split(jax.random.PRNGKey(T + E), 4)
    xt = jax.random.normal(ks[0], (T, Hs), jnp.float32)
    wg = jax.random.normal(ks[1], (E, Hs, Is), jnp.float32) * Hs ** -0.5
    wu = jax.random.normal(ks[2], (E, Hs, Is), jnp.float32) * Hs ** -0.5
    wd = jax.random.normal(ks[3], (E, Is, Hs), jnp.float32) * Is ** -0.5
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True) * 2.5
    kw = {} if routed is None else dict(first_expert=first,
                                        num_routed=routed)
    with jax.default_matmul_precision("highest"):
        out, aux = jax.jit(lambda *a: grouped_experts(
            *a, valid=valid, use_pallas=True, **kw))(
                xt, top_w, top_i, wg, wu, wd)
        # picks outside the held range are rows of zeros in the one-hot
        seen = top_w if valid is None else top_w * valid[:, None]
        oracle = mask_form(xt, seen, top_i - first, wg, wu, wd)
        if routed is not None:
            oracle += jnp.sum(jnp.where(top_i >= routed, seen, 0.0),
                              axis=1, keepdims=True) * xt
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=TOL, rtol=0)
    assert int(aux["moe_held_assignments"]) == int(firsts[E])
