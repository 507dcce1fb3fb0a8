"""The one exact expert layer (``models/moe.grouped_experts``): equal to the
every-expert-on-every-token mask form and to the plain reference's loop
(``benchmarks/reference/joyai.py``) for few and many experts, one token and
a thousand, under uneven routing, on both of its paths (``lax.ragged_dot``
and the ``moe_grouped`` kernel, interpret mode here), with no assignment
dropped by construction and no ``[T, E, I]`` temporary in its program."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.program_check import expert_temporaries
from dynamo_tpu.models.moe import grouped_experts

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
