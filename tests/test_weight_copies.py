"""A layer's weights are read where they lie: ``models/moe.flat_layers`` /
``layer_at`` (the one form of the three period families' forwards) and the
line of ``engine/program_check`` that holds a compiled program to it
(ISSUE 52). The real-size programs are read in
``tests/test_pallas_tpu_lowering.py``; here the helper and the check itself,
on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.program_check import weight_copies
from dynamo_tpu.models.moe import flat_layers, layer_at

P, G, H = 2, 3, 64


def _stack():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.standard_normal((P, G, H, H)), jnp.float32),
            "norm": jnp.asarray(rng.standard_normal((P, G, H)), jnp.float32)}


def _take_static(flat, p, j):
    return layer_at(flat, p * G + j)


def _take_traced(flat, p, j):
    return jax.jit(lambda f, i: layer_at(f, i))(flat, jnp.int32(p * G + j))


def _take_in_the_loops(flat, p, j):
    """The layer a nested scan over indices alone reads at ``(p, j)``."""
    def period(_, pi):
        def layer(_, ji):
            return None, layer_at(flat, pi * G + ji)
        return None, jax.lax.scan(layer, None, jnp.arange(G))[1]
    every = jax.lax.scan(period, None, jnp.arange(P))[1]
    return jax.tree_util.tree_map(lambda a: a[p, j], every)


@pytest.mark.parametrize("take", [_take_static, _take_traced,
                                  _take_in_the_loops])
def test_a_layer_of_the_flat_stack_is_the_layer_of_its_period_and_place(take):
    """``flat_layers`` lays ``[P, G, ...]`` out as ``[P * G, ...]`` leaf by
    leaf (the tree and the trailing shapes untouched) and ``layer_at`` at
    ``p * G + j`` - a Python int, a traced scalar, a scan's counter - is
    the stack's ``[p, j]``, bit for bit."""
    stack = _stack()
    flat = flat_layers(stack)
    assert {k: v.shape for k, v in flat.items()} == {
        "w": (P * G, H, H), "norm": (P * G, H)}
    for p in range(P):
        for j in range(G):
            got = take(flat, p, j)
            assert sorted(got) == ["norm", "w"]
            for k in got:
                np.testing.assert_array_equal(got[k], stack[k][p, j])


# ---- the check on a toy nested scan ---------------------------------------

def _scans_slices(params, x):
    """The old form: the periods' weights as the outer scan's ``xs``, each
    period's ``[G, ...]`` slice handed to the inner scan."""
    def period(h, xs):
        wp, up = xs

        def layer(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(layer, h, wp)
        return jnp.tanh(h @ up), None
    return jax.lax.scan(period, x, (params["w"], params["u"]))[0]


def _scans_indices(params, x):
    """The form the families run: both loops over indices alone."""
    flat = flat_layers(params["w"])

    def period(h, p):
        def layer(h, j):
            return jnp.tanh(h @ layer_at(flat, p * G + j)), None
        h, _ = jax.lax.scan(layer, h, jnp.arange(G))
        return jnp.tanh(h @ layer_at(params["u"], p)), None
    return jax.lax.scan(period, x, jnp.arange(P))[0]


W = 256
TOY = {"w": jax.ShapeDtypeStruct((P, G, W, W), jnp.float32),
       "u": jax.ShapeDtypeStruct((P, W, W), jnp.float32)}


def test_the_two_toy_forms_compute_the_same_thing():
    rng = np.random.default_rng(1)
    params = {k: jnp.asarray(rng.standard_normal(v.shape) / 16, v.dtype)
              for k, v in TOY.items()}
    x = jnp.asarray(rng.standard_normal((8, W)), jnp.float32)
    np.testing.assert_array_equal(jax.jit(_scans_slices)(params, x),
                                  jax.jit(_scans_indices)(params, x))


@pytest.mark.parametrize("forward,several", [(_scans_slices, True),
                                             (_scans_indices, False)])
def test_the_program_check_fails_a_forward_that_scans_a_periods_slices(
        forward, several):
    """Compiled, the old form writes a period's ``[G, W, W]`` slice of the
    stack inside the outer loop's body - the copy of ISSUE 52, 786,432
    bytes here - and the form over indices writes no more than the layer
    at hand. (On the CPU a ``dot`` is a call whose operand has to be a
    buffer, so ONE layer's slice is written in either form; on the chip
    the matmul reads the stack and that goes too:
    ``tests/test_pallas_tpu_lowering.py``.)"""
    x = jax.ShapeDtypeStruct((8, W), jnp.float32)
    hlo = jax.jit(forward).lower(TOY, x).compile().as_text()
    layer = W * W * 4
    found = weight_copies(hlo, TOY, min_bytes=layer)
    assert found["entry"] == [] and found["on_chip"] == []
    assert found["loop"], "a CPU dot reads a buffer: the layer's slice"
    wide = [d for d in found["loop"] if d["bytes"] > layer]
    if several:
        assert [d["bytes"] for d in wide] == [G * layer], found["loop"]
        assert "dynamic-slice" in wide[0]["line"]
    else:
        assert wide == [], wide


# ---- the check on a module written out by hand ----------------------------

_MODULE = """\
HloModule toy

%fused_slice (param_0.1: bf16[4,1024,2048], param_1.1: s32[]) -> bf16[2,1024,2048] {
  %param_0.1 = bf16[4,1024,2048]{2,1,0} parameter(0)
  %param_1.1 = s32[] parameter(1)
  %constant.1 = s32[] constant(0)
  %dynamic-slice.1 = bf16[2,1024,2048]{2,1,0} dynamic-slice(%param_0.1, %param_1.1, %constant.1, %constant.1), dynamic_slice_sizes={2,1024,2048}
  ROOT %bitcast.1 = bf16[2,1024,2048]{2,1,0} bitcast(%dynamic-slice.1)
}

%fused_dot (param_0.2: bf16[8,1024], param_1.2: bf16[4,1024,2048], param_2.2: s32[]) -> bf16[8,2048] {
  %param_0.2 = bf16[8,1024]{1,0} parameter(0)
  %param_1.2 = bf16[4,1024,2048]{2,1,0} parameter(1)
  %param_2.2 = s32[] parameter(2)
  %constant.2 = s32[] constant(0)
  %dynamic-slice.2 = bf16[1,1024,2048]{2,1,0} dynamic-slice(%param_1.2, %param_2.2, %constant.2, %constant.2), dynamic_slice_sizes={1,1024,2048}
  %bitcast.2 = bf16[1024,2048]{1,0} bitcast(%dynamic-slice.2)
  ROOT %convolution.2 = bf16[8,2048]{1,0} convolution(%param_0.2, %bitcast.2), dim_labels=bf_io->bf
}

%body (loop_state: (s32[], bf16[8,1024], bf16[4,1024,2048], bf16[1024,8192])) -> (s32[], bf16[8,1024], bf16[4,1024,2048], bf16[1024,8192]) {
  %loop_state = (s32[], bf16[8,1024]{1,0}, bf16[4,1024,2048]{2,1,0}, bf16[1024,8192]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%loop_state), index=0
  %h = bf16[8,1024]{1,0} get-tuple-element(%loop_state), index=1
  %w = bf16[4,1024,2048]{2,1,0} get-tuple-element(%loop_state), index=2
  %act = bf16[1024,8192]{1,0} get-tuple-element(%loop_state), index=3
  %a_periods_slice = bf16[2,1024,2048]{2,1,0} fusion(%w, %i), kind=kLoop, calls=%fused_slice
  %fetched_ahead = bf16[1,1024,2048]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%w, %i, %i, %i), dynamic_slice_sizes={1,1024,2048}
  %read_in_place = bf16[8,2048]{1,0} fusion(%h, %w, %i), kind=kOutput, calls=%fused_dot
  %an_activation = bf16[8192,1024]{1,0} transpose(%act), dimensions={1,0}
  %h2 = bf16[8,1024]{1,0} slice(%read_in_place), slice={[0:8], [0:1024]}
  ROOT %tuple.1 = (s32[], bf16[8,1024]{1,0}, bf16[4,1024,2048]{2,1,0}, bf16[1024,8192]{1,0}) tuple(%i, %h2, %w, %act)
}

%cond (loop_state.1: (s32[], bf16[8,1024], bf16[4,1024,2048], bf16[1024,8192])) -> pred[] {
  %loop_state.1 = (s32[], bf16[8,1024]{1,0}, bf16[4,1024,2048]{2,1,0}, bf16[1024,8192]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (params.w: bf16[4,1024,2048], x: bf16[8,1024], act: bf16[1024,8192]) -> bf16[8,1024] {
  %params.w = bf16[4,1024,2048]{2,1,0} parameter(0)
  %x = bf16[8,1024]{1,0} parameter(1)
  %act.1 = bf16[1024,8192]{1,0} parameter(2)
  %zero = s32[] constant(0)
  %hoisted_layout = bf16[4,1024,2048]{1,2,0} copy(%params.w)
  %tuple.2 = (s32[], bf16[8,1024]{1,0}, bf16[4,1024,2048]{2,1,0}, bf16[1024,8192]{1,0}) tuple(%zero, %x, %params.w, %act.1)
  %while.1 = (s32[], bf16[8,1024]{1,0}, bf16[4,1024,2048]{2,1,0}, bf16[1024,8192]{1,0}) while(%tuple.2), condition=%cond, body=%body
  ROOT %out = bf16[8,1024]{1,0} get-tuple-element(%while.1), index=1
}
"""


def test_a_weight_written_again_is_told_from_one_read_and_by_where_it_runs():
    """Of a module written out by hand: the fusion that slices two layers
    of the stack inside the loop's body is the loop's; the whole-stack
    transposing copy in the entry computation is the dispatch's; a layer
    fetched into the chip's fast memory (``S(1)``) is neither; the matmul
    that slices its layer inside its own fusion reads and writes no
    weight; and an activation of the stack's own element count
    (``[1024, 8192]`` against four layers of ``[1024, 2048]``), relaid in
    the same body, is not a weight at all - the operand chain decides,
    never the count."""
    params = {"w": jax.ShapeDtypeStruct((4, 1024, 2048), jnp.bfloat16)}
    found = weight_copies(_MODULE, params)

    def names(where):
        return [d["line"].split(" = ")[0] for d in found[where]]
    assert names("loop") == ["%a_periods_slice"]
    assert found["loop"][0]["bytes"] == 2 * 1024 * 2048 * 2
    assert found["loop"][0]["leaf"] == "%params.w"
    assert names("entry") == ["%hoisted_layout"]
    assert found["entry"][0]["bytes"] == 4 * 1024 * 2048 * 2
    assert names("on_chip") == ["%fetched_ahead"]
    # below a threshold of two layers' bytes the one-layer fetch is dropped
    assert weight_copies(_MODULE, params, 2 * 1024 * 2048 * 2)["on_chip"] == []
    # a tree whose leaf has another shape owns none of the parameters
    other = {"w": jax.ShapeDtypeStruct((4, 2048, 1024), jnp.bfloat16)}
    assert weight_copies(_MODULE, other) == {"loop": [], "entry": [],
                                             "on_chip": []}
