"""Which kernel attends which rows of a token-packed step (ISSUE 40).

``ops/pallas/ragged.ragged_mixed_attention_packed`` hands the trailing run
of one-token rows to the decode kernel (``paged_decode``) and runs the
ragged kernel (``ragged_mixed``) over the rows of several tokens. Under
test, on the CPU with both kernels interpreted:

- the op against the oracle ``ops.attention.ragged_paged_attention`` over
  layouts with only one-token rows, none, the batch cell's step (8 chunks
  and 42 one-token rows on 1,152 slots), a one-token chunk among the chunk
  rows, pad rows behind the decode rows, contexts that end on a page
  boundary, a window layer, softcap, a visibility block, and windows of
  ``R`` slots that overhang either end of the packed axis;
- the rows the decode kernel takes, by the rule the op and the engine's
  counter share;
- a visibility ``block`` > 1 traces to the program it always was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import ragged_paged_attention
from dynamo_tpu.ops.pallas import ragged
from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

PS, Hkv, Hq, Dh, L = 8, 2, 4, 128, 2

# name -> (slots T, [(context before the new tokens, new tokens)] per row,
#          kwargs of the op, rows the decode kernel takes)
LAYOUTS = {
    "only_one_token_rows": (
        64, [(9, 1), (30, 1), (0, 1), (63, 1)], {}, [0, 1, 2, 3]),
    "no_one_token_row": (
        128, [(0, 40), (16, 50), (3, 2)], {}, []),
    # the batch cell's step: 8 prompt chunks, 42 decode rows, 14 pad rows
    "batch_cell_step": (
        1152, [(0, 127)] * 4 + [(40, 128)] * 4
        + [(20 + 3 * i, 1) for i in range(42)] + [(0, 0)] * 14, {},
        list(range(8, 50))),
    "one_token_chunk_among_the_chunks": (
        128, [(0, 20), (7, 1), (5, 30), (11, 1), (50, 1)], {}, [3, 4]),
    "pad_rows_behind_the_decode_rows": (
        128, [(0, 33), (12, 1), (40, 1), (0, 0), (0, 0), (0, 0)], {},
        [1, 2]),
    "context_ends_on_a_page_boundary": (
        128, [(PS, 2 * PS), (PS - 1, 1), (4 * PS - 1, 1), (8 * PS - 1, 1)],
        {}, [1, 2, 3]),
    "window_layer": (
        128, [(10, 25), (70, 1), (3, 1), (29, 1)], {"window": 6},
        [1, 2, 3]),
    "softcap": (
        128, [(10, 25), (70, 1), (3, 1)], {"softcap": 30.0}, [1, 2]),
    "window_and_softcap": (
        128, [(0, 9), (70, 1), (17, 1)], {"window": 17, "softcap": 50.0},
        [1, 2]),
    # generation by diffusion over blocks: rows are whole blocks of 4 (a
    # slot sees to its block's end), the ragged kernel takes every row
    "visibility_block": (
        128, [(8, 24), (12, 4), (20, 4), (40, 8)], {"block": 4}, []),
    # 8 rows, 16 slots, 14 of them the chunk's: the 8 slots from the first
    # decode row's on overhang the axis' end
    "rows_window_past_the_end": (
        16, [(0, 14), (9, 1), (30, 1)] + [(0, 0)] * 5, {}, [1, 2]),
    # rows without tokens ahead of the chunk: the window would start
    # before slot 0
    "rows_window_before_the_start": (
        16, [(0, 0), (0, 0), (0, 0), (4, 2), (9, 1), (30, 1)], {}, [4, 5]),
    # a one-token row off the line (a pad row between two): it stays with
    # the ragged kernel
    "one_token_row_off_the_line": (
        64, [(0, 5), (9, 1), (0, 0), (30, 1)], {}, [1]),
    # more rows than slots: no window of R slots, the ragged kernel alone
    "more_rows_than_slots": (
        8, [(0, 3)] + [(5 + i, 1) for i in range(4)] + [(0, 0)] * 11, {},
        None),
}


def _arrays(name):
    T, rows, kw, _ = LAYOUTS[name]
    rng = np.random.default_rng(len(name))
    R = len(rows)
    q_lens = np.asarray([n for _c, n in rows], np.int32)
    kv_lens = np.asarray([c + n if n else 1 for c, n in rows], np.int32)
    q_starts = (np.cumsum(q_lens) - q_lens).astype(np.int32)
    P = -(-int(kv_lens.max()) // PS) + 1
    N = R * P + 1
    table = rng.permutation(np.arange(1, N)).reshape(R, P).astype(np.int32)
    pages = jnp.asarray(rng.normal(size=(L, N, 2, Hkv, PS, Dh))
                        .astype(np.float32)).astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(T, Hq, Dh))
                    .astype(np.float32)).astype(jnp.bfloat16)
    return (q, pages, 1, jnp.asarray(table), jnp.asarray(q_starts),
            jnp.asarray(q_lens), jnp.asarray(kv_lens), 0.09), kw


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_packed_attention_matches_the_oracle(name):
    args, kw = _arrays(name)
    got = ragged_mixed_attention_packed(*args, interpret=True, **kw)
    ref_kw = dict(kw)
    if "window" in kw:
        ref_kw["window"] = jnp.asarray(kw["window"])
    want = ragged_paged_attention(*args, **ref_kw)
    q, n = args[0], int(args[5].sum())
    assert got.shape == q.shape and got.dtype == q.dtype
    err = float(jnp.max(jnp.abs(got[:n].astype(jnp.float32)
                                - want[:n].astype(jnp.float32))))
    assert err < 0.05, err
    # every real slot attended something, and slots of no row read zero
    assert float(jnp.abs(got[:n].astype(jnp.float32))
                 .max(axis=(1, 2)).min()) > 0
    assert float(jnp.max(jnp.abs(got[n:].astype(jnp.float32)))) == 0.0 \
        if n < q.shape[0] else True


@pytest.mark.parametrize("name", sorted(
    n for n, v in LAYOUTS.items() if v[3] is not None and not v[2].get(
        "block")))
def test_the_rows_the_decode_kernel_takes(name):
    args, _kw = _arrays(name)
    mask, first = ragged._decode_rows(args[4], args[5])
    took = np.flatnonzero(np.asarray(mask)).tolist()
    assert took == LAYOUTS[name][3]
    # row r of them sits at slot first + r
    for r in took:
        assert int(args[4][r]) == int(first) + r


def _program(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def test_a_visibility_block_traces_to_the_ragged_kernel_alone():
    """``block`` > 1 (generation by diffusion over blocks) keeps the
    program the packed step had before the decode kernel entered it: the
    jaxpr is that of the ragged kernel's own call, and no ``paged_decode``
    is in it; at ``block`` 1 both kernels are."""
    (q, pages, _lay, table, starts, q_lens, kv_lens, sm), _ = _arrays(
        "visibility_block")

    def op(block):
        return lambda *a: ragged_mixed_attention_packed(
            a[0], a[1], 1, *a[2:], sm, interpret=True, block=block)

    def before(q, pages, table, starts, q_lens, kv_lens):
        return ragged._ragged_mixed(
            q, pages, jnp.asarray(1, jnp.int32).reshape(1),
            jnp.zeros((1,), jnp.int32), table.astype(jnp.int32),
            starts.astype(jnp.int32), q_lens.astype(jnp.int32),
            kv_lens.astype(jnp.int32), sm, softcap=0.0, interpret=True,
            block=4)

    a = (q, pages, table, starts, q_lens, kv_lens)
    blockwise = _program(op(4), *a)
    assert blockwise == _program(before, *a)
    assert "paged_decode" not in blockwise and "ragged_mixed" in blockwise
    causal = _program(op(1), *a)
    assert "paged_decode" in causal and "ragged_mixed" in causal
    assert not ragged.takes_decode_kernel(4)
    assert ragged.takes_decode_kernel(1)
