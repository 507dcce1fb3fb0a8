"""The cache write (``ops/attention.write_kv``, and ``write_kv_packed``
for a token-packed step).

Parity, bit for bit, of the page-at-a-time write against the plain
``.at[layer, phys, :, :, slot].set`` it replaced, at the first and the last
layer of the stacked pool (every other layer untouched), the three step
shapes, the GQA and MLA page geometries, both cache dtypes, and
page sizes the TPU's tile rows divide and do not (one path serves all);
the packed entry against the same scatter on the same rows laid back to
back. Then the guard on the compiled step programs
(``engine/program_check.py``): no pool-sized copy, no pool-sized
temporary.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import write_kv, write_kv_packed

L, LAYER = 3, 1
GEOMETRY = {"gqa": (8, 128), "mla": (1, 512)}      # Hkv, Dh


def _batch(step: str, ps: int):
    """(start positions, new_lens, S) of a padded batch of four rows."""
    if step == "decode":
        return [0, 3 * ps - 1, ps, 2 * ps + 1], [1, 1, 0, 1], 1
    if step == "prefill":       # ragged chunks, one of them resumed
        return [0, 2 * ps + 3, 0, ps], [24, 17, 1, 9], 24
    # mixed: prefill chunks beside decode rows and a dead row
    return [0, 9 * ps + 1, 5, 2 * ps], [24, 1, 0, 13], 24


def _old_write(pool, layer, k_new, v_new, table, positions, new_lens):
    """The scatter this PR replaced, as it stood."""
    ps = pool.shape[-2]
    S = positions.shape[1]
    phys = jnp.take_along_axis(table, positions // ps, axis=1)
    pad = jnp.arange(S)[None, :] >= new_lens[:, None]
    phys = jnp.where(pad, 0, phys)
    slot = jnp.where(pad, 0, positions % ps)
    new = jnp.stack([k_new, v_new], axis=2).astype(pool.dtype)
    return pool.at[layer, phys, :, :, slot].set(new, mode="drop")


def _pool_scatter_window(fn, *args) -> tuple:
    """Shape of the window that the write's scatter into the pool (the
    last one traced) updates per index."""
    eqn = [e for e in jax.make_jaxpr(fn)(*args).eqns
           if e.primitive.name == "scatter"][-1]
    operand = eqn.invars[0].aval.shape
    assert operand == args[0].shape
    inserted = eqn.params["dimension_numbers"].inserted_window_dims
    return tuple(n for d, n in enumerate(operand) if d not in inserted)


@pytest.mark.parametrize("ps", [16, 4])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("geometry", ["gqa", "mla"])
@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
@pytest.mark.parametrize("layer", [0, L - 1])
def test_write_matches_the_plain_scatter_bit_for_bit(layer, step,
                                                     geometry, dtype, ps):
    Hkv, Dh = GEOMETRY[geometry]
    B, P = 4, 16
    N = B * P + 3                    # the last two pages are in no table
    start, new_lens, S = _batch(step, ps)
    rng = np.random.default_rng(7)
    shape = (N, 2, Hkv, ps, Dh)
    pool = jnp.asarray(rng.standard_normal((L,) + shape), dtype)
    k_new = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), dtype)
    v_new = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, 1 + B * P))
                        .reshape(B, P), jnp.int32)
    positions = (jnp.asarray(start, jnp.int32)[:, None]
                 + jnp.arange(S, dtype=jnp.int32)[None, :])
    lens = jnp.asarray(new_lens, jnp.int32)

    new_fn = lambda *a: write_kv(a[0], layer, *a[1:])          # noqa: E731
    old_fn = lambda *a: _old_write(a[0], layer, *a[1:])        # noqa: E731
    args = (pool, k_new, v_new, table, positions, lens)
    # whole pages, the window that is contiguous in the page-major pool
    # and few enough to scatter; a token's window is neither
    assert _pool_scatter_window(new_fn, *args) == (2, Hkv, ps, Dh)
    assert _pool_scatter_window(old_fn, *args) == (2, Hkv, Dh)

    got = np.asarray(jax.jit(new_fn)(*args).astype(jnp.float32))
    want = np.asarray(jax.jit(old_fn)(*args).astype(jnp.float32))
    before = np.asarray(pool.astype(jnp.float32))
    # the other layers: untouched, their page 0 included
    np.testing.assert_array_equal(np.delete(got, layer, 0),
                                  np.delete(before, layer, 0))
    got, want, before = got[layer], want[layer], before[layer]
    # every page but the garbage page, bit for bit
    np.testing.assert_array_equal(got[1:], want[1:])
    # the real tokens landed where the table says (not merely where the
    # old scatter put them)
    kv = np.stack([np.asarray(k_new.astype(jnp.float32)),
                   np.asarray(v_new.astype(jnp.float32))], axis=2)
    tbl = np.asarray(table)
    touched = set()
    for b in range(B):
        for s in range(new_lens[b]):
            pos = start[b] + s
            page = tbl[b, pos // ps]
            touched.add(int(page))
            np.testing.assert_array_equal(got[page, :, :, pos % ps], kv[b, s])
    # pads may land on the garbage page 0 (the old scatter put them in its
    # slot 0) and nowhere else: every page no real token named is as it was
    np.testing.assert_array_equal(got[0, :, :, 1:], before[0, :, :, 1:])
    idle = [p for p in range(1, N) if p not in touched]
    assert {N - 1, N - 2} <= set(idle)
    np.testing.assert_array_equal(got[idle], before[idle])


def _packed_rows(ps: int):
    """(start positions, new_lens) of five rows of a packed step: a fresh
    chunk, a decode row deep in its context, a dead row, a resumed chunk
    that ends off a page boundary, and two tokens across a page edge."""
    return [0, 9 * ps + 1, 5, 2 * ps, 4 * ps - 1], [24, 1, 0, 13, 2]


@pytest.mark.parametrize("ps", [16, 4])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("geometry", ["gqa", "mla"])
def test_packed_write_matches_the_plain_scatter_bit_for_bit(geometry, dtype,
                                                            ps):
    """The same rows with their tokens back to back on one ``[T]`` axis
    (pads behind them): the pool ends as the plain per-token scatter of
    each row leaves it, page 0 and every page no token named untouched,
    and the scatter still takes whole pages."""
    Hkv, Dh = GEOMETRY[geometry]
    start, new_lens = _packed_rows(ps)
    R, P = len(start), 16
    N = R * P + 3
    T = sum(new_lens) + 7
    rng = np.random.default_rng(11)
    shape = (N, 2, Hkv, ps, Dh)
    pool = jnp.asarray(rng.standard_normal((L,) + shape), dtype)
    k_new = jnp.asarray(rng.standard_normal((T, Hkv, Dh)), dtype)
    v_new = jnp.asarray(rng.standard_normal((T, Hkv, Dh)), dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, 1 + R * P))
                        .reshape(R, P), jnp.int32)
    lens = jnp.asarray(new_lens, jnp.int32)
    cu = jnp.cumsum(lens) - lens
    # a dead row reads total 1, new 0, as the engine's pad rows do
    total = jnp.where(lens > 0, jnp.asarray(start, jnp.int32) + lens, 1)
    fn = lambda *a: write_kv_packed(a[0], LAYER, *a[1:])       # noqa: E731
    args = (pool, k_new, v_new, table, cu, lens, total)
    assert _pool_scatter_window(fn, *args) == (2, Hkv, ps, Dh)
    got = jax.jit(fn)(*args)
    # the oracle: each row alone through the plain scatter
    want = pool
    for r in range(R):
        n, s0 = new_lens[r], int(cu[r])
        if n:
            pos = jnp.arange(start[r], start[r] + n, dtype=jnp.int32)[None]
            want = _old_write(want, LAYER, k_new[None, s0:s0 + n],
                              v_new[None, s0:s0 + n], table[r:r + 1], pos,
                              jnp.asarray([n], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    # pads land nowhere: page 0 is as it was, in every layer
    np.testing.assert_array_equal(
        np.asarray(got[:, 0].astype(jnp.float32)),
        np.asarray(pool[:, 0].astype(jnp.float32)))


def test_pool_copies_reads_an_optimised_hlo_text():
    """The reader on a hand-written module: a ``copy`` of the pool, a copy
    of its 2-D view and a fusion that ends in one are listed; the in-place
    scatter, its fusion, bitcasts and other dtypes and sizes are not."""
    from dynamo_tpu.engine.program_check import pool_copies

    hlo = """
%fused_inplace (p0: bf16[96,128]) -> bf16[96,128] {
  %p0 = bf16[96,128]{1,0} parameter(0)
  ROOT %scatter.1 = bf16[96,128]{1,0} scatter(%p0, %i, %u), to_apply=%r
}

%fused_copy (p0: bf16[2,3,16,128]) -> bf16[2,3,16,128] {
  %p0 = bf16[2,3,16,128]{3,2,1,0} parameter(0)
  ROOT %copy.9 = bf16[2,3,16,128]{3,1,2,0:T(8,128)(2,1)} copy(%p0)
}

ENTRY %main (pages: bf16[2,3,16,128]) -> bf16[2,3,16,128] {
  %pages = bf16[2,3,16,128]{3,2,1,0} parameter(0)
  %bitcast.1 = bf16[96,128]{1,0} bitcast(%pages)
  %fusion.1 = bf16[96,128]{1,0} fusion(%bitcast.1), kind=kCustom, calls=%fused_inplace
  %copy.1 = bf16[2,3,16,128]{3,1,2,0:T(8,128)(2,1)} copy(%pages)
  %copy.2 = bf16[96,128]{0,1} copy(%fusion.1)
  %fusion.2 = bf16[2,3,16,128]{3,1,2,0} fusion(%pages), kind=kLoop, calls=%fused_copy
  %copy.3 = f32[2,3,16,128]{3,2,1,0} copy(%other)
  %copy.4 = bf16[3,16,128]{2,1,0} copy(%layer)
  ROOT %bitcast.2 = bf16[2,3,16,128]{3,2,1,0} bitcast(%fusion.1)
}
"""
    found = pool_copies(hlo, (2, 3, 16, 128), jnp.bfloat16)
    assert [line.split(" = ")[0] for line in found] == [
        "ROOT %copy.9", "%copy.1", "%copy.2", "%fusion.2"]


def _toy_engine(attn_impl: str = "scan"):
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    # float32: XLA's CPU backend widens a bf16 scatter's operand to f32 and
    # back, a pool-sized convert of its own that no chip runs
    cfg = ModelConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      head_dim=128, dtype="float32")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    # a pool (16.8 MB) that dwarfs every other temporary of a toy step
    return JaxEngine(cfg, params, JaxEngineConfig(
        num_pages=512, page_size=8, max_num_seqs=4, max_prefill_chunk=64,
        max_context=256, attn_impl=attn_impl))


@pytest.fixture(scope="module")
def toy_reports():
    from dynamo_tpu.engine.program_check import check_step_programs

    return {r["program"]: r
            for r in check_step_programs(_toy_engine(), batch=4, chunk=32,
                                         width=4)}


@pytest.fixture(scope="module")
def toy_reports_packing():
    """The same engine on the kernels (interpreted here): it serves its
    prefill-carrying steps token-packed, so the check has a fourth
    program."""
    from dynamo_tpu.engine.program_check import check_step_programs

    return {r["program"]: r
            for r in check_step_programs(_toy_engine("pallas"), batch=4,
                                         chunk=32, width=4, tokens=64)}


@pytest.mark.parametrize("program", ["decode", "fused", "mixed", "packed"])
def test_a_packing_engines_programs_hold_no_pool_sized_copy(
        toy_reports_packing, program):
    """The packed program beside the three others of an engine that packs:
    the packed cache write gathers and scatters whole pages of the donated
    pool in place, like the padded one."""
    assert list(toy_reports_packing) == ["decode", "fused", "mixed",
                                         "packed"]
    r = toy_reports_packing[program]
    assert r["pool_copies"] == []
    assert r["temp_bytes"] < r["pool_bytes"], r
    assert r["ok"]


def test_an_engine_that_does_not_pack_has_no_packed_program(toy_reports):
    assert list(toy_reports) == ["decode", "fused", "mixed"]


@pytest.mark.parametrize("program", ["decode", "fused", "mixed"])
def test_compiled_step_programs_hold_no_pool_sized_copy(toy_reports,
                                                        program):
    """The toy model's step programs, compiled for the CPU from shapes: a
    write that makes the compiler re-lay or copy the pool (the window
    scatter this PR replaced does, here as on the chip; so would a
    ``pages[layer_idx]`` slice written back) shows as a pool-sized
    instruction or temporary."""
    r = toy_reports[program]
    assert r["pool_bytes"] == 2 * 512 * 2 * 2 * 8 * 128 * 4
    assert r["pool_copies"] == []
    assert r["temp_bytes"] < r["pool_bytes"], r
    assert r["ok"]


def test_the_guard_catches_the_window_scatter(monkeypatch):
    """The same engine with the replaced scatter put back: the guard must
    say so, or it guards nothing."""
    from dynamo_tpu.engine.program_check import check_step_programs
    from dynamo_tpu.models import llama

    monkeypatch.setattr(llama, "write_kv", _old_write)
    reports = check_step_programs(_toy_engine(), batch=4, chunk=32, width=4)
    assert [r["program"] for r in reports] == ["decode", "fused", "mixed"]
    for r in reports:
        assert not r["ok"], r["program"]
        assert r["pool_copies"] or r["temp_bytes"] >= r["pool_bytes"]
