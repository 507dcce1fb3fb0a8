"""The token-packed prefill-carrying step (ISSUE 28).

A ``MixedStepBatch`` or a non-ring ``PrefillBatch`` runs as ONE ``[T]``
program: every row's new tokens back to back (chunk rows, then decode
rows, pads behind them), the rows as descriptors ``[R]``. Under test:

- every family that declares the packed form (``forward.supports_packed``:
  llama/qwen, the llama-attention MoE, gemma, MLA with and without a
  compressed query) gives the same logits rows and the same pool contents
  packed as padded, on plans with and without a cached prefix, chunk ends
  off a page boundary, decode rows, pad rows and a single row;
- the ragged MLA kernel (``ops/pallas/mla_ragged.py``, interpreted) gives
  what its pure-JAX reference gives on the same plans, over one block of
  slots and over several;
- the engine packs where it can tell it may and serves padded everywhere
  else, and says which and why
  (``dynamo_worker_prefill_steps_total{form}``);
- the ring names the packed program and counts the slots it paid for.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import (
    JaxEngine,
    JaxEngineConfig,
    _token_bucket,
)
from dynamo_tpu.models import deepseek, gemma, llama, moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

PS, P, N = 8, 8, 48          # page size, table width, pool pages

# (start position, new tokens) per row; a start > 0 is a cached prefix (or
# a decode row's context), 0 new tokens a pad row
PLANS = {
    # a resumed chunk ending off a page boundary, a fresh chunk, two
    # decode rows (one on a page's first slot), a pad row
    "mixed": [(2 * PS + 3, 11), (0, 13), (5, 1), (3 * PS, 1), (0, 0)],
    # prefill only: three chunks, one of them a whole number of pages
    "prefill": [(0, 2 * PS), (PS, 5), (0, 7), (0, 0)],
    # one row alone (R = 1): a chunk behind a cached prefix
    "single": [(PS + 1, 9)],
}

# MLA: latent attention over its own 2-slot cache [L, N, 2, 1, ps, dkv],
# one dense layer then mixture layers with a shared expert
MLA = dict(model_type="deepseek_v2", num_layers=3, num_heads=2,
           num_kv_heads=1, head_dim=32, kv_lora_rank=32,
           qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
           num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
           n_shared_experts=1, first_k_dense_replace=1,
           routed_scaling_factor=1.0)

FAMILIES = {
    "llama": (llama, dict()),
    "qwen3": (llama, dict(qk_norm=True)),
    "moe": (moe, dict(model_type="mixtral", num_experts=4,
                      num_experts_per_tok=2, moe_intermediate_size=32)),
    "gemma": (gemma, dict(model_type="gemma2", sliding_window=6,
                          attn_logit_softcap=30.0,
                          final_logit_softcap=20.0)),
    "mla": (deepseek, dict(MLA, q_lora_rank=0)),
    "mla_q_lora": (deepseek, dict(MLA, q_lora_rank=24)),
}


def _cfg(**kw):
    base = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _plan_arrays(plan, seed=0):
    """The padded and the packed arrays of one plan, and a pool whose
    prefix pages already hold something."""
    rng = np.random.default_rng(seed)
    R = len(plan)
    S = max(8, max(n for _s, n in plan))
    n_tok = sum(n for _s, n in plan)
    T = _token_bucket(n_tok, 4)
    table = rng.permutation(np.arange(1, 1 + R * P)).reshape(R, P) \
        .astype(np.int32)
    toks = np.zeros((R, S), np.int32)
    pos = np.zeros((R, S), np.int32)
    ptoks = np.zeros((1, T), np.int32)
    ppos = np.zeros((1, T), np.int32)
    total = np.ones(R, np.int32)
    new = np.zeros(R, np.int32)
    at = 0
    for r, (start, n) in enumerate(plan):
        ids = rng.integers(1, 90, size=n)
        toks[r, :n] = ptoks[0, at:at + n] = ids
        pos[r, :n] = ppos[0, at:at + n] = np.arange(start, start + n)
        if n:
            total[r], new[r] = start + n, n
        at += n
    return dict(padded=(jnp.asarray(toks), jnp.asarray(pos)),
                packed=(jnp.asarray(ptoks), jnp.asarray(ppos)),
                table=jnp.asarray(table), total=jnp.asarray(total),
                new=jnp.asarray(new))


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_packed_forward_matches_padded(family, plan):
    mod, extra = FAMILIES[family]
    cfg = _cfg(**extra)
    assert mod.forward.supports_packed
    params = mod.init_params(cfg, jax.random.PRNGKey(1))
    pages = jax.random.normal(
        jax.random.PRNGKey(2),
        (cfg.num_layers, N, 2, cfg.num_kv_heads, PS, cfg.head_dim))
    a = _plan_arrays(PLANS[plan])
    rows = (a["table"], a["total"], a["new"])
    want = mod.forward(params, cfg, *a["padded"], pages, *rows)
    got = mod.forward(params, cfg, *a["packed"], pages, *rows, packed=True)
    real = np.asarray(a["new"]) > 0
    # the same rows in the same order, [R, V], and the same expert counts
    assert got[0].shape == want[0].shape
    assert len(got) == len(want)
    for k, v in (want[2] if len(want) > 2 else {}).items():
        assert int(got[2][k]) == int(v), k
    np.testing.assert_allclose(np.asarray(got[0])[real],
                               np.asarray(want[0])[real],
                               rtol=2e-4, atol=2e-4)
    # the same pool: every page a real token named, and no other (pads
    # land nowhere; page 0 is the padded form's to scribble on, not ours)
    np.testing.assert_allclose(np.asarray(got[1])[:, 1:],
                               np.asarray(want[1])[:, 1:],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[1])[:, 0],
                                  np.asarray(pages)[:, 0])


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("family", ["llama", "gemma"])
def test_packed_forward_on_the_kernel_matches_padded(family, plan):
    """The same, with the Pallas packed kernel (interpreted) as the packed
    step's attention and the XLA path under the padded one; gemma hands
    the kernel its per-layer window and its softcap."""
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    mod, extra = FAMILIES[family]
    cfg = _cfg(head_dim=128, num_heads=2, num_kv_heads=1, hidden_size=128,
               **extra)
    params = mod.init_params(cfg, jax.random.PRNGKey(1))
    pages = jax.random.normal(
        jax.random.PRNGKey(2), (cfg.num_layers, N, 2, 1, PS, 128))
    a = _plan_arrays(PLANS[plan], seed=5)
    rows = (a["table"], a["total"], a["new"])
    want = mod.forward(params, cfg, *a["padded"], pages, *rows)
    got = mod.forward(params, cfg, *a["packed"], pages, *rows,
                      attn_impl=ragged_mixed_attention_packed, packed=True)
    real = np.asarray(a["new"]) > 0
    np.testing.assert_allclose(np.asarray(got[0])[real],
                               np.asarray(want[0])[real],
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(got[1])[:, 1:],
                               np.asarray(want[1])[:, 1:],
                               rtol=5e-3, atol=5e-3)


def _latent_plan(plan):
    """A plan's packed row descriptors, latent queries for its slots and a
    latent pool (slot 1 = the rope key zero-padded to the latent width)."""
    nh, dkv, dr = 4, 128, 16
    a = _plan_arrays(PLANS[plan], seed=3)
    T = a["packed"][0].shape[1]
    kq, kr, kp = jax.random.split(jax.random.PRNGKey(3), 3)
    pool = jax.random.normal(kp, (2, N, 2, 1, PS, dkv))
    pool = pool.at[:, :, 1, :, :, dr:].set(0.0)
    cfg = _cfg(**dict(MLA, num_heads=nh, kv_lora_rank=dkv, head_dim=dkv,
                      qk_rope_head_dim=dr))
    starts = jnp.cumsum(a["new"]) - a["new"]
    return (cfg, jax.random.normal(kq, (T, nh, dkv)),
            jax.random.normal(kr, (T, nh, dr)), pool,
            (a["table"], starts, a["new"], a["total"]))


@pytest.mark.parametrize("query_block", [None, 8])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_mla_ragged_kernel_matches_its_reference(plan, query_block,
                                                 monkeypatch):
    """``mla_ragged`` (interpreted) against the pure-JAX latent attention
    over the same packed layout: chunk from 0, continued chunk, cached
    prefix, decode rows, pad and empty rows; slots of no row read zero.
    ``query_block`` 8 cuts the plan's slots into several blocks, so rows
    start and end inside a block and a block holds several rows."""
    from dynamo_tpu.ops.pallas import mla_ragged

    if query_block:
        monkeypatch.setattr(mla_ragged, "_query_block",
                            lambda *a: query_block)
    mla_ragged._mla_ragged.clear_cache()
    cfg, q_lat, q_pe, pool, rows = _latent_plan(plan)
    want = deepseek.mla_ragged_attention(cfg, q_lat, q_pe, pool, 1, *rows)
    got = mla_ragged.mla_ragged_attention_packed(
        q_lat, q_pe, pool, 1, *rows, deepseek._mla_scale(cfg),
        interpret=True)
    mla_ragged._mla_ragged.clear_cache()
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    n_real = int(rows[2].sum())
    assert float(jnp.abs(want[:n_real]).min(axis=(1, 2)).max()) > 0
    np.testing.assert_array_equal(np.asarray(got)[n_real:], 0.0)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_packed_mla_forward_on_the_kernel_matches_padded(plan):
    """The MLA forward, packed over ``mla_ragged`` (interpreted; the
    marker of any stacked kernel opts the family into its own), against
    the padded form on the XLA path."""
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    cfg = _cfg(**dict(MLA, kv_lora_rank=128, head_dim=128, q_lora_rank=24))
    params = deepseek.init_params(cfg, jax.random.PRNGKey(1))
    pages = jax.random.normal(jax.random.PRNGKey(2),
                              (cfg.num_layers, N, 2, 1, PS, 128))
    pages = pages.at[:, :, 1, :, :, cfg.qk_rope_head_dim:].set(0.0)
    a = _plan_arrays(PLANS[plan], seed=5)
    rows = (a["table"], a["total"], a["new"])
    want = deepseek.forward(params, cfg, *a["padded"], pages, *rows)
    got = deepseek.forward(params, cfg, *a["packed"], pages, *rows,
                           attn_impl=ragged_mixed_attention_packed,
                           packed=True)
    real = np.asarray(a["new"]) > 0
    np.testing.assert_allclose(np.asarray(got[0])[real],
                               np.asarray(want[0])[real],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got[1])[:, 1:],
                               np.asarray(want[1])[:, 1:],
                               rtol=2e-5, atol=2e-5)


def test_mla_declares_the_packed_form():
    assert deepseek.forward.supports_packed


@pytest.mark.parametrize("n,want", [
    (1, 16), (16, 16), (17, 32), (300, 512), (512, 512), (513, 1152),
    (1024 + 22, 1152), (1024 + 64, 1152)])
def test_the_token_ladder(n, want):
    """Powers of two from the floor to 512, then one rung, the step's cap:
    1,152 for the worker's 1,024-token budget beside its decode rows."""
    assert _token_bucket(n, 16, 1152) == want


def test_the_token_ladder_under_a_pinned_floor_and_a_small_cap():
    """A floor above 512 pins the axis (one program, steps of 128 above
    it, whatever the cap); a cap under 512 never shortens a step."""
    assert [_token_bucket(n, 1024, 1152) for n in (1, 600, 1024, 1025)] \
        == [1024, 1024, 1024, 1152]
    assert [_token_bucket(n, 16, 128) for n in (100, 520)] == [128, 640]


# -- the engine: which form, and why --------------------------------------


def _kernel_cfg():
    # the Pallas kernels' geometry (head_dim % 128, page_size % 8)
    return ModelConfig.tiny(head_dim=128, num_heads=2, num_kv_heads=1,
                            hidden_size=128)


def _engine(cfg=None, forward_fn=None, **kw):
    cfg = cfg or _kernel_cfg()
    defaults = dict(num_pages=64, page_size=8, max_num_seqs=4,
                    max_prefill_chunk=16, max_context=128,
                    min_prefill_bucket=4, decode_multistep=4,
                    attn_impl="pallas")
    defaults.update(kw)
    if forward_fn is not None:
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return JaxEngine(cfg, params, JaxEngineConfig(**defaults),
                         forward_fn=forward_fn)
    return JaxEngine.random_init(cfg, JaxEngineConfig(**defaults))


def _dp_mesh():
    from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh
    return make_mesh(MeshSpec(dp=2), devices=jax.devices()[:2])


def _plain_forward(params, cfg, tokens, positions, pages, page_table,
                   total_lens, new_lens, attn_impl=None):
    """A custom forward_fn (what a pipeline stage body is to the engine)."""
    return llama.forward(params, cfg, tokens, positions, pages, page_table,
                         total_lens, new_lens, attn_impl=attn_impl)


def _mla_cfg(**kw):
    return _cfg(**dict(MLA, vocab_size=128, num_layers=2, q_lora_rank=0,
                       **kw))


def _unmarked_family(cfg):
    """A family whose forward does not declare the packed form (what MLA
    was until ISSUE 38): llama behind a forward without the marker."""
    import types

    def forward(params, cfg, tokens, positions, pages, page_table,
                total_lens, new_lens, attn_impl=None, logits_window=1):
        return llama.forward(params, cfg, tokens, positions, pages,
                             page_table, total_lens, new_lens,
                             attn_impl=attn_impl,
                             logits_window=logits_window)

    return types.SimpleNamespace(forward=forward,
                                 init_params=llama.init_params)


@pytest.mark.parametrize("case,reason", [
    ("kernels", None), ("spec", "spec"), ("dp", "dp"),
    ("forward_fn", "forward"), ("family", "family"),
    ("scan", "attn_impl"), ("mla", None), ("mla_scan", "attn_impl")])
def test_engine_picks_the_form_from_what_it_is(case, reason, monkeypatch):
    kw = {
        "kernels": dict(),
        "spec": dict(spec_tokens=2),
        "dp": dict(attn_impl="scan", mesh=_dp_mesh()) if case == "dp"
        else {},
        "forward_fn": dict(forward_fn=_plain_forward),
        "family": dict(),
        "scan": dict(attn_impl="scan"),
        "mla": dict(cfg=_mla_cfg(kv_lora_rank=128, head_dim=128)),
        "mla_scan": dict(cfg=_mla_cfg(), attn_impl="scan", page_size=4),
    }[case]
    if case == "family":
        import dynamo_tpu.models as models
        monkeypatch.setattr(models, "get_family", _unmarked_family)
    eng = _engine(**kw)
    assert eng.padded_reason == reason
    assert (eng._jit_packed is None) == (reason is not None)
    # a form the collector knows, so the scrape shows it before any step
    from dynamo_tpu.worker.metrics import EngineDispatchCollector
    form = "packed" if reason is None else f"padded:{reason}"
    assert form in EngineDispatchCollector.PREFILL_FORMS
    assert eng.prefill_steps == {}


def _req(tokens, rid, max_tokens=6):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[])


async def _serve(eng, reqs):
    """Two requests at once, the third once tokens flow: prefill steps
    and mixed steps both happen."""
    started = asyncio.Event()

    async def one(r, wait):
        if wait:
            await started.wait()
        out = []
        async for f in eng.generate(r):
            out += f.token_ids
            started.set()
        return out

    try:
        return await asyncio.gather(one(reqs[0], False), one(reqs[1], False),
                                    one(reqs[2], True))
    finally:
        await eng.stop()


REQS = [(range(1, 30), "a", 12), ([5, 6, 7], "b", 9), (range(40, 61), "c", 7)]


@pytest.mark.async_timeout(240)
@pytest.mark.parametrize("family", ["llama", "mla"])
async def test_packed_engine_streams_what_the_padded_engine_streams(family):
    """The engine on the kernels (interpreted) serves packed — the Llama
    tree through ``ragged_mixed``, MLA through ``mla_ragged`` — the XLA
    scan engine padded; greedy streams are the same tokens, and each
    counts its prefill-carrying steps under its own form."""
    cfg = (_mla_cfg(kv_lora_rank=128, head_dim=128) if family == "mla"
           else None)
    packed = _engine(cfg=cfg)
    got = await _serve(packed, [_req(*r) for r in REQS])
    padded = _engine(cfg=cfg, attn_impl="scan")
    want = await _serve(padded, [_req(*r) for r in REQS])
    assert got == want
    assert [len(t) for t in got] == [12, 9, 7]
    assert packed.prefill_steps["packed"] > 0 and packed.mixed_steps > 0
    assert set(packed.prefill_steps) == {"packed"}
    assert padded.prefill_steps["padded:attn_impl"] > 0
    assert set(padded.prefill_steps) == {"padded:attn_impl"}


async def test_spec_engine_counts_padded_spec():
    eng = _engine(spec_tokens=2)
    await _serve(eng, [_req(*r) for r in REQS])
    assert eng.prefill_steps["padded:spec"] > 0
    assert set(eng.prefill_steps) == {"padded:spec"}


def test_ring_plan_is_padded_whatever_the_engine(monkeypatch):
    """A sequence-parallel whole-prompt step keeps its own program: the
    form is the plan's, counted as ``padded:ring``."""
    from dynamo_tpu.engine.scheduler import PrefillBatch, PrefillChunk

    eng = _engine()
    assert eng.padded_reason is None
    seen = {}

    def fake_invoke(kind, arrays, step, **kw):
        seen["kind"], seen["toks"] = kind, arrays["toks"].shape
        raise RuntimeError("stop here")

    monkeypatch.setattr(eng, "_invoke_step", fake_invoke)

    class Toks:
        def tokens(self):
            return list(range(1, 12))

    class Seq:
        tokens, page_ids = Toks(), [1, 2]
        request = _req(range(1, 12), "ring")

        def __len__(self):
            return 11

    seq = Seq()
    chunk = PrefillChunk(seq=seq, start=0, length=11, is_last=True)
    plan = PrefillBatch(chunks=[chunk], ring=True)
    with pytest.raises(RuntimeError, match="stop here"):
        eng._execute_plan(plan)
    assert seen["kind"] == "ring" and seen["toks"] == (1, 16)
    assert eng.prefill_steps["padded:ring"] == 1
    plan = PrefillBatch(chunks=[chunk], ring=False)
    with pytest.raises(RuntimeError, match="stop here"):
        eng._execute_plan(plan)
    assert seen["kind"] == "packed" and seen["toks"] == (1, 16)
    assert eng.prefill_steps["packed"] == 1


def test_ring_and_program_name_of_a_packed_step():
    """``last_padded`` is ``(1, T)`` and the ring's program reads
    ``packed[T,R]``: the slots the device computed, whatever the rows."""
    eng = _engine()
    R, T = 4, 32
    arrays = {"toks": np.zeros((1, T), np.int32),
              "pos": np.zeros((1, T), np.int32),
              "table": np.zeros((R, eng.table_width), np.int32),
              "total": np.asarray([9, 3, 1, 1], np.int32),
              "new": np.asarray([9, 1, 0, 0], np.int32),
              "temp": np.zeros(R, np.float32),
              "top_k": np.zeros(R, np.int32),
              "top_p": np.ones(R, np.float32)}
    arrays["pos"][0, :9] = np.arange(9)
    arrays["pos"][0, 9] = 2
    arrays["table"][0, :2] = [1, 2]
    arrays["table"][1, 0] = 3
    sampled, _lps, _extras = eng.execute_arrays("packed", arrays, 0)
    assert sampled.shape == (R,)
    assert eng.last_padded == (1, T)
    assert eng.last_program == f"packed[{T},{R}]"
    (ev,) = eng.drain_compile_events()
    assert (ev["kind"], ev["batch"], ev["width"]) == ("packed", R, T)


def test_metrics_render_every_form_pre_seeded():
    from prometheus_client import CollectorRegistry, generate_latest

    from dynamo_tpu.worker.metrics import (EngineDispatchCollector,
                                           engine_dispatch_stats)
    assert "padded:ring" in EngineDispatchCollector.PREFILL_FORMS
    eng = _engine(attn_impl="scan")
    eng.prefill_steps["padded:attn_impl"] = 3
    reg = CollectorRegistry()
    EngineDispatchCollector(reg).attach(lambda: engine_dispatch_stats(eng))
    text = generate_latest(reg).decode()
    for form in EngineDispatchCollector.PREFILL_FORMS:
        want = 3.0 if form == "padded:attn_impl" else 0.0
        assert (f'dynamo_worker_prefill_steps_total{{form="{form}"}} '
                f'{want}') in text, form


def test_the_marker_is_read_off_the_familys_forward():
    """The engine reads the marker off the family's forward, not off the
    ``functools.partial`` it may wrap it in (expert parallelism)."""
    import functools

    wrapped = functools.partial(moe.forward, ep_mesh=None)
    assert not hasattr(wrapped, "supports_packed")
    assert moe.forward.supports_packed


# -- which kernel attends which rows (ISSUE 40) ---------------------------


def _watch_plans(eng, monkeypatch):
    """The one-token rows at the tail of every packed step the engine
    assembles, in dispatch order: what the decode kernel is to take."""
    tails = []
    assemble = eng._prefill_arrays

    def watched(plan, mixed):
        lens = [c.length for c in plan.chunks]
        lens += [1] * (len(plan.decode_seqs) if mixed else 0)
        n = 0
        while n < len(lens) and lens[-1 - n] == 1:
            n += 1
        tails.append(n)
        return assemble(plan, mixed)

    monkeypatch.setattr(eng, "_prefill_arrays", watched)
    return tails


def _ring_since(eng, total):
    """The ring's records stamped after it held ``total`` (the recorder is
    the process's: other tests' engines wrote to it), oldest first."""
    snap = eng.steptrace.snapshot(limit=512)
    return snap["records"][:snap["total"] - total][::-1]


@pytest.mark.async_timeout(300)
async def test_a_packed_step_of_many_decode_rows_serves_the_padded_tokens(
        monkeypatch):
    """36 rows decode while two longer prompts are admitted: the packed
    steps carry >= 32 one-token rows behind the prompt chunks — the decode
    kernel's rows, the ragged kernel's chunks — and every stream is the
    padded XLA engine's, token for token. The ring's
    ``decode_kernel_rows`` counts exactly those rows, step by step, and
    the worker counter adds them up."""
    from dynamo_tpu.worker.metrics import engine_dispatch_stats

    reqs = [([3 + i, 5, 7 + i % 5], f"d{i}", 24) for i in range(36)]
    late = [(range(1, 40), "p0", 5), (range(50, 81), "p1", 5)]

    async def serve(eng):
        started = asyncio.Event()
        flowing = set()

        async def one(r, wait):
            if wait:
                await started.wait()
            out = []
            async for f in eng.generate(_req(*r)):
                out += f.token_ids
                flowing.add(r[1])
                if len(flowing) == len(reqs):
                    started.set()
            return out

        try:
            return await asyncio.gather(
                *(one(r, False) for r in reqs),
                *(one(r, True) for r in late))
        finally:
            await eng.stop()

    kw = dict(max_num_seqs=40, num_pages=256, max_prefill_chunk=32)
    packed = _engine(**kw)
    assert packed.packed_attention == \
        "chunks:ragged_mixed,one_token:paged_decode"
    tails = _watch_plans(packed, monkeypatch)
    before = packed.steptrace.total
    got = await serve(packed)
    ring = _ring_since(packed, before)
    want = await serve(_engine(attn_impl="scan", **kw))
    assert got == want
    assert [len(t) for t in got] == [24] * 36 + [5, 5]
    recs = [r for r in ring if r["program"].startswith("packed[")]
    assert [r["decode_kernel_rows"] for r in recs] == tails
    assert max(tails) >= 32
    # a step of decode rows and chunks: fewer rows for the decode kernel
    # than the step has, more slots than rows
    assert all(r["decode_kernel_rows"] <= r["rows"] <= r["tokens_real"]
               for r in recs)
    assert engine_dispatch_stats(packed)["packed_decode_kernel_rows"] \
        == float(sum(tails)) == float(packed.packed_decode_kernel_rows)
    # every other program of the ring reads 0
    assert not [r for r in ring if not r["program"].startswith("packed[")
                and r["decode_kernel_rows"]]


def _block_cfg():
    """A model that generates by diffusion over blocks of 4."""
    return ModelConfig.from_hf(dict(
        vocab_size=256, hidden_size=128, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, model_type="sdar",
        block_size=4, mask_token_id=255, max_position_embeddings=512),
        dtype="float32")


async def test_the_ring_reads_no_decode_kernel_row_under_a_visibility_block():
    """Generation by diffusion over blocks: the prompt's whole blocks are
    prefilled in a packed step of the ragged kernel alone, and neither the
    ring's field nor the worker counter counts a row."""
    eng = _engine(cfg=_block_cfg())
    n0 = eng.steptrace.total
    try:
        out = [t async for f in eng.generate(_req(range(1, 14), "b", 8))
               for t in f.token_ids]
    finally:
        await eng.stop()
    assert len(out) == 8
    ring = _ring_since(eng, n0)
    assert [r["program"] for r in ring if r["kind"] == "prefill"] \
        == ["packed[16,1]"]
    assert not [r for r in ring if r["decode_kernel_rows"]]
    assert eng.packed_decode_kernel_rows == 0


@pytest.mark.parametrize("case,says", [
    ("gqa", "chunks:ragged_mixed,one_token:paged_decode"),
    ("block", "ragged_mixed"), ("mla", "mla_ragged"), ("scan", None)])
def test_the_engine_says_which_kernels_attend_a_packed_step(case, says):
    """``startup.engine``'s ``prefill.attention``, read off what the
    engine is: the decode kernel takes the one-token rows of a causal GQA
    model; a visibility block (generation by diffusion over blocks) and
    latent attention keep every row in one kernel, and their ring field
    reads 0 whatever the rows."""
    cfg, kw = {
        "gqa": (None, {}),
        "block": (_block_cfg(), {}),
        "mla": (_mla_cfg(kv_lora_rank=128, head_dim=128), {}),
        "scan": (None, dict(attn_impl="scan")),
    }[case]
    eng = _engine(cfg=cfg, **kw)
    assert eng.packed_attention == says
    new = np.asarray([9, 5, 1, 1, 1, 0, 0, 0], np.int32)
    assert eng._decode_kernel_rows(new, 32) == (3 if case == "gqa" else 0)
    # more rows than slots: the ragged kernel alone, as the op decides
    assert eng._decode_kernel_rows(new, 4) == 0
    # only one-token rows, none, a one-token chunk among the chunks
    for lens, n in (([1, 1, 1, 0], 3), ([7, 2, 0, 0], 0),
                    ([4, 1, 6, 1, 1, 0], 2)):
        assert eng._decode_kernel_rows(np.asarray(lens, np.int32), 16) \
            == (n if case == "gqa" else 0)


def test_the_host_counts_the_rows_the_op_takes():
    """``JaxEngine._decode_kernel_rows`` (the ring's field) and
    ``ops/pallas/ragged._decode_rows`` (the program) are one rule, on the
    layouts ``_prefill_arrays`` makes: chunk rows, decode rows, pads."""
    from dynamo_tpu.ops.pallas.ragged import _decode_rows

    eng = _engine()
    rng = np.random.default_rng(0)
    for _ in range(50):
        chunks = rng.integers(1, 9, size=rng.integers(0, 4))
        new = np.concatenate([chunks, np.ones(rng.integers(0, 6), int),
                              np.zeros(rng.integers(0, 3), int)]) \
            .astype(np.int32)
        if not new.size:
            continue
        starts = (np.cumsum(new) - new).astype(np.int32)
        mask, _first = _decode_rows(jnp.asarray(starts), jnp.asarray(new))
        assert eng._decode_kernel_rows(new, 64) == int(mask.sum()), new
