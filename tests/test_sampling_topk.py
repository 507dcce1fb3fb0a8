"""The sampler's two-stage selection (``ops/sampling.top_candidates``).

It has to return what ``jax.lax.top_k`` returns, bit for bit — values,
indices, order, ties to the lower vocabulary index — at every vocabulary
the repo's configs name, and the sampling paths built on it have to serve
the tokens and logprobs they served on ``lax.top_k`` under the same
``rng``. The step programs of an engine whose vocabulary engages the
grouped form must hold no ``top_k`` / ``sort`` over an axis of the
vocabulary's length (``engine/program_check.vocab_sorts`` reads the
compiled programs for the same line).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import sampling
from dynamo_tpu.ops.sampling import (TOPK_MAX, candidate_form, sample_tokens,
                                     spec_verify, top_candidates)

VOCABS = (151936, 128256, 102400, 32000, 1000, 100)
ROWS = 3


def _logits(kind: str, V: int, k: int) -> np.ndarray:
    rng = np.random.default_rng(V + k)
    x = (3.0 * rng.standard_normal((ROWS, V))).astype(np.float32)
    if kind == "ties":
        # bfloat16's 8 bits of mantissa, then whole numbers: every value
        # many times over, so the order among equals decides the indices
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                       .astype(jnp.float32))
        x = np.round(x)
    elif kind == "masked":
        # a guided row: fewer finite entries than k, the rest -inf
        keep = rng.choice(V, size=min(5, V), replace=False)
        m = np.full_like(x, -np.inf)
        m[:, keep] = x[:, keep]
        x = m
    elif kind == "all_inf":
        x[1] = -np.inf
    return x


@pytest.mark.parametrize("kind", ["random", "ties", "masked", "all_inf"])
@pytest.mark.parametrize("k", [64, 8, 1])
@pytest.mark.parametrize("V", VOCABS)
def test_top_candidates_is_lax_top_k_bit_for_bit(V, k, kind):
    k = min(k, V)
    x = jnp.asarray(_logits(kind, V, k))
    vals, idx = jax.jit(lambda l: top_candidates(l, k))(x)
    want_vals, want_idx = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    assert idx.dtype == want_idx.dtype and vals.dtype == want_vals.dtype


@pytest.mark.parametrize("kind", ["random", "ties", "masked", "all_inf"])
@pytest.mark.parametrize("k", [64, 8, 1])
@pytest.mark.parametrize("V", [151936 - 37, 40001])
def test_a_vocabulary_the_group_width_does_not_divide(V, k, kind):
    """The last group is padded with ``-inf``: no index at or past ``V``
    may come back, even from a row that is ``-inf`` all over (where the
    pads tie with every real column)."""
    assert candidate_form(V, k).startswith("grouped")
    assert V % sampling.GROUP_WIDTH
    x = jnp.asarray(_logits(kind, V, k))
    vals, idx = jax.jit(lambda l: top_candidates(l, k))(x)
    want_vals, want_idx = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    assert int(jnp.max(idx)) < V


def test_leading_axes_and_the_form_by_shape():
    """``[B, S, V]`` logits (the verify step's, the prompt scorer's) give
    ``[B, S, k]``; the form is decided from the static ``(V, k)`` alone."""
    assert candidate_form(151936, 64) == "grouped[G=1187,g=128]"
    assert candidate_form(128256, 64) == "grouped[G=1002,g=128]"
    assert candidate_form(102400, 64) == "grouped[G=800,g=128]"
    for V in (1024, 512, 256, 100):
        assert candidate_form(V) == "direct"
    assert candidate_form(32000, 64) == "grouped[G=250,g=128]"
    assert candidate_form(16383, 64) == "direct"
    x = jnp.asarray(_logits("ties", 40000, 8)).reshape(ROWS, 1, 40000)
    x = jnp.concatenate([x, -x], axis=1)                      # [3, 2, V]
    vals, idx = top_candidates(x, 8)
    want_vals, want_idx = jax.lax.top_k(x, 8)
    assert vals.shape == idx.shape == (ROWS, 2, 8)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))


# -- the sampling paths on it against the same paths on lax.top_k ----------

V_SERVE = 20000      # a small vocabulary that still groups at k = 64
B = 6


def _here_and_on_lax_top_k(monkeypatch, fn):
    """``fn()`` as the module stands, then the oracle: the module's own
    sampling bodies with the selection put back to ``jax.lax.top_k`` —
    the parent's program."""
    got = fn()
    with monkeypatch.context() as m:
        m.setattr(sampling, "top_candidates", jax.lax.top_k)
        return got, fn()


def _serve_logits(seed: int, guided: bool = False) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    x = (2.5 * rng.standard_normal((B, V_SERVE))).astype(np.float32)
    # served logits are bfloat16 matmul outputs: ties are everywhere
    x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    if guided:
        words = np.zeros((B, -(-V_SERVE // 32)), np.uint32)
        for b in range(B):
            for t in rng.choice(V_SERVE, size=7 + b, replace=False):
                words[b, t // 32] |= np.uint32(1) << np.uint32(t % 32)
        x = np.asarray(sampling.apply_vocab_mask(jnp.asarray(x),
                                                 jnp.asarray(words)))
    return jnp.asarray(x)


_F = lambda *v: jnp.asarray(v, jnp.float32)       # noqa: E731
_I = lambda *v: jnp.asarray(v, jnp.int32)         # noqa: E731
SAMPLE_CASES = {
    "greedy": dict(temperature=_F(*[0.0] * B), top_k=_I(*[0] * B),
                   top_p=_F(*[1.0] * B)),
    "temperature": dict(temperature=_F(0.3, 0.7, 1.0, 1.3, 2.0, 0.0),
                        top_k=_I(*[0] * B), top_p=_F(*[1.0] * B)),
    "top_k": dict(temperature=_F(*[1.0] * B), top_k=_I(1, 2, 5, 40, 64, 500),
                  top_p=_F(*[1.0] * B)),
    "top_p": dict(temperature=_F(*[1.0] * B), top_k=_I(*[0] * B),
                  top_p=_F(0.1, 0.5, 0.9, 0.95, 0.99, 1.0)),
    "min_p": dict(temperature=_F(*[1.0] * B), top_k=_I(*[0] * B),
                  top_p=_F(*[1.0] * B),
                  min_p=_F(0.0, 0.01, 0.05, 0.1, 0.3, 0.9)),
    "seeded": dict(temperature=_F(*[0.9] * B), top_k=_I(*[0] * B),
                   top_p=_F(*[0.95] * B), seeds=_I(0, 7, 7, 11, 0, 123),
                   seed_pos=_I(5, 9, 9, 3, 1, 77)),
    "guided": dict(temperature=_F(0.0, 0.5, 1.0, 1.0, 1.5, 0.0),
                   top_k=_I(0, 0, 3, 0, 50, 0),
                   top_p=_F(1.0, 0.9, 1.0, 1.0, 0.8, 1.0)),
}


@pytest.mark.parametrize("case", sorted(SAMPLE_CASES))
def test_sample_tokens_serves_the_parents_tokens_and_logprobs(
        case, monkeypatch):
    assert candidate_form(V_SERVE).startswith("grouped")
    kw = dict(SAMPLE_CASES[case])
    logits = _serve_logits(3, guided=case == "guided")
    if "seeds" in kw:
        kw["seed_rng"] = jax.random.PRNGKey(42)

    def three_steps():
        out = []
        for step in range(3):
            key = jax.random.fold_in(jax.random.PRNGKey(42), step)
            toks, lps = jax.jit(
                lambda l, r: sample_tokens(l, r, **kw))(logits, key)
            out.append((np.asarray(toks), np.asarray(lps)))
        return out

    got, want = _here_and_on_lax_top_k(monkeypatch, three_steps)
    for (toks, lps), (want_toks, want_lps) in zip(got, want):
        np.testing.assert_array_equal(toks, want_toks)
        np.testing.assert_array_equal(lps, want_lps)
    if case == "guided":
        # a guided row never leaves its allowed set, though the set is
        # smaller than the candidate list
        allowed = np.isfinite(np.asarray(logits))
        for toks, _ in got:
            assert allowed[np.arange(B), toks].all()


@pytest.mark.parametrize("case", ["greedy", "temperature", "top_k", "top_p",
                                  "guided"])
def test_spec_verify_accepts_and_replaces_as_the_parent_did(
        case, monkeypatch):
    S = 4
    kw = {k: v for k, v in SAMPLE_CASES[case].items()
          if k in ("temperature", "top_k", "top_p")}
    rng = np.random.default_rng(9)
    logits = jnp.stack([_serve_logits(20 + j, guided=case == "guided")
                        for j in range(S)], axis=1)           # [B, S, V]
    # a peaked distribution and drafts that are often its argmax, so
    # that sampled rows accept some too
    best = np.asarray(jnp.argmax(logits, axis=-1))            # [B, S]
    logits = logits + 8.0 * jax.nn.one_hot(best, V_SERVE)
    drafts = np.where(rng.random((B, S - 1)) < 0.6, best[:, :S - 1],
                      rng.integers(0, V_SERVE, (B, S - 1)))
    tokens = jnp.asarray(np.concatenate(
        [np.zeros((B, 1), np.int64), drafts], axis=1), jnp.int32)
    got, want = _here_and_on_lax_top_k(monkeypatch, lambda: [
        np.asarray(a) for a in jax.jit(
            lambda l, t, r: spec_verify(l, t, r, **kw))(
                logits, tokens, jax.random.PRNGKey(5))])
    for a, b in zip(got, want):                   # n_acc, tok, lp, draft lps
        np.testing.assert_array_equal(a, b)
    assert got[0].max() > 0                       # something was accepted


# -- the programs: no selection over an axis of the vocabulary's length -----

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _vocab_ordering_eqns(jaxpr, V: int):
    """The ``top_k`` / ``sort`` / ``approx_top_k`` equations with an
    operand that has an axis of ``V`` columns."""
    return [eqn for eqn in _eqns(jaxpr)
            if eqn.primitive.name in ("top_k", "sort", "approx_top_k")
            and any(V in v.aval.shape for v in eqn.invars)]


def _grouping_engine(**kw):
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig(vocab_size=V_SERVE, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=128, dtype="float32")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    return JaxEngine(cfg, params, JaxEngineConfig(
        num_pages=32, page_size=8, max_num_seqs=4, max_prefill_chunk=64,
        max_context=256, **kw))


def _traced_programs(engine):
    """name -> jaxpr of the engine's own jitted step programs, traced
    from shapes."""
    from dynamo_tpu.engine.program_check import step_programs

    return {name: fn.trace(*args).jaxpr for name, (fn, args) in
            step_programs(engine, batch=4, chunk=32, width=4,
                          tokens=64).items()}


@pytest.fixture(scope="module")
def program_jaxprs():
    out = _traced_programs(_grouping_engine(attn_impl="pallas"))
    out["spec"] = _traced_programs(_grouping_engine(spec_tokens=3))["spec"]
    return out


@pytest.mark.parametrize("program", ["decode", "fused", "packed", "spec"])
def test_no_step_program_orders_an_axis_of_the_vocabulary(program_jaxprs,
                                                          program):
    jaxpr = program_jaxprs[program]
    assert _vocab_ordering_eqns(jaxpr, V_SERVE) == []
    # and the selection is there, once: top_k of the group maxima and of
    # the gathered candidates (the alternatives' columns are a slice of
    # the same candidates after XLA's CSE; in the jaxpr each caller's
    # call still stands)
    G = -(-V_SERVE // sampling.GROUP_WIDTH)
    widths = {eqn.invars[0].aval.shape[-1] for eqn in _eqns(jaxpr)
              if eqn.primitive.name == "top_k"}
    assert widths == {G, TOPK_MAX * sampling.GROUP_WIDTH}


def _select_directly(monkeypatch):
    """Put ``lax.top_k`` back behind both names the programs call."""
    from dynamo_tpu.engine import jax_engine

    monkeypatch.setattr(sampling, "top_candidates", jax.lax.top_k)
    monkeypatch.setattr(jax_engine, "top_candidates", jax.lax.top_k)


def test_the_walker_catches_a_direct_selection(monkeypatch):
    """The same engine on ``lax.top_k``: the walker has to find ``top_k``
    over the vocabulary, or it guards nothing."""
    _select_directly(monkeypatch)
    for name, jaxpr in _traced_programs(_grouping_engine()).items():
        found = _vocab_ordering_eqns(jaxpr, V_SERVE)
        assert found and all(e.primitive.name == "top_k" for e in found), name


def test_vocab_sorts_reads_an_optimised_hlo_text():
    """The reader on a hand-written module: the key+index sort of the
    vocabulary and a ``TopK`` custom call on it are listed; the sorts of
    the group maxima and of the candidates, and a ``TopK`` of them, are
    not."""
    from dynamo_tpu.engine.program_check import vocab_sorts

    hlo = """
ENTRY %main (logits: f32[32,151936]) -> s32[32,64] {
  %logits = f32[32,151936]{1,0:T(8,128)} parameter(0)
  %iota = s32[32,151936]{1,0:T(8,128)} iota(), iota_dimension=1
  %sort.10 = (f32[32,151936]{1,0:T(8,128)}, s32[32,151936]{1,0:T(8,128)}) sort(%logits, %iota), dimensions={1}, is_stable=true, to_apply=%cmp
  %custom-call.1 = (f32[32,8]{1,0}, s32[32,8]{1,0}) custom-call(%logits), custom_call_target="TopK", called_computations={%cmp}
  %reduce.1 = f32[32,1187]{1,0} reduce(%bitcast.3, %neg_inf), dimensions={2}, to_apply=%max
  %sort.38 = (f32[32,1187]{1,0:T(8,128)}, s32[32,1187]{1,0}) sort(%reduce.1, %iota.2), dimensions={1}, is_stable=true, to_apply=%cmp
  %sort.40 = (f32[32,8192]{1,0}, s32[32,8192]{1,0}) sort(%fusion.5, %iota.3), dimensions={1}, is_stable=true, to_apply=%cmp, metadata={op_name="f32[32,151936]"}
  %custom-call.2 = (f32[32,64]{1,0}, s32[32,64]{1,0}) custom-call(%fusion.5), custom_call_target="TopK", called_computations={%cmp}
  ROOT %gte = s32[32,64]{1,0} get-tuple-element(%custom-call.2), index=1
}
"""
    found = vocab_sorts(hlo, 151936)
    assert [line.split(" = ")[0] for line in found] == [
        "%sort.10", "%custom-call.1"]
    assert [line.split(" = ")[0] for line in vocab_sorts(hlo, 1187)] == [
        "%sort.38"]


def test_the_program_check_fails_an_engine_that_sorts_the_vocabulary(
        monkeypatch):
    """``check_step_programs`` on the CPU backend: the grouping engine's
    programs are ``ok``; on ``lax.top_k`` they are not, and the report
    names the instruction."""
    from dynamo_tpu.engine import program_check

    # the pool as a shape that dwarfs a toy step's temporaries
    good = program_check.check_step_programs(
        _grouping_engine(), batch=4, chunk=32, width=2, num_pages=4096)
    assert [r["program"] for r in good] == ["decode", "fused", "mixed"]
    for r in good:
        assert r["vocab_sorts"] == [] and r["ok"], r["program"]
    _select_directly(monkeypatch)
    bad = program_check.check_step_programs(
        _grouping_engine(), batch=4, chunk=32, width=2, num_pages=4096)
    for r in bad:
        assert r["vocab_sorts"] and not r["ok"], r["program"]
