"""Parallelism tests on the virtual 8-device CPU mesh (see conftest).

These exercise the same GSPMD partitioning paths XLA uses on a real TPU
slice: tp-sharded params/KV-pages must produce bit-identical greedy tokens to
the unsharded engine.
"""

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel import MeshSpec, ModelSharding, make_mesh, tp_sharding
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)


def make_req(tokens, rid, max_tokens=6):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0))


async def run_tokens(engine, tokens, rid):
    out = []
    async for f in engine.generate(make_req(tokens, rid)):
        out.extend(f.token_ids)
    return out


class TestMesh:
    def test_make_mesh_axes(self):
        mesh = make_mesh(MeshSpec(dp=2, tp=4))
        assert mesh.shape == {"dp": 2, "pp": 1, "tp": 4, "sp": 1,
                              "ep": 1}

    def test_mesh_size_mismatch(self):
        with pytest.raises(ValueError):
            make_mesh(MeshSpec(tp=3))

    def test_spec_from_dict_rejects_unknown(self):
        with pytest.raises(ValueError):
            MeshSpec.from_dict({"zz": 2})


class TestTpSharding:
    def test_tp_rejects_indivisible_heads(self):
        cfg = ModelConfig.tiny()  # 2 kv heads
        with pytest.raises(ValueError):
            tp_sharding(cfg, 8)

    async def test_tp_matches_unsharded_generation(self):
        cfg = ModelConfig.tiny()  # Hkv=2, I=128 -> tp=2 divides both
        prompt = list(range(1, 10))

        base = JaxEngine.random_init(cfg, JaxEngineConfig(
            num_pages=32, page_size=4, max_num_seqs=2,
            max_prefill_chunk=16, max_context=64, min_prefill_bucket=4))
        try:
            want = await run_tokens(base, prompt, "base")
        finally:
            await base.stop()

        shard = tp_sharding(cfg, 2)
        ecfg = JaxEngineConfig(
            num_pages=32, page_size=4, max_num_seqs=2,
            max_prefill_chunk=16, max_context=64, min_prefill_bucket=4,
            shard_params_fn=shard.shard_params,
            shard_pages_fn=shard.shard_pages)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        sharded = JaxEngine(cfg, params, ecfg)
        try:
            got = await run_tokens(sharded, prompt, "tp")
        finally:
            await sharded.stop()

        assert got == want
        assert len(got) == 6

    def test_pages_sharded_over_kv_heads(self):
        cfg = ModelConfig.tiny()
        shard = tp_sharding(cfg, 2)
        pages = llama.make_pages(cfg, 8, 4)
        placed = shard.shard_pages(pages)
        # Hkv axis split across tp: each shard holds Hkv/2 heads
        shard_shape = placed.sharding.shard_shape(placed.shape)
        assert shard_shape[3] == cfg.num_kv_heads // 2

    def test_param_placement(self):
        cfg = ModelConfig.tiny()
        shard = tp_sharding(cfg, 2)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        placed = shard.shard_params(params)
        wq = placed["layers"]["wq"]
        assert wq.sharding.shard_shape(wq.shape)[2] == cfg.q_size // 2
        emb = placed["embed"]
        assert emb.sharding.shard_shape(emb.shape) == emb.shape  # replicated


class TestDpSharding:
    """Batch-dim data parallelism on one engine: the mesh carries a dp axis,
    the step's batch inputs are dp-sharded and the packed output is
    re-replicated (the all-gather that unlocks cross-host dp,
    VERDICT r3 §5)."""

    @pytest.mark.async_timeout(150)
    async def test_dp_tp_matches_unsharded_generation(self):
        # two engine compiles (unsharded + dp x tp GSPMD) in one test:
        # runs ~30s warm but has flaked at the default 60s under load
        cfg = ModelConfig.tiny()  # Hkv=2 -> tp=2
        prompts = [list(range(1, 10)), list(range(20, 32)),
                   list(range(40, 47)), list(range(60, 70))]

        async def run_all(engine):
            import asyncio
            return await asyncio.gather(*[
                run_tokens(engine, p, f"r{i}")
                for i, p in enumerate(prompts)])

        base = JaxEngine.random_init(cfg, JaxEngineConfig(
            num_pages=64, page_size=4, max_num_seqs=4,
            max_prefill_chunk=16, max_context=64, min_prefill_bucket=4))
        try:
            want = await run_all(base)
        finally:
            await base.stop()

        mesh = make_mesh(MeshSpec(dp=2, tp=2),
                         devices=jax.devices()[:4])
        shard = ModelSharding(cfg, mesh)
        ecfg = JaxEngineConfig(
            num_pages=64, page_size=4, max_num_seqs=4,
            max_prefill_chunk=16, max_context=64, min_prefill_bucket=4,
            shard_params_fn=shard.shard_params,
            shard_pages_fn=shard.shard_pages, mesh=mesh)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        sharded = JaxEngine(cfg, params, ecfg)
        assert sharded._dp == 2
        # bucket floors raised so every padded batch divides by dp
        assert sharded.cfg.min_decode_bucket >= 2
        try:
            got = await run_all(sharded)
        finally:
            await sharded.stop()

        assert got == want
        assert all(len(g) == 6 for g in got)
