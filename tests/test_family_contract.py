"""What a model family is, and what ``attn_impl`` may say.

A family (``models.get_family``) is ``init_params``, ``make_pages`` and
ONE ``forward`` over ONE stacked page pool; the engine's ``attn_impl``
picks the attention op that forward calls, and names only what something
uses.
"""

import jax
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import get_family
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.sharding import transport_sharding

FAMILIES = {
    "llama": dict(),
    "moe": dict(num_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=32, model_type="qwen3_moe"),
    "deepseek": dict(num_layers=3, num_kv_heads=1, head_dim=32,
                     model_type="deepseek_v2", q_lora_rank=0,
                     kv_lora_rank=32, qk_rope_head_dim=16,
                     qk_nope_head_dim=32, v_head_dim=32, num_experts=4,
                     num_experts_per_tok=2, moe_intermediate_size=32,
                     n_shared_experts=2, first_k_dense_replace=1,
                     routed_scaling_factor=1.0),
    "gemma": dict(model_type="gemma2", sliding_window=6,
                  attn_logit_softcap=40.0),
}

ENGINE = dict(num_pages=16, page_size=4, max_num_seqs=2,
              max_prefill_chunk=8, max_context=32, min_prefill_bucket=4)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_is_one_forward_over_one_stacked_pool(family):
    cfg = ModelConfig.tiny(**FAMILIES[family])
    mod = get_family(cfg)
    assert mod.__name__ == f"dynamo_tpu.models.{family}"
    for name in ("forward", "init_params", "make_pages"):
        assert callable(getattr(mod, name)), name
    for gone in ("forward_unrolled", "make_pages_list"):
        assert not hasattr(mod, gone), gone
    eng = JaxEngine.random_init(cfg, JaxEngineConfig(attn_impl="scan",
                                                     **ENGINE))
    assert eng.attn_impl == "scan"
    assert isinstance(eng.pages, jax.Array)
    assert eng.pages.ndim == 6
    assert eng.pages.shape[:3] == (cfg.num_layers, ENGINE["num_pages"], 2)
    assert eng.pages.shape[4] == ENGINE["page_size"]
    assert transport_sharding(eng.pages) == eng.pages.sharding


@pytest.mark.parametrize("where", ["engine", "worker_flag"])
@pytest.mark.parametrize("value", ["unrolled", "pallas_unrolled"])
def test_attn_impl_names_auto_pallas_scan_and_nothing_else(value, where,
                                                           capsys):
    if where == "engine":
        with pytest.raises(ValueError) as err:
            JaxEngine.random_init(ModelConfig.tiny(), JaxEngineConfig(
                attn_impl=value, **ENGINE))
        said = str(err.value)
    else:
        from dynamo_tpu.worker.main import build_parser

        parser = build_parser()
        for ok in ("auto", "pallas", "scan"):
            args = parser.parse_args(["--model-path", "m", "--attn-impl", ok])
            assert args.attn_impl == ok
        with pytest.raises(SystemExit):
            parser.parse_args(["--model-path", "m", "--attn-impl", value])
        said = capsys.readouterr().err
    assert value in said
    # the three that are left, by name ("pallas_unrolled" holds one)
    rest = said.replace(value, "")
    for name in ("auto", "pallas", "scan"):
        assert name in rest, said
