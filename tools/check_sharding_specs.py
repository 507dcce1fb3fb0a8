#!/usr/bin/env python
"""Fail when a sharded engine's compiled step programs drift off their
declared shardings.

Usage: check_sharding_specs.py

The mesh-sharded fast path depends on invariants no unit assertion on
Python state can see: the fused multi-step block must keep its DONATED
pages carry on the cache's NamedSharding (donation silently degrades to a
copy when in/out shardings diverge), and its packed output + scalar carry
must come back fully replicated (the host ``np.asarray``s them; the next
chained block feeds them straight in). The per-step decode program must
likewise return the pages on the sharding they came in with — a silent
reshard would insert an all-gather into every decode step.

This tool builds a tiny tensor-parallel (tp=2) engine on a forced
2-device CPU mesh — the same GSPMD partitioning paths XLA uses on a real
slice — jit-LOWERS the decode / mixed / fused-multistep programs, and
asserts the compiled input/output shardings against the declared specs
(``parallel/sharding.ModelSharding.pages_spec``). Runs in tier-1 as a
subprocess test (tests/test_mesh_sharded.py) the way
``check_metrics_docs.py`` guards the metric docs.
"""

from __future__ import annotations

import os
import sys

# must happen before jax initializes a backend
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < 2:
        print("FAIL: could not force a 2-device CPU backend", file=sys.stderr)
        return 1

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel import tp_sharding
    from dynamo_tpu.parallel.sharding import transport_sharding

    cfg = ModelConfig.tiny(dtype="float32")
    shard = tp_sharding(cfg, 2)
    ecfg = JaxEngineConfig(
        num_pages=32, page_size=4, max_num_seqs=2, max_prefill_chunk=16,
        max_context=64, min_prefill_bucket=4, mesh=shard.mesh,
        shard_params_fn=shard.shard_params,
        shard_pages_fn=shard.shard_pages)
    eng = JaxEngine(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
                    ecfg)

    mesh = shard.mesh
    rep = NamedSharding(mesh, PartitionSpec())
    pages_sharding = NamedSharding(mesh, shard.pages_spec())
    errors: list = []

    def check(name: str, got, want, ndim: int) -> None:
        try:
            ok = got.is_equivalent_to(want, ndim)
        except Exception as e:  # noqa: BLE001 — incomparable IS a drift
            ok = False
            got = f"{got} (compare failed: {e})"
        if not ok:
            errors.append(f"{name}: compiled sharding {got} != declared "
                          f"{want}")

    B, P = 2, eng.table_width
    pages_ndim = eng.pages.ndim
    W = eng.cfg.penalty_window
    CARRY_2D = ("tok", "pos", "pids", "pcnt", "pctx", "pbias")

    def check_multistep(tag: str, ms) -> None:
        out_pages, out_packed, out_carry, out_aux = ms.output_shardings
        check(f"multistep{tag}.pages(out)", out_pages, pages_sharding,
              pages_ndim)
        check(f"multistep{tag}.packed(out)", out_packed, rep, 3)
        for key, s in out_carry.items():
            nd = 2 if key in CARRY_2D else 1
            check(f"multistep{tag}.carry[{key}](out)", s, rep, nd)
        # the expert layer's counts: scalars, none for a dense family
        for key, s in out_aux.items():
            check(f"multistep{tag}.aux[{key}](out)", s, rep, 0)
        in_shardings, _in_kw = ms.input_shardings
        # donated pages: argument 1 must come in on the sharding it goes
        # out with, or XLA falls back to copy-and-reshard and the
        # donation is lost
        check(f"multistep{tag}.pages(in,donated)", in_shardings[1],
              pages_sharding, pages_ndim)

    # -- fused multi-step block (explicit out_shardings) -------------------
    fn = eng._get_jit_multistep(2)
    ms_args = (
        eng.params, eng.pages, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, P), jnp.int32),
        jnp.ones(B, jnp.int32), jnp.zeros(B, bool),
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32), eng._rng,
        np.int32(0), jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.float32), jnp.full((B, 1), -1, jnp.int32), None,
        None)
    check_multistep("", fn.lower(*ms_args).compile())

    # -- CONSTRAINED fused block (penalty window + guided table riding the
    # carry): the same explicit out_shardings must hold for the trace that
    # carries the ring-buffer / automaton-state buffers, and the batched
    # grammar table must not force a reshard of the carry
    V = eng.model_cfg.vocab_size
    words = (V + 31) // 32
    pen = {
        "seeds": jnp.zeros(B, jnp.int32),
        "min_p": jnp.zeros(B, jnp.float32),
        "pw": {
            "fp": jnp.full(B, 0.5, jnp.float32),
            "pp": jnp.zeros(B, jnp.float32),
            "rp": jnp.full(B, 1.2, jnp.float32),
            "active": jnp.ones(B, bool),
            "prompt_ids": jnp.zeros((B, 2 * max(W, 1)), jnp.int32),
            "prompt_valid": jnp.zeros((B, 2 * max(W, 1)), bool),
        },
        "gt": {
            "trans": jnp.zeros((4, V), jnp.int32),
            "masks": jnp.full((4, words), 0xFFFFFFFF, jnp.uint32),
        },
    }
    pcarry = {
        "pids": jnp.zeros((B, W), jnp.int32),
        "pcnt": jnp.zeros((B, W), jnp.float32),
        "pctx": jnp.zeros((B, W), jnp.float32),
        "pbias": jnp.zeros((B, W), jnp.float32),
        "pn": jnp.zeros(B, jnp.int32),
        "gstate": jnp.zeros(B, jnp.int32),
    }
    ms_args_con = ms_args[:15] + (pen, pcarry)
    check_multistep(".constrained", fn.lower(*ms_args_con).compile())

    # -- per-step decode program (propagated shardings) --------------------
    def step_args(S: int):
        return (
            eng.params, eng.pages, jnp.zeros((B, S), jnp.int32),
            jnp.zeros((B, S), jnp.int32), jnp.zeros((B, P), jnp.int32),
            jnp.ones(B, jnp.int32), jnp.zeros(B, jnp.int32), eng._rng,
            np.int32(0), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32), None)

    # a padded mixed step is the one-step program at S > 1 (this engine
    # runs the XLA path on the CPU, so it does not pack)
    for name, fn2, S in (("decode", eng._jit_step, 1),
                         ("mixed", eng._jit_step, 4)):
        comp = fn2.lower(*step_args(S)).compile()
        pg, packed, _aux = comp.output_shardings
        check(f"{name}.pages(out)", pg, pages_sharding, pages_ndim)
        ins, _kw = comp.input_shardings
        check(f"{name}.pages(in,donated)", ins[1], pages_sharding,
              pages_ndim)

    # -- transport sharding (per-shard KV export/inject placement) ---------
    check("transport", transport_sharding(eng.pages), pages_sharding,
          pages_ndim)

    if errors:
        print("sharding spec drift detected:", file=sys.stderr)
        for e in errors:
            print(f"  FAIL {e}", file=sys.stderr)
        return 1
    print("sharding specs OK: multistep plain+constrained (pages donated "
          "sharded, packed/carry incl. penalty-window + guided-state "
          "buffers replicated), decode/mixed (pages stay on the cache "
          "sharding), transport placement")
    return 0


if __name__ == "__main__":
    sys.exit(main())
