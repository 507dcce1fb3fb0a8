#!/usr/bin/env python3
"""The reference child: holds the device alone, scores token sequences with
the configuration's plain float32 reference, and exits.

    python3 benchmarks/reference/score.py <in.json> <out.json>

Input: ``{"config", "tiny", "sequences": [{"prompt", "continuation",
"carried"}]}``. Output, per sequence and per continuation position: ``{token
id: log-probability}`` for the served token and the reference's own top 32.

How a served sequence is scored is the reference module's: it may export

    score(hf, params, layer_fns, prompt, continuation, carried)

which returns, for each continuation position, the float32 log-probability
vector ``[len(continuation), vocabulary]`` that the configuration's rule
assigns to what was served there (``carried``: what the configuration's
``probe.carry`` kept beside each token, ``{key: [per position]}``). Such a
configuration says so in its file with a ``probe`` block under ``benchmark``
(``correctness.py``), an empty one where its rule needs nothing carried. A
module that exports none is scored by ``next_token_rule``: the model
teacher-forced on prompt + continuation.

Weights are data, taken as the worker takes them: the program's
``init_params(cfg, PRNGKey(0))`` in the served dtype. The reference streams
them a layer at a time in float32 under ``default_matmul_precision
("highest")`` (a TPU multiplies float32 in lower precision otherwise).
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

KEEP_TOP = 32


def load_family(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reference_{name}", os.path.join(HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def next_token_rule(ref, hf, params):
    """The rule of a reference module that exports no ``score``: one clean
    pass over prompt + continuation, and position ``len(prompt) - 1 + j``
    predicts continuation token ``j``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def logp_of(h_last):
        return jax.nn.log_softmax(ref.head(hf, params, h_last), axis=-1)

    def score(hf, params, layer_fns, prompt, continuation, carried):
        tokens = jnp.asarray(prompt + continuation, jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)
        for kind, stack, n in ref.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(jnp.float32), stack)
                h = layer_fns[kind](w, h)
        lo = len(prompt) - 1
        return logp_of(h[lo:lo + len(continuation)])

    return score


def main() -> int:
    import modeldir
    with open(sys.argv[1]) as f:
        ask = json.load(f)
    config = modeldir.load_config(ask["config"], ask["tiny"])
    hf, bench = config["hf"], config["bench"]

    from dynamo_tpu.utils.platform import (
        enable_compilation_cache, pin_platform)
    enable_compilation_cache(pin_platform())
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf(hf, dtype=bench["dtype"])
    params = get_family(cfg).init_params(cfg, jax.random.PRNGKey(0))
    ref = load_family(bench["reference"])
    if hasattr(ref, "score") != ("probe" in bench):
        # the file's data says whose rule scores the configuration
        raise SystemExit(
            f"{ask['config']}.json has "
            f"{'a' if 'probe' in bench else 'no'} probe block under "
            f"benchmark, and reference/{bench['reference']}.py exports "
            f"{'a' if hasattr(ref, 'score') else 'no'} score: a "
            "configuration with a rule of its own has both")
    rule = getattr(ref, "score", None) or next_token_rule(ref, hf, params)
    layer_fns = {kind: jax.jit(lambda w, h, fn=fn: fn(hf, w, h))
                 for kind, fn in ref.LAYER_FNS.items()}

    @jax.jit
    def pick(logp, targets):
        top_lp, top_id = jax.lax.top_k(logp, KEEP_TOP)
        chosen = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return chosen, top_lp, top_id

    out = []
    with jax.default_matmul_precision("highest"):
        for seq in ask["sequences"]:
            prompt, cont = seq["prompt"], seq["continuation"]
            logp = jnp.asarray(rule(hf, params, layer_fns, prompt, cont,
                                    seq.get("carried", {})), jnp.float32)
            if logp.ndim != 2 or logp.shape[0] != len(cont):
                raise SystemExit(
                    f"the rule scored {len(cont)} served tokens with an "
                    f"array of shape {logp.shape}")
            chosen, top_lp, top_id = jax.device_get(pick(
                logp, jnp.asarray(cont, jnp.int32)))
            scored = []
            for j, tok in enumerate(cont):
                row = {int(i): float(v)
                       for i, v in zip(top_id[j], top_lp[j])}
                row[int(tok)] = float(chosen[j])
                scored.append(row)
            out.append(scored)
            print(f"scored {len(prompt)}+{len(cont)} tokens", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
