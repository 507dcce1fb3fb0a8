#!/usr/bin/env python3
"""The control of ``correct`` for a configuration scored by
``reference/sdar.py``'s own rule (``control.py`` refuses one: it replays a
causal pass): the same rule with the plain reference in the program's
place, computed in the nearest precision below the one the configuration
states, and read as a run reads the served path. A builder's tool; no run of
the benchmark starts it.

    python3 benchmarks/reference/control_sdar.py <config> [--tiny] [--seeds 3]

The configuration's probe prompts, each with 16 further tokens drawn from
the seed and revealed by the static schedule of the worker's
``--denoising-steps`` (a block's masks in position order, the quota's worth
a pass), go through ``sdar.score`` twice: in float32, and with every weight
matrix rounded to the lower precision (int8 with one scale a column for a
configuration that states bfloat16, bfloat16 for one that states float32;
norms stay). It does not generate: at each served position it takes the
tokens the lower precision puts first, five of them as a probe asks for,
and reads the gap between the log-probability the lower precision gives
them and the float32 one. The widest gap of a seed is that seed's reading of
``served_vs_reference_max_nats`` and their mean its reading of
``served_vs_reference_mean_nats``; a limit holds only where it lies under
the smallest of its readings. One JSON object on the last line.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))   # the program
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

LOWER = {"bfloat16": "int8", "float32": "bfloat16"}


def static_passes(prompt_len: int, n: int, block: int, steps: int) -> list:
    """The pass that reveals each of ``n`` generated tokens under the
    static schedule, masks taken in position order."""
    out = []
    for g in range(n):
        at = prompt_len + g
        start = at // block * block
        first = max(start, prompt_len)       # the block's first mask
        rank, p, quota = at - first, 0, 0
        masks = min(start + block, prompt_len + n) - first
        while True:
            quota += min(block // steps + (p < block % steps),
                         masks - quota)
            if rank < quota:
                break
            p += 1
        out.append(p)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("config")
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--seeds", type=int, default=3)
    args = p.parse_args()

    import correctness
    import modeldir
    import score
    config = modeldir.load_config(args.config, args.tiny)
    hf, bench = config["hf"], config["bench"]
    if bench["reference"] != "sdar":
        raise SystemExit("the control of reference/sdar.py's rule")

    from dynamo_tpu.utils.platform import (
        enable_compilation_cache, pin_platform)
    enable_compilation_cache(pin_platform())
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf(hf, dtype=bench["dtype"])
    params = get_family(cfg).init_params(cfg, jax.random.PRNGKey(0))
    ref = score.load_family("sdar")
    lower = LOWER[bench["dtype"]]
    wargs = bench["worker_args"]
    steps = int(wargs[wargs.index("--denoising-steps") + 1])
    f32 = jnp.float32

    @jax.jit
    def rounded(a):
        """A weight matrix as the lower precision holds it, in float32."""
        a = a.astype(f32)
        if a.ndim < 2:
            return a
        if lower == "bfloat16":
            return a.astype(jnp.bfloat16).astype(f32)
        scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.clip(jnp.round(a / scale), -127, 127) * scale

    readings, means = [], []
    with jax.default_matmul_precision("highest"):
        for seed in range(args.seeds):
            worst, total, count = 0.0, 0.0, 0
            for i, prompt in enumerate(correctness.probe_prompts(config)):
                cont = np.random.default_rng([seed, i]).integers(
                    0, hf["vocab_size"],
                    size=correctness.PROBE_TOKENS).tolist()
                carried = {"reveal_pass": static_passes(
                    len(prompt), len(cont), int(hf["block_size"]), steps)}
                exact = ref.score(hf, params, None, prompt, cont, carried)
                low = ref.score(hf, params, None, prompt, cont, carried,
                                upcast=rounded)
                top_lp, top_id = jax.lax.top_k(low, correctness.TOP)
                gap = jnp.abs(top_lp - jnp.take_along_axis(exact, top_id,
                                                           axis=-1))
                worst = max(worst, float(jnp.max(gap)))
                total, count = total + float(jnp.sum(gap)), count + gap.size
            readings.append(worst)
            means.append(total / count)
            print(f"seed {seed}: widest {worst}, mean {total / count}",
                  flush=True)
    limit = correctness.REFERENCE_TOL[bench["dtype"]]
    mean_limit = bench.get("reference_mean_tol", {}).get(bench["dtype"])
    print(json.dumps({"config": args.config, "tiny": args.tiny,
                      "stated": bench["dtype"], "control": lower,
                      "readings": readings, "means": means, "limit": limit,
                      "mean_limit": mean_limit,
                      "control_fails": min(readings) > limit or (
                          mean_limit is not None
                          and min(means) > mean_limit),
                      "platform": jax.devices()[0].platform}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
