"""What decides ``correct``.

After the window the run sends the configuration's fixed probe prompts
through the frontend, greedy, 16 tokens each with ``logprobs``: once cold
(their tokens were never seen by this worker) and once more, when the prefix
cache holds them. ``correct`` is true when

1. the log-probabilities the served path reported for its own continuation
   - computed by prefill into the paged cache and decode out of it - agree
   with the plain float32 reference (``reference/<family>.py``, run in a
   child of its own while no worker holds the chip, teacher-forced on the
   served tokens), for the chosen tokens and for the served top
   alternatives, within ``REFERENCE_TOL`` of the configuration's dtype,
   and on average within the configuration's own ``reference_mean_tol``
   where its file states one for that dtype: the widest gap of a
   mixture-of-experts model is an expert swapped at the top-k boundary, in
   bfloat16 as in int8, and only the mean tells the two apart (PERF.md,
   PR 36: the limit lies between a clean run's mean and the mean of the
   reference with int8 weights, ``reference/control.py``);
2. cold and cached agree within ``REPEAT_TOL`` on the first token (same
   context, same position) and on every further token for as long as both
   continuations chose the same tokens and carried the same values;
3. every request of the window that completed carried exactly the number of
   tokens it asked for (the load generator fails any other), and the probes
   did too.

Whose rule scores a served token: the configuration's. Its reference module
(``reference/<family>.py``) may export ``score``; one that exports none is
scored by ``reference/score.py``'s ``next_token_rule`` (position ``i - 1``,
teacher-forced, predicts token ``i``). What such a rule needs beside the
tokens is data in the configuration's file, an optional block ``probe``
under ``benchmark``: ``{"extra": {...}, "carry": [...]}``. ``extra`` is
merged into every probe's request body; ``carry`` names keys of the streamed
``logprobs`` object whose value is a list with one entry per token of its
chunk (the pass that revealed a token, say). The carried lists are kept per
token beside the ids and travel to the reference child with the sequence.

The reference scores are kept in ``benchmarks/.cache/reference/`` keyed by
the token sequence (and by what was carried, where a configuration carries
anything), so a later run that is served the same continuation starts no
child.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import numpy as np

import modeldir
from served import Failed, log_tail
from traffic import Request

HERE = os.path.dirname(os.path.abspath(__file__))
PROBE_TOKENS = 16
TOP = 5

# Served (bf16 weights, bf16 cache, bf16 activations between layers, f32
# accumulation) against the float32 reference, in nats of log-probability.
# A bf16 rounding is 2^-9 relative; through some tens of layers and a
# vocabulary projection of order-1 logits the chip measured at most 0.125
# (qwen3-4b, 36 layers; dsv2lite 0.047; PERF.md, PR 23) on any probed token.
# The bound is about 2.5 times that: int8 weights (2^-8 of each row's maximum per weight, an
# order of magnitude coarser) or a skipped layer move log-probabilities by
# tenths of a nat to whole nats and fail. float32 (the CPU tests) differs
# only by summation order.
REFERENCE_TOL = {"bfloat16": 0.3, "float32": 2e-3}
# the cached run prefills a shorter chunk, so the same logits come out of
# differently shaped bf16 matmuls: two bf16 roundings of one quantity, so
# the same size as the difference from the reference (measured 0.028-0.097;
# chip_smoke.py allows 0.1 on the first token of 28 layers); float32 on the
# CPU agrees to rounding
REPEAT_TOL = {"bfloat16": 0.3, "float32": 1e-3}


def probe_prompts(config: dict) -> list:
    """The configuration's fixed probes: token ids drawn from its name, not
    from the run's seed, so every run of the configuration asks the same."""
    out = []
    for i, n in enumerate(config["bench"]["probe_lengths"]):
        seed = int.from_bytes(hashlib.sha256(
            f"{config['name']}/probe{i}".encode()).digest()[:8], "big")
        out.append(np.random.default_rng(seed).integers(
            0, config["hf"]["vocab_size"], size=n).tolist())
    return out


async def send_probes(run, client) -> None:
    """Cold, then cached, one request at a time on an idle system, so the
    batch is the same in every run."""
    run.probes = []
    probe = run.config["bench"].get("probe", {})
    extra = {**probe.get("extra", {}), "logprobs": TOP}
    for prompt in probe_prompts(run.config):
        passes = []
        for _ in ("cold", "cached"):
            r = Request(due=client.now(), prompt=prompt,
                        max_tokens=PROBE_TOKENS, source="probe", turn=0)
            chunks = await client.send(r, extra=extra)
            if not r.ok:
                raise Failed(f"probe of {len(prompt)} tokens failed: "
                             f"{r.error}")
            passes.append(_served(chunks, probe.get("carry", ())))
        run.probes.append({"prompt": prompt, "cold": passes[0],
                           "cached": passes[1]})


def _served(chunks: list, carry=()) -> dict:
    """{"ids": chosen ids, "lps": their log-probabilities, "top": per
    position {id: logprob}, "carried": {key: per position value}} from the
    legacy ``logprobs`` objects; ``carried`` holds the configuration's
    ``probe.carry`` keys, and is empty where it names none."""
    ids, lps, top = [], [], []
    carried = {key: [] for key in carry}
    for lp in chunks:
        for tok, val, alts in zip(lp["tokens"], lp["token_logprobs"],
                                  lp["top_logprobs"]):
            ids.append(modeldir.ids_of(tok)[0])
            lps.append(val)
            top.append({modeldir.ids_of(t)[0]: v
                        for t, v in (alts or {}).items()})
        for key, values in carried.items():
            got = lp.get(key)
            if not isinstance(got, list) or len(got) != len(lp["tokens"]):
                raise Failed(f"a probe's logprobs carry {key!r} as {got!r}: "
                             f"wanted one entry for each of its "
                             f"{len(lp['tokens'])} tokens")
            values.extend(got)
    if len(ids) != PROBE_TOKENS:
        raise Failed(f"a probe came back with {len(ids)} token logprobs, "
                     f"wanted {PROBE_TOKENS}")
    return {"ids": ids, "lps": lps, "top": top, "carried": carried}


def _key(tokens: list, carried: dict = None) -> str:
    """A sequence's name in the reference cache: its tokens and, only where
    the configuration carries anything, the carried values."""
    h = hashlib.sha256(np.asarray(tokens, np.int64).tobytes())
    if carried:
        h.update(json.dumps(carried, sort_keys=True).encode())
    return h.hexdigest()


def reference_scores(run, sequences: list) -> dict:
    """``{key: [per position {id: logprob}]}`` for each sequence ``(prompt,
    continuation, carried)``: from the cache file, or from a child that
    holds the device alone (the workers have stopped)."""
    tag = run.config["name"] + ("-tiny" if run.args.tiny else "")
    path = os.path.join(HERE, ".cache", "reference", f"{tag}.json")
    try:
        with open(path) as f:
            cache = json.load(f)
    except (OSError, ValueError):
        cache = {}
    todo = [(p, c, k) for p, c, k in sequences
            if _key(p + c, k) not in cache]
    if todo:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        ask = os.path.join(run.run_dir, "reference_in.json")
        got = os.path.join(run.run_dir, "reference_out.json")
        with open(ask, "w") as f:
            json.dump({"config": run.config["name"], "tiny": run.args.tiny,
                       "sequences": [{"prompt": p, "continuation": c,
                                      "carried": k} for p, c, k in todo]}, f)
        env = dict(os.environ, JAX_PLATFORMS=run.platform,
                   PYTHONPATH=os.path.dirname(HERE),
                   JAX_COMPILATION_CACHE_DIR=run.cache_dir)
        with open(os.path.join(run.run_dir, "reference.log"), "wb") as log:
            rc = subprocess.run(
                [sys.executable, os.path.join(HERE, "reference", "score.py"),
                 ask, got], env=env, stdout=log, stderr=subprocess.STDOUT,
                timeout=600).returncode
        if rc != 0:
            raise Failed("the reference child failed:\n" + log_tail(
                os.path.join(run.run_dir, "reference.log")))
        with open(got) as f:
            for (p, c, k), scored in zip(todo, json.load(f)):
                cache[_key(p + c, k)] = scored
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f)
        os.replace(tmp, path)
    return cache


def judge(run) -> bool:
    dtype = run.config["bench"]["dtype"]
    ref_tol, rep_tol = REFERENCE_TOL[dtype], REPEAT_TOL[dtype]
    mean_tol = run.config["bench"].get("reference_mean_tol", {}).get(dtype)
    sequences = []
    for p in run.probes:
        for which in ("cold", "cached"):
            seq = (p["prompt"], p[which]["ids"], p[which]["carried"])
            if seq not in sequences:
                sequences.append(seq)
    cache = reference_scores(run, sequences)
    worst_ref = worst_rep = sum_ref = 0.0
    compared = 0
    for p in run.probes:
        for which in ("cold", "cached"):
            got = p[which]
            ref = cache[_key(p["prompt"] + got["ids"], got["carried"])]
            for pos in range(PROBE_TOKENS):
                known = {int(k): v for k, v in ref[pos].items()}
                pairs = [(got["lps"][pos], known[got["ids"][pos]])]
                pairs += [(v, known[i]) for i, v in got["top"][pos].items()
                          if i in known]
                for served_lp, ref_lp in pairs:
                    worst_ref = max(worst_ref, abs(served_lp - ref_lp))
                    sum_ref += abs(served_lp - ref_lp)
                    compared += 1
        cold, cached = p["cold"], p["cached"]
        for pos in range(PROBE_TOKENS):
            if cold["ids"][pos] != cached["ids"][pos] or any(
                    v[pos] != cached["carried"][key][pos]
                    for key, v in cold["carried"].items()):
                break     # from here on the two contexts differ
            worst_rep = max(worst_rep,
                            abs(cold["lps"][pos] - cached["lps"][pos]))
    mean_ref = sum_ref / max(1, compared)
    run.probe_result = {
        "served_vs_reference_max_nats": worst_ref, "reference_tol": ref_tol,
        "cold_vs_cached_max_nats": worst_rep, "repeat_tol": rep_tol,
        "served_vs_reference_mean_nats": mean_ref,
        "reference_mean_tol": mean_tol, "logprobs_compared": compared,
        "probe_lengths": [len(p["prompt"]) for p in run.probes]}
    return (worst_ref <= ref_tol and worst_rep <= rep_tol
            and (mean_tol is None or mean_ref <= mean_tol))
