"""Write the model directory a worker loads, from a configuration file.

A configuration file (``benchmarks/configs/<name>.json``) holds the
published ``config.json`` keys at its top level and everything that belongs
to the benchmark under the one key ``benchmark``. The directory written here
is those published keys verbatim plus a tokenizer of the benchmark's own.

The tokenizer maps token id ``i`` to the word ``<i>`` over the model's whole
vocabulary. Prompts are sent as token ids, so nothing is ever encoded; what
the tokenizer buys is that every streamed text piece and every ``logprobs``
token names its id, so the client counts output tokens exactly and the
correctness check knows which tokens were served. (The repo's test tokenizer
has 261 entries: an id above that decodes to nothing, and a stream of
nothing has no first token to time.)
"""

from __future__ import annotations

import copy
import json
import os
import re

CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "configs")
_ID = re.compile(r"<(\d+)>")


def load_config(name: str, tiny: bool = False) -> dict:
    """``{"hf": published keys as run, "bench": the benchmark block}``.
    ``tiny`` overlays the block's ``tiny`` keys (toy widths for the CPU
    tests; never a measured configuration)."""
    with open(os.path.join(CONFIG_DIR, f"{name}.json")) as f:
        raw = json.load(f)
    bench = copy.deepcopy(raw.pop("benchmark"))
    hf = raw
    if tiny:
        hf.update(bench["tiny"]["config"])
        bench["worker_args"] = bench["tiny"]["worker_args"]
        bench["probe_lengths"] = bench["tiny"]["probe_lengths"]
        bench["dtype"] = "float32"
    return {"name": name, "hf": hf, "bench": bench}


def write_model_dir(path: str, hf: dict) -> str:
    from tokenizers import Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    vocab = {f"<{i}>": i for i in range(hf["vocab_size"])}
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<0>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f)
    eos = hf.get("eos_token_id")
    eos = eos[0] if isinstance(eos, list) else eos
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"eos_token": f"<{eos}>" if eos is not None else None}, f)
    return path


def ids_of(text: str) -> list:
    """Token ids named by streamed text or by a ``logprobs`` token."""
    return [int(m) for m in _ID.findall(text)]
