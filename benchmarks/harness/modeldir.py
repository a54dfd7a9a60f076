"""Write the model directory a configuration is served from.

``config.json`` is the configuration file without its ``benchmark`` group.
``tokenizer.json`` is generated: a word-level vocabulary in which token ``i``
is the word ``t<i>``. The HTTP surface returns text, not token ids, and the
program's byte tokenizer renders every id above 255 as nothing (no streamed
chunk, no way back to the id). With this vocabulary every token is one
visible word, so each token is one SSE chunk and the served ids can be read
back for the comparison with the reference. Weights stay seeded random-init:
the directory holds no tensors.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List


def token_word(i: int) -> str:
    return f"t{i}"


def word_token(word: str) -> int:
    w = word.strip()
    if not w.startswith("t") or not w[1:].isdigit():
        raise ValueError(f"not a token word: {word!r}")
    return int(w[1:])


def count_tokens(text: str) -> int:
    """Tokens in a streamed text piece (words joined by single spaces)."""
    return len(text.split())


def tokens_of(text: str) -> List[int]:
    return [word_token(w) for w in text.split()]


def write_model_dir(path: str, config: Dict[str, Any]) -> None:
    os.makedirs(path, exist_ok=True)
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f)
    vocab = {token_word(i): i for i in range(int(hf["vocab_size"]))}
    tok = {"version": "1.0", "truncation": None, "padding": None,
           "added_tokens": [], "normalizer": None,
           "pre_tokenizer": {"type": "WhitespaceSplit"},
           "post_processor": None, "decoder": None,
           "model": {"type": "WordLevel", "vocab": vocab,
                     "unk_token": token_word(0)}}
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        json.dump(tok, f)
