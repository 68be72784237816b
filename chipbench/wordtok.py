"""The benchmark's own tokenizer: one word per id of the model's vocabulary.

A prompt of n words is exactly n tokens of the ids the seed chose, and
every streamed chunk and every ``logprobs`` key names its id
(``"t9165"``).  No token is declared special, so none is skipped when
the server decodes and the server knows no end-of-sequence id.
"""

from __future__ import annotations

import json
import os


def word(token_id: int) -> str:
    return f"t{token_id}"


def token_id(text: str) -> int:
    return int(text[1:])


def text_of(ids) -> str:
    return " ".join(map(word, ids))


def write_model_dir(path: str, hf_config: dict) -> None:
    """``config.json`` and a word-level tokenizer, as the server's
    ``--model`` and ``--tokenizer`` directory."""
    os.makedirs(path, exist_ok=True)
    vocab = {word(i): i for i in range(hf_config["vocab_size"])}
    tokenizer = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab,
                  "unk_token": word(0)},
    }
    for name, content in (
            ("config.json", hf_config),
            ("tokenizer.json", tokenizer),
            ("tokenizer_config.json",
             {"tokenizer_class": "PreTrainedTokenizerFast",
              "clean_up_tokenization_spaces": False})):
        with open(os.path.join(path, name), "w") as f:
            json.dump(content, f)
