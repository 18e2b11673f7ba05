"""A synthetic full-vocabulary tokenizer the chat client can see through.

`ByteTokenizer.decode` keeps ids < 256, so with a 32k vocabulary and random
weights 99% of generated ids decode to nothing and a "first chunk" arrives
with the first lucky byte. This writes a WordLevel vocabulary of the
configuration's `vocab_size` instead (`t00000 ... t<V-1>`, whitespace-split,
no special token) as a Hugging Face tokenizer directory, loaded by
the program through `tokenizer: hf:<dir>`. A prompt is a string of vocabulary
words, so its token count is exact and its ids span the vocabulary; every
generated id reaches the client as one whitespace-separated word, so the
client counts tokens itself.

The tokenizer declares NO end-of-sequence token. Random weights sample any
id with probability 1/V per step, so with one declared about one run in five
had a request that stopped early, and that run's work, and every metric of
it, then depended on the token ids `--seed` drew (my chip runs, PR 23: the
same seed read 2.5% off in both sets). Without one every request runs to its
cap and every run of a cell does the same work.
"""

from __future__ import annotations

import json
from pathlib import Path

# the chat step renders "role: content\nassistant:" unless the tokenizer
# carries a template; this one passes the content through alone, so a
# prompt of n words is n tokens
CHAT_TEMPLATE = "{% for m in messages %}{{ m['content'] }}{% endfor %}"


def word(token_id: int) -> str:
    return f"t{token_id:05d}"


def write_tokenizer(directory: Path, vocab_size: int) -> Path:
    """Write tokenizer.json + tokenizer_config.json: every id 0..V-1 is a
    word, none is special (a special token would be dropped from decoded
    text, and the client could not count it). An unknown word would map to id
    0; no prompt contains one."""
    directory.mkdir(parents=True, exist_ok=True)
    (directory / "tokenizer.json").write_text(json.dumps({
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {
            "type": "WordLevel", "vocab": {word(i): i for i in range(vocab_size)},
            "unk_token": word(0),
        },
    }))
    (directory / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "chat_template": CHAT_TEMPLATE,
        "clean_up_tokenization_spaces": False,
        "model_max_length": 1 << 30,
    }))
    return directory


def prompt_text(token_ids) -> str:
    return " ".join(word(int(i)) for i in token_ids)


def count_words(text: str) -> int:
    return len(text.split())
