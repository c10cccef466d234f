"""The tokenizer the benchmark deploys with its seed-weight models.

The sandbox has no checkpoint and no tokenizer files, and the program's
byte fallback knows 258 of a model's ~152,000 ids: it drops every other id a
seed-weight model emits, so a client would see almost no text and could not
count tokens. A deployment brings its tokenizer as it brings its weights;
this one covers the whole vocabulary, one visible character per id
(``chr(0x10000 + id)``), has no stop token (requests run to
``max_new_tokens``) and no chat template (the program's ChatML fallback
renders the prompt). So every SSE event's text has exactly one character
per token it carries, and ``len(message)`` is the message's token count.
"""

from __future__ import annotations

import numpy as np

BASE = 0x10000


class FullVocabTokenizer:
    bos_token_id = None
    eos_token_id = None
    chat_template = None
    model_max_length = 1 << 20

    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)

    def encode(self, text: str, add_special_tokens: bool = False) -> list[int]:
        return [encode_char(c) for c in text]

    def decode(self, ids, skip_special_tokens: bool = True) -> str:
        return "".join(chr(BASE + int(i)) for i in ids)


def encode_char(c: str) -> int:
    o = ord(c)
    return o - BASE if o >= BASE else o


def text_to_ids(text: str) -> list[int]:
    return [encode_char(c) for c in text]


def random_text(seed: int, n: int, vocab_size: int) -> str:
    """``n`` tokens of seeded content, as the text that encodes to them."""
    ids = np.random.default_rng([int(seed), 0xC0]).integers(
        0, vocab_size, size=max(int(n), 0)
    )
    return "".join(chr(BASE + int(i)) for i in ids)
