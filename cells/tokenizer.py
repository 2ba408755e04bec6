"""The tokenizer the serve cells hand to ``LLMEngine``: ids in, ids out.

The repo's own BPE vocabulary has 4096 entries and is not Mistral's; the
model's tokenizer (32768 entries) is not in the repo and its text is not
what a cell measures.  The benchmark's prompts are token ids from the
seed, and to check the answers it needs the ids that came back.  So the
engine is given, through ``engine_kwargs["tokenizer"]`` as a user gives it
a model's tokenizer, one whose text is the ids written out: every id of
the model's vocabulary round-trips, every token streams as its own chunk,
and there is no end-of-sequence id, so a request returns exactly the
``max_tokens`` it asked for (the ``ignore_eos`` of serving benchmarks).
"""


class IdTokenizer:
    pad_id, bos_id, eos_id = 0, None, None

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str):
        return [int(w) for w in text.split()]

    def decode(self, ids) -> str:
        return "".join(f" {int(i)}" for i in ids)
