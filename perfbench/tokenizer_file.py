"""Write the tokenizer file a configuration is served with.

No model tokenizer exists offline, and the engine's byte-level fallback
(260 usable ids under a 32768-wide head) makes an answer's length a
lottery: a stop id is 2 of the ~512 ids the sampler may draw, so an
answer ends after ~256 tokens on average wherever chance puts it, and
half the ids decode to nothing, so tokens cannot be counted at the
client. This file is the stand-in, stated under ``assumed`` in the
configuration: a character-level vocabulary of the model's PUBLISHED
size. One prompt character is one token (as with the byte fallback),
every id decodes to visible text (one SSE frame per generated token),
and the two stop ids are 2 of ``vocab_size`` as with a real vocabulary.
"""
from __future__ import annotations

CHAT_MARKERS = (
    "<|begin_of_text|>", "<|end_of_text|>", "<|start_header_id|>",
    "<|end_header_id|>", "<|eot_id|>",
)


def write_tokenizer(path: str, vocab_size: int) -> None:
    from tokenizers import AddedToken, Tokenizer, decoders, models

    vocab = {}
    for ch in [chr(c) for c in range(32, 127)] + ["\n", "\t"]:
        vocab[ch] = len(vocab)
    vocab["<unk>"] = len(vocab)
    for marker in CHAT_MARKERS:
        vocab[marker] = len(vocab)
    if vocab_size < len(vocab):
        raise ValueError(f"vocab_size {vocab_size} is below the {len(vocab)} base entries")
    filler = 0
    while len(vocab) < vocab_size:
        vocab[f" w{filler:05d}"] = len(vocab)
        filler += 1
    tok = Tokenizer(models.BPE(vocab=vocab, merges=[], unk_token="<unk>"))
    tok.decoder = decoders.Fuse()
    # not "special": decode(skip_special_tokens=True) still prints them, so
    # every generated id is a visible frame; they still encode atomically
    tok.add_tokens([AddedToken(m, special=False, normalized=False) for m in CHAT_MARKERS])
    if tok.get_vocab_size() != vocab_size:
        raise ValueError(f"tokenizer has {tok.get_vocab_size()} ids, wanted {vocab_size}")
    tok.save(path)
