"""Character-level text source for causal language-model training.

No reference analog (SURVEY §5 documents long-context as absent from the
reference); this is the data-side half of the framework's long-context
extra — the model-side half is ``models.charlm`` (a causal decoder built
from prototxt-compatible layers).  Design mirrors the other data sources
(``data/cifar.py``, ``data/listfile.py``): a plain loader returning
numpy feed dicts the solver consumes, TPU-friendly static shapes
throughout.

A char-level corpus needs no tokenizer download (this environment has
zero egress), and any UTF-8 text works — the convergence example trains
on the repo's own documentation.
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np


class CharVocab:
    """Byte-free char vocabulary: id 0 is reserved for <unk>.

    Built from the corpus itself; stable order (sorted by codepoint) so a
    vocab rebuilt from the same text maps identically — checkpoints
    remain usable across runs without serializing the vocab separately
    (though ``to_lines``/``from_lines`` round-trips it for deploy).
    """

    UNK = 0

    def __init__(self, chars: "list[str]"):
        self.chars = list(chars)
        self._ids = {c: i + 1 for i, c in enumerate(self.chars)}

    @classmethod
    def from_text(cls, text: str) -> "CharVocab":
        return cls(sorted(set(text)))

    @property
    def size(self) -> int:
        return len(self.chars) + 1  # + <unk>

    def encode(self, text: str) -> np.ndarray:
        return np.array([self._ids.get(c, self.UNK) for c in text],
                        dtype=np.int32)

    def decode(self, ids) -> str:
        out = []
        for i in np.asarray(ids).reshape(-1):
            i = int(i)
            out.append(self.chars[i - 1] if 1 <= i <= len(self.chars) else "�")
        return "".join(out)

    def to_lines(self) -> "list[str]":
        return [f"U+{ord(c):06X}" for c in self.chars]

    @classmethod
    def from_lines(cls, lines: "list[str]") -> "CharVocab":
        return cls([chr(int(ln.strip()[2:], 16)) for ln in lines if ln.strip()])


def load_corpus(paths: "list[str] | str") -> str:
    """Concatenate UTF-8 text files (a directory = all *.md/*.txt/*.py
    under it, sorted) into one training corpus string."""
    if isinstance(paths, str):
        paths = [paths]
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in sorted(os.walk(p)):
                files += sorted(
                    os.path.join(root, n) for n in names
                    if n.endswith((".md", ".txt", ".py"))
                )
        else:
            files.append(p)
    parts = []
    for f in files:
        with open(f, "r", encoding="utf-8", errors="replace") as fh:
            parts.append(fh.read())
    return "\n\n".join(parts)


def char_lm_batches(
    text: str,
    vocab: CharVocab,
    batch: int,
    seq_len: int,
    seed: int | None = 0,
) -> Iterator[dict]:
    """Endless stream of next-char prediction minibatches.

    Each element: ``{"data": int32 [batch, seq_len],
    "label": int32 [batch, seq_len]}`` with ``label[t] = data[t+1]`` —
    the causal-LM shift done data-side so the model graph stays a plain
    forward net (the reference pattern: supervision arrives as a blob,
    not a graph transform).  Windows start at uniform-random offsets,
    the char-level analog of ``MinibatchSampler``'s contiguous windows
    (ref: src/main/scala/libs/MinibatchSampler.scala:18-27).
    """
    ids = vocab.encode(text)
    if ids.size < seq_len + 2:
        raise ValueError(
            f"corpus has {ids.size} chars; need > seq_len+1 = {seq_len + 1}")
    rs = np.random.RandomState(seed)
    hi = ids.size - seq_len - 1
    while True:
        starts = rs.randint(0, hi, size=batch)
        data = np.stack([ids[s:s + seq_len] for s in starts])
        label = np.stack([ids[s + 1:s + seq_len + 1] for s in starts])
        yield {"data": data, "label": label}


def token_windows(path: str, batch: int, seq_len: int, stride: int = 1,
                  offset: int = 0):
    """Host data fn over a flat token file: ``fn(it, out=None)``.

    ``path`` holds ``uint16`` token ids back to back and nothing else
    (the OLMo / Megatron ``.npy``-less on-disk convention: documents
    already tokenised, concatenated with their end-of-text ids, no
    header).  The file is memory-mapped.  Window ``j`` is the ``seq_len +
    1`` tokens from ``j * (seq_len + 1)`` on: its first ``seq_len`` are
    the row's ``data``, its last ``seq_len`` the row's ``label``
    (next-token targets; attention crosses document boundaries, nothing
    is padded or masked — OLMo's packing).  Batch ``b`` holds windows
    ``b * batch .. b * batch + batch - 1`` modulo the file's whole
    windows, and call ``n`` of the fn reads batch ``offset + n * stride``
    (a multi-process job interleaves its batches by process id).

    Like the ``db:`` cursor the fn takes a destination (``takes_out``):
    ``out["data"]`` / ``out["label"]``, ``[batch, seq_len]`` of any
    integer dtype, are filled in place, each token copied once from the
    mapped file with the cast riding on the assignment, and come back as
    the batch; without ``out`` the batch is two fresh int32 arrays."""
    tokens = np.memmap(path, dtype=np.uint16, mode="r")
    span = seq_len + 1
    windows = tokens.size // span
    if windows < 1:
        raise ValueError(
            f"{path}: {tokens.size} tokens, one window needs {span}")
    state = {"n": 0}

    def fn(_, out=None):
        first = (offset + state["n"] * stride) * batch
        state["n"] += 1
        if out is None:
            out = {"data": np.empty((batch, seq_len), np.int32),
                   "label": np.empty((batch, seq_len), np.int32)}
        elif out["data"].shape != (batch, seq_len) \
                or out["label"].shape != (batch, seq_len):
            raise ValueError(
                f"destination is {out['data'].shape} / {out['label'].shape}"
                f", the batch is {(batch, seq_len)}")
        for row in range(batch):
            lo = ((first + row) % windows) * span
            out["data"][row] = tokens[lo:lo + seq_len]
            out["label"][row] = tokens[lo + 1:lo + span]
        return {"data": out["data"], "label": out["label"]}

    fn.takes_out = True
    return fn
