"""Traffic generator: the raw documents a run checks in, from its seed.

A traffic file (``bench/traffic/<name>.json``) gives the parameters; this
one generator reads every mix.  The documents are real text: the JSON
lines file under ``bench/`` that the mix names in ``documents``, one
``{"path", "text"}`` object per document, each a whole source file as it
was published, so their lengths are the files' own.  The run takes them in
file order until the corpus holds ``corpus_tokens_per_chip`` tokens per
chip, so every seed gets the same documents, and ``--seed`` only picks
their order: the work is the same from seed to seed.

The byte tokenizer gives one token per UTF-8 byte plus BOS and EOS, so a
document of ``n`` bytes is ``n + 2`` tokens.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
SPECIAL_TOKENS = 2          # BOS + EOS around every document


def texts(traffic: Dict, chips: int) -> List[bytes]:
    """The mix's documents, in file order, the same for every seed."""
    target = int(traffic["corpus_tokens_per_chip"]) * chips
    out: List[bytes] = []
    total = 0
    with open(BENCH / traffic["documents"], encoding="ascii") as fh:
        for line in fh:
            if total >= target:
                break
            text = json.loads(line)["text"].encode("utf-8")
            out.append(text)
            total += len(text) + SPECIAL_TOKENS
    if total < target:
        raise ValueError(f"{traffic['documents']} holds {total} tokens; "
                         f"the mix asks for {target}")
    return out


def documents(traffic: Dict, chips: int, seed: int
              ) -> List[Tuple[str, bytes]]:
    """``(record_id, text)`` for every document, in record-id order.

    Ids are zero-padded so that their sorted order is their index order."""
    docs = texts(traffic, chips)
    order = np.random.default_rng(seed).permutation(len(docs))
    return [(f"doc-{i:07d}", docs[j]) for i, j in enumerate(order.tolist())]
