"""Inputs from the seed: the e-learning reference set as arrays, and query
rows as CSV text.

The distributions are those of ``avenir_tpu/datagen/elearn.py`` (a port of
upstream ``resource/elearn.py:13-105``), copied here so that the yardstick
does not move with the program: nine truncated-Gaussian integer activity
signals and a P/F status whose failure probability rises with low activity.
The references are made as arrays (no 2^24 Python strings); a test ties them
to the CSV path field for field (tests/test_data.py).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

SIGNALS = ("contentTime", "discussTime", "organizerTime", "emailCount",
           "testScore", "assignmentScore", "chatMsgCount", "searchTime",
           "bookMarkCount")
CLASS_VALUES = ("P", "F")

_REFS, _QUERIES = 0, 1          # second word of the seed sequence
# References are drawn in pieces of this many rows, each from a stream of its
# own ([seed, _REFS, piece]), so that threads can share the work and the
# arrays do not depend on how many threads there were.
PIECE_ROWS = 1 << 20


def _signals(rng: np.random.Generator, n: int) -> np.ndarray:
    """[n, 9] int64 activity signals, in schema order."""
    def gauss(mu, sd):
        return np.maximum(rng.normal(mu, sd, size=n), 0).astype(np.int64)

    content = gauss(300, 100)
    discuss = gauss(80, 40)
    organizer = gauss(40, 20)
    email = gauss(10, 6)
    test = np.clip(rng.normal(50, 30, size=n), 10, 100).astype(np.int64)
    assign = np.clip(rng.normal(60, 40, size=n), 10, 100).astype(np.int64)
    chat = gauss(100, 60)
    search = gauss(60, 40)
    bookmark = gauss(12, 8)
    return np.stack([content, discuss, organizer, email, test, assign, chat,
                     search, bookmark], axis=1)


def _fail_percent(sig: np.ndarray) -> np.ndarray:
    content, discuss, _org, email, test, assign, chat, search, bookmark = sig.T
    prob = np.full(sig.shape[0], 10.0)
    prob += np.select([content < 100, content < 150], [10, 6], 0)
    prob += np.select([discuss < 30, discuss < 50], [8, 4], 0)
    prob += np.where(discuss < 10, 5, 0)
    prob += np.where(email < 3, 6, 0)
    prob += np.select([test < 30, test < 40, test < 50], [34, 20, 14], 0)
    prob += np.select([assign < 35, assign < 50, assign < 60], [28, 18, 10], 0)
    prob += np.where(chat < 20, 4, 0)
    prob += np.select([search < 15, search < 30], [7, 3], 0)
    prob += np.where(bookmark < 4, 8, 0)
    return prob


def make_refs(n: int, seed: int, threads: int = 8
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(cont [n, 9] float32 raw signals, labels [n] int32: 0 = P, 1 = F)."""
    cont = np.empty((n, len(SIGNALS)), np.float32)
    labels = np.empty(n, np.int32)

    def piece(i: int) -> None:
        lo, hi = i * PIECE_ROWS, min((i + 1) * PIECE_ROWS, n)
        rng = np.random.default_rng([int(seed), _REFS, i])
        sig = _signals(rng, hi - lo)
        cont[lo:hi] = sig
        labels[lo:hi] = rng.integers(0, 101, size=hi - lo) < _fail_percent(sig)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(piece, range(-(-n // PIECE_ROWS))))
    return cont, labels


def make_query_lines(n: int, seed: int) -> List[str]:
    """``n`` request rows as CSV text, ``userID`` then the nine signals — a
    day's activity file has no status column.  Drawn from a stream of the
    seed that the references never use."""
    rng = np.random.default_rng([int(seed), _QUERIES])
    sig = _signals(rng, n)
    ids = 1000000 + rng.integers(0, 1000000, size=n)
    return [",".join(map(str, row))
            for row in np.column_stack([ids, sig]).tolist()]


def refs_as_csv_lines(cont: np.ndarray, labels: np.ndarray) -> List[str]:
    """The reference rows as the training CSV the jobs read (id, signals,
    status) — only ever called at test sizes."""
    sig = cont.astype(np.int64)
    return [",".join(map(str, [2000000 + i] + row + [CLASS_VALUES[lab]]))
            for i, (row, lab) in enumerate(zip(sig.tolist(), labels.tolist()))]
