"""Traffic generators: from a traffic file's parameters and ``--seed`` to the
batches a cell trains on.  The program sees only the batches.

One general generator today, ``packed_documents``: documents with log-normal
lengths (cut at the sequence length), token ids Zipf-distributed over the
configuration's real vocabulary, packed greedily into rows of S+1 tokens with
an end-of-document id after each; what is left of a row when the next
document does not fit is padding (the same id).  There is no mask across
document boundaries and none over padding: the program has neither (PERF.md,
Open questions), so every position is trained on.

Batch ``i`` is a pure function of ``(seed, i)``, so the ingest may build
batches in any order and on any thread.

A traffic file that states ``dataset_batches`` (with ``data_seed``) describes
a job that trains for epochs over a small corpus: the dataset is that many
batches, batch ``j`` a pure function of ``(data_seed, j)``, the same rows
whatever ``--seed`` is, and the run iterates the program's ingest epoch after
epoch; ``--seed`` then draws only the order of each epoch (the ingest's
seeded shuffle), the check rows and whatever else the cell draws from it.
That is for models whose step length is data (expert layers that walk as many
windows as their router sent rows): every seed does the same work, in another
order.  A traffic file names its generator
under ``"generator"``: a key of ``GENERATORS`` here, or a module
``benchmarks/traffic_gen/<name>.py`` with the same ``make`` signature.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np


@functools.lru_cache(maxsize=4)
def _zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    weights = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


class PackedDocuments:
    """``params``: seq_len, seqs_per_chip, doc_len_median, doc_len_sigma,
    zipf_exponent and, for a corpus trained on for epochs, dataset_batches
    and data_seed.  Token id = Zipf rank - 1 over ``vocab_size`` ids."""

    def __init__(self, params: Dict, *, vocab_size: int, eod_id: int,
                 global_batch: int, seq_len: int, seed: int):
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.vocab_size = vocab_size
        self.eod_id = eod_id
        self.seed = seed
        self.median = float(params["doc_len_median"])
        self.sigma = float(params["doc_len_sigma"])
        self.exponent = float(params["zipf_exponent"])
        #: batches in the corpus, or None: a stream, one epoch long
        self.dataset_batches = params.get("dataset_batches")
        if self.dataset_batches is not None:
            self.dataset_batches = int(self.dataset_batches)
            self.data_seed = int(params["data_seed"])

    def rows(self, index: int) -> Tuple[np.ndarray, Dict]:
        """Batch ``index`` as (global_batch, S+1) int32 rows, and what was
        drawn: document lengths as packed, padding and separator counts."""
        if self.dataset_batches is None:
            rng = np.random.default_rng([self.seed, index])
        else:
            rng = np.random.default_rng(
                [self.data_seed, index % self.dataset_batches])
        B, width = self.global_batch, self.seq_len + 1
        cdf = _zipf_cdf(self.vocab_size, self.exponent)
        rows = np.searchsorted(cdf, rng.random((B, width))).astype(np.int32)
        np.minimum(rows, self.vocab_size - 1, out=rows)
        doc_lens, padding = [], 0
        carry = None
        for r in range(B):
            pos = 0
            while pos < width:
                if carry is None:
                    n = int(np.exp(rng.normal(np.log(self.median),
                                              self.sigma)))
                    carry = min(max(n, 1), self.seq_len)
                if pos + carry + 1 > width:     # carry <= S, so pos > 0
                    rows[r, pos:] = self.eod_id
                    padding += width - pos
                    break
                pos += carry
                rows[r, pos] = self.eod_id
                pos += 1
                doc_lens.append(carry)
                carry = None
        return rows, {"doc_lens": doc_lens, "padding": padding,
                      "positions": B * width}

    def check_rows(self, n: int) -> np.ndarray:
        """``n`` seeded rows of S+1 Zipf ids with no documents in them, for
        the comparison with the reference: no step trains on them, and no
        padding makes them easy."""
        rng = np.random.default_rng([self.seed, 2 ** 31 - 1])
        cdf = _zipf_cdf(self.vocab_size, self.exponent)
        rows = np.searchsorted(cdf, rng.random((n, self.seq_len + 1)))
        return np.minimum(rows, self.vocab_size - 1).astype(np.int32)

    def batch(self, index: int) -> Dict[str, np.ndarray]:
        rows, _ = self.rows(index)
        return {"tokens": rows[:, :-1], "targets": rows[:, 1:]}

    def describe(self, n_batches: int = 8) -> Dict:
        """The distribution the first ``n_batches`` batches drew."""
        lens, padding, positions = [], 0, 0
        for i in range(n_batches):
            _, drawn = self.rows(i)
            lens += drawn["doc_lens"]
            padding += drawn["padding"]
            positions += drawn["positions"]
        q = np.percentile(lens, [5, 50, 95]) if lens else [0, 0, 0]
        return {"batches": n_batches, "documents": len(lens),
                "doc_len_p5": float(q[0]), "doc_len_p50": float(q[1]),
                "doc_len_p95": float(q[2]),
                "doc_len_max": int(max(lens, default=0)),
                "padding_share": padding / max(positions, 1)}

    def dataset(self, n_batches: int):
        """``n_batches`` lazy blocks (the corpus's ``dataset_batches`` where
        the traffic file states them: one epoch), one global batch each,
        through the program's own dataset API so that the streaming ingest,
        its shuffle window, its prefetcher and ``device_put_batch`` do the
        work they do in a job."""
        from ray_tpu import data

        B = self.global_batch
        if self.dataset_batches is not None:
            n_batches = self.dataset_batches

        def to_batch(block):
            return self.batch(int(block["id"][0]) // B)

        return data.range(B * n_batches, parallelism=n_batches) \
            .map_batches(to_batch)


GENERATORS = {"packed_documents": PackedDocuments}


def make(traffic: Dict, **kwargs):
    """The generator a traffic file names, built for one run."""
    name = traffic["generator"]
    if name in GENERATORS:
        return GENERATORS[name](traffic, **kwargs)
    from benchmarks.lib.spec import load_module

    return load_module("traffic_gen", name).make(traffic, **kwargs)
