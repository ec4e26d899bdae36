"""The traffic generator: same seed, same batches; another seed, others; the
rows are what the docstring says; the drawn distribution is reported."""

import numpy as np

from benchmarks.lib import spec, traffic


def make(seed, name="packed-s1024-b8", chips=1, vocab=32768, eod=2):
    params = spec.load_json(spec.BENCH_DIR, "traffic", name + ".json")
    return traffic.make(params, vocab_size=vocab, eod_id=eod,
                        global_batch=params["seqs_per_chip"] * chips,
                        seq_len=params["seq_len"], seed=seed)


def test_same_seed_same_batches_other_seed_others():
    a, b, c = make(7), make(7), make(8)
    for i in (0, 1, 5):
        assert all(np.array_equal(a.batch(i)[k], b.batch(i)[k])
                   for k in ("tokens", "targets"))
        assert not np.array_equal(a.batch(i)["tokens"], c.batch(i)["tokens"])
    assert not np.array_equal(a.batch(0)["tokens"], a.batch(1)["tokens"])


def test_rows_are_packed_documents():
    gen = make(3)
    rows, drawn = gen.rows(0)
    assert rows.shape == (8, 1025) and rows.dtype == np.int32
    assert rows.min() >= 0 and rows.max() < 32768
    batch = gen.batch(0)
    assert np.array_equal(batch["tokens"], rows[:, :-1])
    assert np.array_equal(batch["targets"], rows[:, 1:])
    # Every document is followed by the end-of-document id, documents are cut
    # at S, and what the documents and separators do not fill is padding.
    assert all(1 <= n <= 1024 for n in drawn["doc_lens"])
    used = sum(drawn["doc_lens"]) + len(drawn["doc_lens"])
    assert used + drawn["padding"] == drawn["positions"] == 8 * 1025
    first = drawn["doc_lens"][0]
    assert rows[0, first] == 2


def test_drawn_distribution_is_reported():
    long_rows = make(11, "packed-s8192-b1").describe(16)
    short_rows = make(11, "packed-s1024-b8").describe(16)
    # log-normal, median 600, sigma 1.2: the median document survives the cut
    # at 8192 and the 95th percentile (600 * e^(1.645 * 1.2) = 4320) does too.
    assert 350 < long_rows["doc_len_p50"] < 1000
    assert long_rows["doc_len_max"] <= 8192
    assert short_rows["doc_len_max"] <= 1024
    # Greedy packing wastes more of a short row than of a long one.
    assert 0.0 < long_rows["padding_share"] < short_rows["padding_share"] < 0.5


def test_ids_follow_zipf():
    tokens = make(5, "packed-s1024-b16", vocab=50257, eod=50256).rows(0)[0]
    counts = np.bincount(tokens.ravel(), minlength=50257)
    assert counts[0] > counts[1] > counts[3] > counts[20]
    assert tokens.max() < 50257


# ------------------------------------------------ a corpus, trained on for epochs
def test_a_corpus_is_the_same_rows_whatever_the_seed():
    a, b = make(7, "epochs8-s8192-b1"), make(2251000011, "epochs8-s8192-b1")
    assert a.dataset_batches == 8
    for j in range(8):
        assert np.array_equal(a.batch(j)["tokens"], b.batch(j)["tokens"])
        assert np.array_equal(a.batch(j)["tokens"],
                              a.batch(j + 8)["tokens"])  # it repeats
    distinct = {a.batch(j)["tokens"].tobytes() for j in range(8)}
    assert len(distinct) == 8
    # the check rows stay the seed's: no step trains on them
    assert not np.array_equal(a.check_rows(1), b.check_rows(1))
    # a stream (no dataset_batches) is the seed's from its first row
    assert make(7, "packed-s8192-b1").dataset_batches is None


def _epoch_orders(seed, epochs):
    """The order in which the program's ingest hands out the corpus's rows,
    epoch by epoch, as kinds/train.py's loop asks for them."""
    from ray_tpu.data.ingest import StreamingIngest

    gen = make(seed, "epochs8-s8192-b1")
    rows = {gen.batch(j)["tokens"].tobytes(): j for j in range(8)}
    shard = StreamingIngest(gen.dataset(171), seed=seed).make_shard()
    return [[rows[np.asarray(b["tokens"]).tobytes()]
             for b in shard.iter_batches(batch_size=1)]
            for _ in range(epochs)]


def test_every_epoch_is_every_row_once_in_the_seeds_order():
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    try:
        first, again, other = (_epoch_orders(s, 3) for s in (7, 7, 8))
    finally:
        ray_tpu.shutdown()
    assert all(sorted(epoch) == list(range(8)) for epoch in first + other)
    assert first == again                   # the same seed, the same order
    assert first != other                   # another seed, another order
    assert first[0] != first[1]             # and another in every epoch
