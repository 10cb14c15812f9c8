import numpy as np

import corpus


def small(**kw):
    args = dict(vocab=400, topics=12, nodes=6, shared=3, docs_per_node=40,
                val_docs_per_node=5, seed=2 ** 31 + 5)
    args.update(kw)
    return corpus.generate(**args)


def test_lengths_lie_in_range_and_counts_sum_to_them():
    c = small(len_range=(150, 250))
    lengths = c.concat_bows().sum(axis=1)
    assert lengths.min() >= 150 and lengths.max() <= 250
    assert np.all(c.concat_bows() == np.round(c.concat_bows()))
    assert len(c.node_bows) == 6 and c.node_bows[0].shape == (40, 400)
    assert c.node_val_bows[5].shape == (5, 400)


def test_documents_use_only_the_node_topics_words():
    c = small(eta=0.001)
    for l, topics in enumerate(c.node_topics):
        support = (c.beta[topics] > 1e-4).any(axis=0)
        words = c.node_bows[l].sum(axis=0) > 0
        assert (~support & words).sum() <= 0.02 * words.sum()


def test_topic_split_matches_the_program():
    from repro.data.synthetic_lda import make_federated_topic_split
    for k, shared, nodes in ((50, 10, 1000), (50, 10, 5), (12, 3, 4)):
        a = corpus.topic_split(k, shared, nodes, np.random.default_rng(7))
        b = make_federated_topic_split(k, shared, nodes,
                                       np.random.default_rng(7))
        assert np.array_equal(a[0], b[0])
        assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))


def test_same_seed_same_corpus_whatever_the_threads():
    a, b = small(threads=1), small(threads=3)
    assert np.array_equal(a.concat_bows(), b.concat_bows())
    assert not np.array_equal(a.concat_bows(), small(seed=3).concat_bows())
