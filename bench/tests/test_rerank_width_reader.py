"""The reader of ``rerank_width``: the mean over answered queries, and
nothing on answers that lack the field, as a program without the counter
gives."""

import types

import spec


def _run(stats):
    return types.SimpleNamespace(answers=[types.SimpleNamespace(stats=s)
                                          for s in stats])


def test_reader_means_the_counter_or_gives_nothing():
    read = spec.metric_reader("rerank_width.sat")
    have = [types.SimpleNamespace(rerank_width=v) for v in (1.0, 8.0, 6.0)]
    assert read(_run(have)) == 5.0
    assert read(_run([types.SimpleNamespace(ios=1)] * 3)) is None
    assert read(_run(have + [types.SimpleNamespace(ios=1)])) is None
    assert read(_run([])) is None
