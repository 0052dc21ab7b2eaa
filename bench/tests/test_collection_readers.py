"""The readers of ``lists_empty`` and ``candidates_collected``: the mean
over answered queries, and nothing on answers that lack the field, as a
program without the counters gives."""

import types

import pytest

import spec


def _run(stats):
    return types.SimpleNamespace(answers=[types.SimpleNamespace(stats=s)
                                          for s in stats])


@pytest.mark.parametrize("name", ["lists_empty.sat",
                                  "candidates_collected.sat"])
def test_reader_means_the_counter_or_gives_nothing(name):
    read = spec.metric_reader(name)
    field = name.split(".")[0]
    have = [types.SimpleNamespace(**{field: v}) for v in (0, 3, 6)]
    assert read(_run(have)) == 3.0
    assert read(_run([types.SimpleNamespace(ios=1)] * 3)) is None
    assert read(_run(have + [types.SimpleNamespace(ios=1)])) is None
    assert read(_run([])) is None

