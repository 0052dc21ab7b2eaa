"""Executor host stages (core/executor, core/rerank): share of the
device's idle time in the traced window during which some host thread was
inside an ``executor.rerank`` span (merge, re-rank against the SSD tier
and delta merge of a window, stage ⑦)."""

import hostspans


def read(run):
    return hostspans.read_share(run, "executor.rerank")
