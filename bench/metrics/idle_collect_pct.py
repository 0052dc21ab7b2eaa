"""Executor host stages (core/executor, core/navgraph): share of the
device's idle time in the traced window during which some host thread was
inside an ``executor.collect`` span (graph traversal, candidate
collection and union, stages ①-③)."""

import hostspans


def read(run):
    return hostspans.read_share(run, "executor.collect")
