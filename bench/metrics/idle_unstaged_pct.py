"""Service (serve/router, serve/anns_service): share of the device's idle
time in the traced window during which no host thread was inside any of
the executor's five stage spans: batching glue, pump waits, the ticker,
the client and the garbage collector."""

import hostspans


def read(run):
    return hostspans.read_share(run, hostspans.UNSTAGED)
