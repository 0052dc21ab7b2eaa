"""Stage spans and counters of the serving path.

Contract under test:
* ``QueryStats.cpu_graph`` / ``cpu_rerank`` are thread CPU time over the
  intervals ``t_graph`` / ``t_rerank`` time: above 0, and never more than
  the wall time of the same interval;
* a profiler trace around a threaded ``BatchingANNSService`` batch holds
  the ``service.batch`` span and the five ``executor.*`` stage spans; the
  stage spans on the pump thread lie inside ``service.batch``, and every
  span carries its batch's identifier (stage spans also the window's);
* ``SearchResponse.ticker_polls`` is the batch's ``BatchTicket.polls``;
* the LUT build is one jitted program with the eager build's tables.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import pq
from repro.core.executor import QueryExecutor
from repro.serve.anns_service import BatchingANNSService
from repro.serve.client import SearchRequest

STAGES = ("executor.collect", "executor.lut", "executor.scan",
          "executor.scan_wait", "executor.rerank")
SPANS = ("service.batch",) + STAGES


def _serve_one_batch(index, queries, **kw):
    """Serve ``queries`` as one batch of a threaded service with its own
    executor; returns (responses, tickets the executor handed out)."""
    ex = QueryExecutor(index)
    tickets = []
    submit = ex.submit

    def spy(*a, **k):
        tickets.append(submit(*a, **k))
        return tickets[-1]

    ex.submit = spy
    svc = BatchingANNSService(index, executor=ex, threaded=True,
                              max_batch=len(queries), max_wait_s=30.0, **kw)
    try:
        futs = [svc.submit(SearchRequest(query=q, k=10)) for q in queries]
        resps = [f.result(timeout=120) for f in futs]
    finally:
        svc.stop()
    return resps, tickets


@pytest.mark.parametrize("fused", [False, True])
def test_cpu_counters_within_wall_time(anns_bundle, fused):
    b = anns_bundle
    res = b.index.executor.run(b.queries[:8],
                               b.index.plan(window=4, fused=fused))
    for r in res:
        s = r.stats
        assert 0 < s.cpu_graph <= s.t_graph + 1e-3
        assert 0 < s.cpu_rerank <= s.t_rerank + 1e-3


@pytest.fixture(scope="module")
def traced_batch(anns_bundle, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        resps, tickets = _serve_one_batch(
            anns_bundle.index, anns_bundle.queries[:8], scan_window=2,
            inflight_depth=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    lines = []              # per host line: [(name, start, end, stats)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name in SPANS]
            if evs:
                lines.append(evs)
    return resps, tickets, lines


def test_trace_holds_every_span(traced_batch):
    _, _, lines = traced_batch
    names = {ev[0] for evs in lines for ev in evs}
    assert names == set(SPANS)


def test_every_span_carries_its_batch(traced_batch):
    _, tickets, lines = traced_batch
    (ticket,) = tickets
    spans = [ev for evs in lines for ev in evs]
    assert all(st.get("batch") == ticket.batch_id
               for _, _, _, st in spans)
    windows = {st["window"] for name, _, _, st in spans if name in STAGES}
    assert windows == {0, 1, 2, 3}          # 8 queries, windows of 2


def test_pump_stage_spans_lie_inside_the_batch_span(traced_batch):
    _, _, lines = traced_batch
    (pump,) = [evs for evs in lines
               if any(ev[0] == "service.batch" for ev in evs)]
    (batch,) = [ev for ev in pump if ev[0] == "service.batch"]
    stages = [ev for ev in pump if ev[0] in STAGES]
    assert stages
    for _, s, e, _ in stages:
        assert batch[1] <= s <= e <= batch[2]


def test_ticker_polls_ride_on_the_responses(anns_bundle):
    resps, tickets = _serve_one_batch(
        anns_bundle.index, anns_bundle.queries[:8], scan_window=2,
        inflight_depth=2)
    (ticket,) = tickets
    assert all(r.ticker_polls >= 0 and r.batch_size == 8 for r in resps)
    assert sum(r.ticker_polls / r.batch_size for r in resps) == \
        pytest.approx(ticket.polls)
    # a finished ticket's poll does nothing and is not counted
    assert ticket.poll() is False
    assert ticket.polls == resps[0].ticker_polls


def test_lut_build_is_one_program_matching_the_eager_build(anns_bundle):
    cb = anns_bundle.index.codebook
    q = jax.numpy.asarray(anns_bundle.queries[:5])
    eager = jax.vmap(lambda x: pq.adc_lut(cb, x))(q)
    np.testing.assert_allclose(np.asarray(pq.adc_lut_batch(cb, q)),
                               np.asarray(eager), rtol=1e-6)
    assert "jit__adc_lut_batch" in \
        pq._adc_lut_batch.lower(cb.codebooks, q).as_text()
