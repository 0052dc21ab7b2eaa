"""The reduction from a profiler trace to the host stages' shares of the
device's idle time."""

import pytest
from jax.profiler import ProfileData

import hostspans

# window 1,000-21,000 ns; device ops leave it idle in 5,000-9,000 and
# 11,000-19,000 (12,000 ns).  The pump thread's line has a collect span
# (3,000 ns of it idle), a re-rank span (3,000 ns idle, its stats folded
# into its name) and the batch span, which is no stage; the ticker's line
# has a re-rank span that overlaps the pump's (together 5,000 ns idle), a
# scan wait across a busy stretch (2,000 ns idle) and a collect span
# outside the window.
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 19000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 18000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 3000000 }
  }
  lines {
    id: 2
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 13000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 8000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 22000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "service.batch" } }
  event_metadata { key: 3 value { id: 3 name: "executor.collect" } }
  event_metadata { key: 4 value { id: 4
    name: "executor.rerank#batch=7,window=0#" } }
  event_metadata { key: 5 value { id: 5 name: "executor.rerank" } }
  event_metadata { key: 6 value { id: 6 name: "executor.scan_wait" } }
}
"""


def test_idle_shares_per_stage_and_unstaged():
    shares = hostspans.idle_shares(ProfileData.from_text_proto(TRACE))
    assert shares == pytest.approx({
        "executor.collect": 25.0, "executor.lut": 0.0,
        "executor.scan": 0.0, "executor.scan_wait": 100 / 6,
        "executor.rerank": 250 / 6,
        # stage spans together cover 4,000-16,000: 9,000 ns of the idle
        hostspans.UNSTAGED: 25.0})


def test_a_trace_without_stage_spans_gives_nothing():
    txt = TRACE
    for stage in ("collect", "rerank", "scan_wait"):
        txt = txt.replace(f'name: "executor.{stage}', 'name: "other')
    assert hostspans.idle_shares(ProfileData.from_text_proto(txt)) is None


def test_the_run_trace_is_read_once(tmp_path, monkeypatch):
    """A trace recorded here under the harness's trace directory: with no
    device plane the whole window is idle, and the readers share one
    parse of it."""
    import jax

    import harness
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "cell"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("executor.collect", batch=1,
                                          window=0):
            sum(range(100_000))
    jax.profiler.stop_trace()
    first = hostspans.latest_shares()
    assert 0 < first["executor.collect"] < 100
    assert first["executor.collect"] + first[hostspans.UNSTAGED] == \
        pytest.approx(100)
    assert hostspans.latest_shares() is first


def test_readers_give_nothing_without_a_trace():
    class Run:
        trace = None
    assert hostspans.read_share(Run(), "executor.collect") is None
