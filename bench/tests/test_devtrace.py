"""The reduction from a profiler trace to device numbers."""

import glob
import os

import pytest
from jax.profiler import ProfileData

import devtrace

# one device with two programs and three ops, a host thread with two spans,
# and the benchmark's window span from 1,000 ns to 11,000 ns
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 2500000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10500000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 10500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_scan(17)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_lut(3)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 8200000 duration_ps: 600000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "rerank" } }
  event_metadata { key: 3 value { id: 3 name: "traverse" } }
}
"""


def test_busy_programs_and_gaps_are_clipped_to_the_window():
    s = devtrace.summarize(ProfileData.from_text_proto(TRACE))
    assert s.window_s == pytest.approx(10e-6)
    # ops inside [1000, 11000]: 1000-3000, 6000-8000 (merged), 10500-11000
    assert s.busy_s == pytest.approx(4.5e-6)
    assert s.program_s == pytest.approx({"jit_scan": 2.5e-6,
                                         "jit_lut": 2e-6})
    # gaps 3000-6000 (3 us, the re-rank span covers 2.5 us of it) and
    # 8000-10500 (2.5 us, the traversal span covers only 0.6 us of it)
    assert s.gaps == [("rerank", pytest.approx(3e-6)),
                      (devtrace.NO_EVENT, pytest.approx(2.5e-6))]
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "jit_scan"
    assert bd["idle_gaps"][0][0] == "rerank"


def test_a_trace_without_the_window_span_is_refused():
    txt = TRACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.summarize(ProfileData.from_text_proto(txt))


def test_a_trace_recorded_here_reduces(tmp_path):
    """A real trace from this machine's CPU: the window span is found and,
    with no device plane, nothing counts as device time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = devtrace.latest_xplane(str(tmp_path))
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True) == [path]
    s = devtrace.summarize(ProfileData.from_file(path))
    assert s.window_s > 0
    assert s.busy_s == 0.0
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s)
