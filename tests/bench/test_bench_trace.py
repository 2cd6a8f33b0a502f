"""The trace reduction on a small trace written in the profiler's own
format (XSpace): one TPU plane whose ops overlap and leave gaps, and a host
thread whose event spans one of the gaps."""

import gzip
from pathlib import Path

import jax
import pytest

from bench import trace

# ops on the device, [start, end) in ns: paged 1000-3000, fusion 2000-4000,
# paged 6000-7000, fusion 9000-9500; host event 3500-6500 spans the gap
# 4000-6000; the gap 7000-9000 lies after the host event ends
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 500000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "tsl_paged_attention" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_step" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction(step)" } }
}
'''


@pytest.fixture(params=["plain", "gz"])
def trace_file(request, tmp_path):
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE)
    if request.param == "gz":
        path = tmp_path / "t.xplane.pb.gz"
        path.write_bytes(gzip.compress(raw))
    else:
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(raw)
    return path


def test_reduce_counts_busy_time_ops_and_gaps(trace_file):
    s = trace.reduce(trace_file, window_s=10e-6)
    assert s.devices == 1                    # the modules line is not ops
    assert s.busy_s == pytest.approx(3000e-9 + 1000e-9 + 500e-9)
    assert s.kernel_count("tsl_paged_attention") == 2
    assert s.kernel_s("tsl_paged_attention") == pytest.approx(3000e-9)
    assert s.op_s["fusion.3"] == pytest.approx(2500e-9)
    assert s.top_ops()[0] == ("tsl_paged_attention", pytest.approx(3e-6))
    assert [g for _, g in s.idle_gaps] == [pytest.approx(2000e-9)] * 2
    labels = [name for name, _ in s.idle_gaps]
    assert "PjitFunction(step) [python3]" in labels
    assert "after PjitFunction(step) [python3]" in labels


def test_union_and_gaps_of_overlapping_intervals():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 41)]
    assert trace.union_length(iv) == 15 + 11 + 1
    assert trace.gaps(iv) == [(15, 20), (31, 40)]
    assert trace.union_length([]) == 0


# a device trace of the chat cell on one TPU v5e (the profiler's XSpace,
# gzipped, as ``--keep-trace`` keeps it), with the traced window's length as
# the run read it on the host clock
CHIP_TRACE = Path(trace.__file__).parent / "testdata" / "chat_v5e.xplane.pb.gz"
CHIP_WINDOW_S = 3.000384909000104


def test_reduce_reads_a_chip_trace():
    s = trace.reduce(CHIP_TRACE, CHIP_WINDOW_S)
    assert s.devices == 1
    assert 0 < s.busy_s < CHIP_WINDOW_S
    # the decode kernel by the name the device trace gives it
    assert s.kernel_count("tsl_paged_attention") > 0
    assert 0 < s.kernel_s("tsl_paged_attention") < s.busy_s
    assert len(s.top_ops()) == trace.TOP
    assert len(s.idle_gaps) == trace.TOP
    assert all(g > 0 for _, g in s.idle_gaps)
