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


# two chips' ops under their HLO text, as a TPU trace names them: a kernel
# call and the fusion that reads its output, collectives whole and in
# asynchronous halves (one renamed, known by its opcode), and ops that only
# read a collective's output or share a word with one
def _hlo_op(key, name):
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "{name}" }} }}')


HLO_OPS = [
    "%tsl_paged_attention.9 = bf16[8,16,8,64]{3,2,1,0} custom-call("
    "s32[8,24]{1,0} %copy-done.1), custom_call_target=tpu_custom_call",
    "%slice_reduce_fusion.2 = bf16[8,16,64]{2,1,0} fusion(bf16[8,16,8,64]"
    "{3,2,1,0} %tsl_paged_attention.9), kind=kLoop",
    "%all-reduce.3 = f32[8,1024]{1,0} all-reduce(f32[8,1024]{1,0} "
    "%fusion.1), replica_groups={{0,1,2,3}}, to_apply=%add",
    "%all-gather-start.1 = (bf16[2,64]{1,0}, bf16[8,64]{1,0}) "
    "all-gather-start(bf16[2,64]{1,0} %p.1), dimensions={0}",
    "%all-gather-done.1 = bf16[8,64]{1,0} all-gather-done((bf16[2,64]{1,0}, "
    "bf16[8,64]{1,0}) %all-gather-start.1)",
    "%fusion.7 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} "
    "%all-gather-done.1), kind=kLoop",
    "%reduce.5 = f32[8]{0} reduce(f32[8,64]{1,0} %x, f32[] %c), "
    "dimensions={1}, to_apply=%add",
    "%tp_sum = f32[8]{0:T(128)} reduce-scatter(f32[32]{0} %y), "
    "dimensions={0}",
    "%collective-permute-start.2 = (f32[8]{0}, f32[8]{0}) "
    "collective-permute-start(f32[8]{0} %z), source_target_pairs={{0,1}}",
    "all-to-all.4",
]
# (metadata id, duration ns) per chip; collectives are ids 3, 4, 5, 8, 9, 10
HLO_CHIPS = [[(1, 3000), (2, 200), (3, 1000), (4, 400), (5, 100), (6, 300),
              (7, 50), (8, 70)],
             [(1, 2000), (2, 100), (3, 500), (9, 30), (10, 20), (6, 10)]]
HLO_COLLECTIVE_NS = [1000 + 400 + 100 + 70, 500 + 30 + 20]


def _hlo_xspace():
    planes = []
    for n, chip in enumerate(HLO_CHIPS):
        t, events = 0, []
        for key, dur in chip:
            events.append(f"events {{ metadata_id: {key} offset_ps: {t} "
                          f"duration_ps: {dur * 1000} }}")
            t += dur * 1000
        planes.append(
            f'planes {{ id: {n + 1} name: "/device:TPU:{n}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 '
            f'{" ".join(events)} }} '
            + " ".join(_hlo_op(k + 1, op) for k, op in enumerate(HLO_OPS))
            + " }")
    return "\n".join(planes)


@pytest.fixture
def hlo_trace(tmp_path):
    path = tmp_path / "hlo.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            _hlo_xspace()))
    return path


def test_a_fusion_reading_the_kernel_is_not_a_call(hlo_trace):
    s = trace.reduce(hlo_trace, window_s=10e-6)
    assert s.devices == 2
    # one call on each chip; slice_reduce_fusion.2 names the kernel among
    # its operands and is left out
    assert s.kernel_count("tsl_paged_attention") == 2
    assert s.kernel_s("tsl_paged_attention") == pytest.approx(5000e-9)
    assert s.kernel_count("slice_reduce_fusion") == 2


def test_collective_time_sums_the_collectives_and_nothing_else(hlo_trace):
    s = trace.reduce(hlo_trace, window_s=10e-6)
    assert s.collective_s == pytest.approx(
        sum(HLO_COLLECTIVE_NS) * 1e-9 / len(HLO_CHIPS))
    got = sorted(trace.own_name(op) for op in s.op_s
                 if trace.is_collective(op))
    assert got == ["all-gather-done.1", "all-gather-start.1", "all-reduce.3",
                   "all-to-all.4", "collective-permute-start.2", "tp_sum"]


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
    # one chip: no collective runs
    assert s.collective_s == 0
    assert len(s.top_ops()) == trace.TOP
    assert len(s.idle_gaps) == trace.TOP
    assert all(g > 0 for _, g in s.idle_gaps)
