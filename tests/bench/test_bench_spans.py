"""The serving loop's spans against the device trace (``bench/spans.py``),
and the readers of the loop's stamps and phases, on records and traces
with known values."""

import gzip
import math
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import spans as bspans
from bench.metrics import (decode_occupancy, decode_step_ms, host_gap_ms,
                           itl_p90_ms, park_share, queue_wait_p50_s)

# device ops [start, end) in ns: 0-400, 1000-3000, 5000-9000, 10000-11000,
# 14000-15000, so the holes are 400-1000, 3000-5000, 9000-10000 and
# 11000-14000. Host spans: step 0 (500-12000) holds admit 600-900, a chunk
# 900-3500, decode 4000-10500 and complete 10500-11800; an idle wait
# 12000-13000; step 1 (13500-15500, past the last op) holds a decode
# 13600-15200. Idle is read from 500, where the first step starts, to
# 15000, where the last op ends: 6500 ns.
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 14000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 400000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 5000000 duration_ps: 6000000 }
    events { metadata_id: 7 offset_ps: 14000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "tsl_paged_attention" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_serve_reset(444)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_serve_chunk(111)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_serve_decode_paged(222)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_serve_decode_paged(333)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 11500000
             stats { metadata_id: 1 int64_value: 0 }
             stats { metadata_id: 2 int64_value: 1 }
             stats { metadata_id: 3 int64_value: 1 } }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 900000 duration_ps: 2600000
             stats { metadata_id: 4 str_value: "r1" }
             stats { metadata_id: 5 int64_value: 0 } }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 6500000 }
    events { metadata_id: 5 offset_ps: 10500000 duration_ps: 1300000 }
    events { metadata_id: 6 offset_ps: 12000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 13500000 duration_ps: 2000000
             stats { metadata_id: 1 int64_value: 1 }
             stats { metadata_id: 2 int64_value: 0 }
             stats { metadata_id: 3 int64_value: 1 } }
    events { metadata_id: 4 offset_ps: 13600000 duration_ps: 1600000 }
    events { metadata_id: 7 offset_ps: 4100000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serve.step" } }
  event_metadata { key: 2 value { id: 2 name: "serve.admit" } }
  event_metadata { key: 3 value { id: 3 name: "serve.prefill_chunk" } }
  event_metadata { key: 4 value { id: 4 name: "serve.decode" } }
  event_metadata { key: 5 value { id: 5 name: "serve.complete" } }
  event_metadata { key: 6 value { id: 6 name: "serve.wait_arrival" } }
  event_metadata { key: 7 value { id: 7 name: "PjitFunction(serve_decode_paged)" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "chunks" } }
  stat_metadata { key: 3 value { id: 3 name: "decoded" } }
  stat_metadata { key: 4 value { id: 4 name: "rid" } }
  stat_metadata { key: 5 value { id: 5 name: "chunk" } }
}
'''


@pytest.fixture
def spans_file(tmp_path):
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE)
    path = tmp_path / "t.xplane.pb.gz"
    path.write_bytes(gzip.compress(raw))
    return path


def test_idle_is_split_by_the_innermost_span(spans_file):
    s = bspans.reduce_spans(spans_file)
    assert s.idle_s == pytest.approx(6500e-9)
    want = {"outside": 500, "serve.step": 900, "serve.admit": 300,
            "serve.prefill_chunk": 600, "serve.decode": 2400,
            "serve.complete": 800, "serve.wait_arrival": 1000}
    assert s.idle_by_span == {k: pytest.approx(v * 1e-9)
                              for k, v in want.items()}
    assert s.idle_in("serve.step") == pytest.approx(5000e-9)


def test_programs_and_the_step_gap(spans_file):
    s = bspans.reduce_spans(spans_file)
    assert s.program_s == {
        "jit_serve_reset": {"n": 1, "s": pytest.approx(400e-9)},
        "jit_serve_chunk": {"n": 1, "s": pytest.approx(2000e-9)},
        "jit_serve_decode_paged": {"n": 2, "s": pytest.approx(7000e-9)}}
    # step 1 ends past the last device op: only step 0 is whole
    assert s.step_idle_s == [pytest.approx(4500e-9)]
    assert s.host_gap_ms == pytest.approx(4500e-6)
    steps = [a for n, _, _, a in s.spans if n == "serve.step"]
    assert steps == [{"step": 0, "chunks": 1, "decoded": 1},
                     {"step": 1, "chunks": 0, "decoded": 1}]
    assert {"rid": "r1", "chunk": 0} in [a for n, _, _, a in s.spans]
    assert host_gap_ms.read("host_gap_ms.chat", {"spans": s}) == \
        pytest.approx(4500e-6)
    # two runs of the decode program, 7000 ns on the device
    assert decode_step_ms.read("decode_step_ms.chat", {"spans": s}) == \
        pytest.approx(3500e-6)


def test_a_trace_without_a_device_plane_reads_nothing(tmp_path):
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        XSPACE.split("planes {\n  id: 2")[0])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    assert bspans.reduce_spans(path) is None       # no serve.step span
    assert host_gap_ms.read("host_gap_ms.chat", {"spans": None}) is None
    assert decode_step_ms.read("decode_step_ms.chat", {"spans": None}) is None


def _record():
    """Three requests in a 10-s window (one never served) and one after it,
    with the program's stamps; 6 slots, 10 decode steps, 8 of them in the
    traced window."""
    reqs = [
        {"rid": "a", "arrival_s": 1.0, "tokens_out": 4, "ttft_s": 0.5,
         "latency_s": 2.5, "queue_s": 0.2, "park_s": 1.0,
         "token_s": [1.5, 1.6, 2.8, 3.5]},
        {"rid": "b", "arrival_s": 2.0, "tokens_out": 3, "ttft_s": 1.0,
         "latency_s": 2.0, "queue_s": 0.4, "park_s": 0.0,
         "token_s": [3.0, 3.3, 4.0]},
        {"rid": "c", "arrival_s": 3.0},
        {"rid": "d", "arrival_s": 11.0, "tokens_out": 2, "ttft_s": 0.1,
         "latency_s": 5.0, "queue_s": 0.0, "park_s": 4.0,
         "token_s": [11.1, 16.0]},
    ]
    return {"seconds": 10.0, "requests": reqs, "slots": 6,
            "decode_steps": 10, "decode_slot_steps": 45,
            "phases": {"decode": {"n": 8, "s": 0.96},
                       "step": {"n": 10, "s": 1.5}},
            "spans": SimpleNamespace(host_gap_ms=4.5, program_s={
                "jit_serve_chunk": {"n": 3, "s": 0.3},
                "jit_serve_decode_paged": {"n": 8, "s": 0.48}})}


def test_the_readers_on_a_record_with_known_values():
    rec = _record()
    # queue waits 0.2, 0.4 and never (c): the median is 0.4
    assert queue_wait_p50_s.read("queue_wait_p50_s.chat", rec) == 0.4
    # parked 1.0 of the (2.0 + 1.0) s between first and last tokens
    assert park_share.read("park_share.chat", rec) == \
        pytest.approx(100 * 1.0 / 3.0)
    # gaps 0.1, 1.2, 0.7, 0.3, 0.7: nearest-rank p90 of 5 is the largest
    assert itl_p90_ms.read("itl_p90_ms.chat", rec) == pytest.approx(1200)
    assert decode_occupancy.read("decode_occupancy.chat", rec) == \
        pytest.approx(75.0)
    # the decode program's device time, not the chunk program's
    assert decode_step_ms.read("decode_step_ms.chat", rec) == \
        pytest.approx(60.0)
    assert host_gap_ms.read("host_gap_ms.chat", rec) == 4.5


def test_the_readers_read_nothing_from_a_program_without_stamps():
    """A program that records no stamps, phases or counts (as before they
    existed) gives every reader nothing to read, and none raises."""
    rec = _record()
    for r in rec["requests"]:
        for k in ("queue_s", "park_s", "token_s"):
            r.pop(k, None)
    for k in ("slots", "decode_steps", "decode_slot_steps", "phases",
              "spans"):
        rec.pop(k)
    for reader in (queue_wait_p50_s, park_share, itl_p90_ms,
                   decode_occupancy, decode_step_ms, host_gap_ms):
        assert reader.read("x.chat", rec) is None
    # a request never reserved reads as infinitely late
    rec = _record()
    rec["requests"] = rec["requests"][:1] + [
        {"rid": "c", "arrival_s": 3.0}, {"rid": "e", "arrival_s": 4.0}]
    assert queue_wait_p50_s.read("queue_wait_p50_s.chat", rec) == math.inf


# one second of the chat cell traced on one TPU v5e with the serving loop's
# spans (the profiler's XSpace, gzipped, as ``--keep-trace`` keeps it)
CHIP_SPANS = Path(bspans.__file__).parent / "testdata" / \
    "chat_v5e_spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip():
    return bspans.reduce_spans(CHIP_SPANS)


def _within(spans, name, t):
    return [(s, e, a) for n, s, e, a in spans if n == name and s <= t < e]


def test_the_chip_trace_shares_one_clock(chip):
    """Every run of the decode program between the first whole step and the
    last starts inside a serve.decode span, each in a step of its own."""
    steps = [(s, e) for n, s, e, _ in chip.spans if n == "serve.step"]
    lo, hi = steps[0][0], steps[-1][1]
    runs = [s for p, s, _ in chip.modules
            if p == "jit_serve_decode_paged" and lo <= s < hi]
    assert len(runs) >= 4
    owners = []
    for t in runs:
        dec = _within(chip.spans, "serve.decode", t)
        assert len(dec) == 1, t
        step = _within(chip.spans, "serve.step", dec[0][0])
        owners.append(step[0][2]["step"])
    assert len(set(owners)) == len(runs)


def test_the_chip_trace_names_every_engine_program(chip):
    assert "jit__lambda" not in chip.program_s
    for p in ("jit_serve_decode_paged", "jit_serve_sample",
              "jit_serve_page_write"):
        assert chip.program_s[p]["n"] > 0
    dec = chip.program_s["jit_serve_decode_paged"]
    assert 0.05 < dec["s"] / dec["n"] < 0.5         # a decode step's seconds
    assert decode_step_ms.read("decode_step_ms.chat", {"spans": chip}) == \
        pytest.approx(1e3 * dec["s"] / dec["n"])


def test_the_chip_trace_idle_falls_inside_steps(chip):
    """The host gap lies between 0 and the step period, and nearly all the
    device's idle falls inside a serve.step span."""
    periods = [e - s for n, s, e, _ in chip.spans if n == "serve.step"]
    period_ms = 1e-6 * sum(periods) / len(periods)
    assert 0 < chip.host_gap_ms < period_ms
    assert chip.idle_in("serve.step") >= 0.95 * chip.idle_s
    assert chip.idle_by_span["outside"] < 0.05 * chip.idle_s
