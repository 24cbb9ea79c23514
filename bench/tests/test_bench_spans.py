"""The program's host spans on a small synthetic trace."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spans  # noqa: E402
import trace_reduce  # noqa: E402

# Times in us. Window 0-100. Client line: map.batch 5-95 holding seed
# 5-20, chain 20-40, submit 40-50 and await 50-90 (a wait); a chain span
# 95-105 runs past the window. Dispatcher line: flush 45-60 holding
# enqueue 47-55; finalize 70-85 holding fetch 72-80 and decode 80-84.
# Device 0 runs two ops, 10-30 and 25-35, and a kernel 55-70; device 1
# is busy all window. Proto times: ps from the line's ns stamp.
TRACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 15000000
      stats { metadata_id: 20 int64_value: 2 } }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 50000000 duration_ps: 40000000
      stats { metadata_id: 21 int64_value: 1 } }
    events { metadata_id: 4 offset_ps: 95000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 45000000 duration_ps: 15000000
      stats { metadata_id: 22 str_value: "fill" }
      stats { metadata_id: 23 int64_value: 4 }
      stats { metadata_id: 24 int64_value: 40 } }
    events { metadata_id: 8 offset_ps: 47000000 duration_ps: 8000000
      stats { metadata_id: 23 int64_value: 4 }
      stats { metadata_id: 25 int64_value: 2 } }
    events { metadata_id: 9 offset_ps: 70000000 duration_ps: 15000000
      stats { metadata_id: 25 int64_value: 1 } }
    events { metadata_id: 10 offset_ps: 72000000 duration_ps: 8000000 }
    events { metadata_id: 11 offset_ps: 80000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "rapidx.map.batch" } }
  event_metadata { key: 3 value { id: 3 name: "rapidx.map.seed" } }
  event_metadata { key: 4 value { id: 4 name: "rapidx.map.chain" } }
  event_metadata { key: 5 value { id: 5 name: "rapidx.map.submit" } }
  event_metadata { key: 6 value { id: 6 name: "rapidx.map.await" } }
  event_metadata { key: 7 value { id: 7 name: "rapidx.serve.flush" } }
  event_metadata { key: 8 value { id: 8 name: "rapidx.serve.enqueue" } }
  event_metadata { key: 9 value { id: 9 name: "rapidx.serve.finalize" } }
  event_metadata { key: 10 value { id: 10 name: "rapidx.serve.fetch" } }
  event_metadata { key: 11 value { id: 11 name: "rapidx.serve.decode" } }
  stat_metadata { key: 20 value { id: 20 name: "reads" } }
  stat_metadata { key: 21 value { id: 21 name: "wait" } }
  stat_metadata { key: 22 value { id: 22 name: "cause" } }
  stat_metadata { key: 23 value { id: 23 name: "pairs" } }
  stat_metadata { key: 24 value { id: 24 name: "wait_us_sum" } }
  stat_metadata { key: 25 value { id: 25 name: "builds" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000
      stats { metadata_id: 9 str_value: "jit(chain_anchors)/rapidx.chain/while" } }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 10000000
      stats { metadata_id: 9 str_value: "jit(chain_anchors)/rapidx.chain/add" } }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 15000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "tpu_custom_call.1" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "kernel.1" } }
}
"""

US = 1e-6


@pytest.fixture(scope="module")
def traced():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(TRACE)
    window = spans.window_ns(profile)
    return (trace_reduce.reduce(profile), spans.program_spans(profile, window),
            window)


def test_program_spans_are_clipped_to_the_window(traced):
    _, got, window = traced
    assert window == (1000, 101000)
    assert len(got) == 11
    assert {sp[0] for sp in got if sp[3].startswith("rapidx.map.")} == {0}
    assert {sp[0] for sp in got if sp[3].startswith("rapidx.serve.")} == {1}
    late = [sp for sp in got if sp[1] == 96000]
    assert late == [(0, 96000, 101000, "rapidx.map.chain", {})]
    flush = next(sp for sp in got if sp[3] == "rapidx.serve.flush")
    assert flush[4] == {"cause": "fill", "pairs": 4, "wait_us_sum": 40}


def test_self_time_leaves_out_the_child_spans(traced):
    _, got, _ = traced
    want = {"rapidx.map.batch": 5, "rapidx.map.seed": 15,
            "rapidx.map.chain": 20 + 5, "rapidx.map.submit": 10,
            "rapidx.map.await": 40, "rapidx.serve.flush": 7,
            "rapidx.serve.enqueue": 8, "rapidx.serve.finalize": 3,
            "rapidx.serve.fetch": 8, "rapidx.serve.decode": 4}
    assert spans.self_seconds(got) == pytest.approx(
        {k: v * US for k, v in want.items()})
    # The client line's stages add up to its whole window inside spans.
    client = [iv for iv in spans.self_intervals(got) if iv[0] == 0]
    assert sum(e - s for _, s, e, _, _ in client) == 95000


def test_stat_sums(traced):
    _, got, _ = traced
    assert spans.stat_sum(got, "rapidx.serve.enqueue", "builds") == 2
    assert spans.stat_sum(got, "rapidx.serve.finalize", "builds") == 1
    assert spans.stat_sum(got, "rapidx.serve.flush", "wait_us_sum") == 40
    assert spans.stat_sum(got, "rapidx.serve.flush", "pairs") == 4


def test_idle_is_attributed_to_the_working_stages(traced):
    reduced, got, window = traced
    # Device 0 idles 0-10, 35-55 and 70-100 us; device 1 never, so each
    # figure is half device 0's. Line 0 waits in map.await 50-90; the
    # gaps 0-5 and 85-90 have no working stage.
    want = {"none": 10, "map.seed": 5, "map.chain": 5 + 5,
            "map.submit": 5, "map.submit+serve.flush": 2,
            "map.submit+serve.enqueue": 3, "serve.enqueue": 5,
            "serve.finalize": 3, "serve.fetch": 8, "serve.decode": 4,
            "map.batch": 5}
    assert spans.idle_by_stage(reduced, got, window) == pytest.approx(
        {k: v * US / 2 for k, v in want.items()})
    assert spans.idle_explained_share(reduced, got, window) == \
        pytest.approx(50 / 60)


def test_a_gap_covered_only_by_a_wait_span_is_unexplained(traced):
    reduced, got, window = traced
    waits = [(0, 36000, 56000, "rapidx.map.await", {"wait": 1})]
    assert spans.idle_by_stage(reduced, waits, window) == pytest.approx(
        {"none": 60 * US / 2})
    assert spans.idle_explained_share(reduced, waits, window) == 0.0

