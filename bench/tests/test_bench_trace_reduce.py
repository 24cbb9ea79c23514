"""The trace reduction on a small synthetic trace."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402

# Host window 0-100 us; device 0 busy 10-30 and 25-40 (overlapping) and
# 70-80 us; device 1 busy 0-100 us. Host spans: bench.submit 40-60,
# bench.wait 55-100. Times in the proto are picoseconds from the line's
# timestamp (ns).
TRACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 40000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 45000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 15000000
      stats { metadata_id: 9 str_value: "jit(run)/jit(walker)/while" } }
    events { metadata_id: 1 offset_ps: 70000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "kernel.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_module" } }
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


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_text_proto(TRACE))


def test_window_and_busy_union(reduced):
    assert reduced["window_s"] == pytest.approx(100e-6)
    busy = {d["name"]: d["busy_s"] for d in reduced["devices"]}
    # 10-40 (the overlap counted once) + 70-80 = 40 us.
    assert busy["/device:TPU:0"] == pytest.approx(40e-6)
    assert busy["/device:TPU:1"] == pytest.approx(100e-6)


def test_op_time_by_name_sums_devices(reduced):
    assert reduced["op_s"]["kernel.1"] == pytest.approx(130e-6)
    assert reduced["op_s"]["fusion.2"] == pytest.approx(15e-6)
    assert "jit_module" not in reduced["op_s"]


def test_gaps_longest_first_and_labelled(reduced):
    # Device 0 idles 0-10, 40-70 and 80-100 us.
    assert [round(s * 1e6) for _, s in reduced["gaps"]] == [30, 20, 10]
    labels = [name for name, _ in reduced["gaps"]]
    # 40-70: bench.submit covers 40-60 (20 us), bench.wait 55-70 (15 us).
    assert labels == ["bench.submit", "bench.wait", "none"]


def test_ops_are_found_by_name_or_by_their_origin_stat(reduced, tmp_path,
                                                      monkeypatch):
    names = tmp_path / "names.json"
    names.write_text('{"banded_dp": ["^kernel"], "walker": ["jit\\\\(walker\\\\)"]}')
    monkeypatch.setattr(trace_reduce, "NAMES_FILE", names)
    assert trace_reduce.named_seconds(reduced, "banded_dp") == \
        pytest.approx(130e-6)
    assert trace_reduce.named_seconds(reduced, "walker") == \
        pytest.approx(15e-6)
    assert [n for n, _ in trace_reduce.top_ops(reduced)] == \
        ["kernel.1", "fusion.2"]


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    bare = TRACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError):
        trace_reduce.reduce(ProfileData.from_text_proto(bare))


def test_a_part_that_matches_no_op_is_refused(reduced, tmp_path,
                                              monkeypatch):
    names = tmp_path / "names.json"
    names.write_text('{"walker": ["^no_such_op"]}')
    monkeypatch.setattr(trace_reduce, "NAMES_FILE", names)
    with pytest.raises(ValueError, match="walker"):
        trace_reduce.named_seconds(reduced, "walker")
