"""The trace reduction (``bench/trace.py``), on a hand-written trace whose
answer is known and on a small trace recorded on a TPU v5e chip."""
from pathlib import Path

import pytest

from bench import trace

SYNTHETIC = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-gather.3" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "sample" } }
  event_metadata { key: 3 value { id: 3 name: "dispatch" } }
}
'''
RECORDED = Path(__file__).resolve().parent / "data"


def test_synthetic_trace():
    from jax.profiler import ProfileData
    s = trace.reduce(ProfileData.from_text_proto(SYNTHETIC))
    # window 1000..11000 ns; ops [1000,3000] (an op nested inside it),
    # [6000,7000]; the op at 13000 lies outside the window
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3e-6)
    assert s.collective_s == pytest.approx(1e-6)
    assert s.top_ops == [["fusion.1", pytest.approx(2.5e-6)],
                         ["all-gather.3", pytest.approx(1e-6)]]
    # gaps: [3000,6000] under 'sample'; [7000,11000] overlaps 'dispatch'
    # for 500 ns and nothing else, so it goes to 'dispatch'
    assert dict((k, v) for k, v in s.idle_by_span) == {
        "sample": pytest.approx(3e-6), "dispatch": pytest.approx(4e-6)}


def test_no_window_is_an_error():
    from jax.profiler import ProfileData
    with pytest.raises(RuntimeError, match="window"):
        trace.reduce(ProfileData.from_text_proto(
            SYNTHETIC.replace('name: "window"', 'name: "other"')))


@pytest.mark.parametrize("path", sorted(RECORDED.glob("*.xplane.pb")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    from jax.profiler import ProfileData
    s = trace.reduce(ProfileData.from_file(str(path)))
    assert s.device_planes and s.device_planes[0].startswith("/device:TPU")
    assert 0 < s.busy_s <= s.window_s
    assert s.top_ops and s.top_ops[0][1] > 0
    assert abs(s.busy_s + sum(v for _, v in s.idle_by_span)
               - s.window_s) < 1e-6 * s.window_s + 1e-9
