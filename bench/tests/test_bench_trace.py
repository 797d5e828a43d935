"""The trace reduction on a small synthetic trace whose numbers are worked
out by hand."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from tracereduce import reduce  # noqa: E402

# device 0: fusion.1 over [1, 3) us, a Pallas kernel's custom call over
# [2, 4) us and fusion.1 again over [6, 7) us; the window is [0, 10) us; the host runs
# bench.query over [4, 10) us and a jitted call over [5, 6) us inside it
TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%custom-call.1 = f32[512,3]{1,0} custom-call(s32[1,1024]{1,0} %a, f32[3,1024]{1,0} %b), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "jit_query" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.query" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(query)" } }
}
'''


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(TRACE)


def test_busy_is_the_union_of_device_ops(trace, monkeypatch):
    import tracereduce

    monkeypatch.setattr(tracereduce, "SHORT_GAP_NS", 0)
    r = reduce(trace, "bench.window")
    assert r["window_s"] == pytest.approx(10e-6)
    # [1, 4) and [6, 7): 4 us busy of 10, the module line does not count
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["devices"] == 1


def test_top_ops_sum_by_name(trace):
    r = reduce(trace, "bench.window")
    assert r["top_ops"] == [["fusion.1:fusion", pytest.approx(3e-6)],
                            ["custom-call.1:custom-call", pytest.approx(2e-6)]]


def test_idle_gaps_labelled_by_innermost_host_span(trace, monkeypatch):
    import tracereduce

    monkeypatch.setattr(tracereduce, "SHORT_GAP_NS", 0)
    r = reduce(trace, "bench.window")
    # gaps [0,1) mid 0.5 -> window only; [4,6) mid 5 -> PjitFunction;
    # [7,10) mid 8.5 -> bench.query
    gaps = dict((n, t) for n, t in r["idle_gaps"])
    assert gaps == {"no host span": pytest.approx(1e-6),
                    "PjitFunction(query)": pytest.approx(2e-6),
                    "bench.query": pytest.approx(3e-6)}


def test_short_gaps_are_summed_apart(trace):
    r = reduce(trace, "bench.window")
    assert r["idle_gaps"] == [["gaps under 10 us", pytest.approx(6e-6)]]


def test_op_label():
    from tracereduce import op_label

    assert op_label("%while.48 = (s32[]{:T(128)}, f32[4]{0}) while((s32[]"
                    "{:T(128)}, f32[4]{0}) %tuple.1), condition=%c") \
        == "while.48:while"
    assert op_label("%iota.12 = s32[8]{0:T(1024)S(1)} iota(), "
                    "iota_dimension=0") == "iota.12:iota"
    assert op_label("plain name") == "plain name"


def test_missing_window_is_an_error(trace):
    with pytest.raises(ValueError):
        reduce(trace, "bench.nowhere")


def test_union_seconds_clips_and_merges():
    import harness

    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 9.0), (11.0, 12.0)]
    # [0, 3) and [5, 9) clipped to [1, 8): 2 + 3
    assert harness.union_seconds(spans, 1.0, 8.0) == pytest.approx(5.0)
    assert harness.union_seconds([], 0.0, 1.0) == 0.0
