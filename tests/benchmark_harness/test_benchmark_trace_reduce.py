"""The reduction from a profiler trace to device busy/idle, ladder time
and the breakdown, on a synthetic trace in the profiler's own format."""

from __future__ import annotations

import pytest

import benchmark_harness_util  # noqa: F401  (puts the repo on sys.path)
from benchmark import trace as tracelib

# one TPU with two ops, a second TPU with one, and the host's window
# and flush annotations; times in ns from each line's timestamp
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "wei_ladder_windowed_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_verify" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "ed_ladder_kernel" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "flush" } }
  event_metadata { key: 3 value { id: 3 name: "pump.tick" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE)
    )
    return tracelib.reduce_profile(pd)


def test_busy_is_the_union_inside_the_window(reduced):
    # window 1000..11000 ns; TPU:0 busy 1000..4000 (the op at 21000 is
    # outside), TPU:1 busy 1000..2000: mean 2000 ns
    assert reduced.window_s == pytest.approx(10e-6)
    assert reduced.n_devices == 2
    assert reduced.busy_s == pytest.approx(2e-6)


def test_kernel_time_sums_the_ladder_events_in_the_window(reduced):
    assert reduced.kernel_s(r"ladder") == pytest.approx(3e-6)
    assert reduced.kernel_s(r"^fusion") == pytest.approx(1.5e-6)
    assert "jit_verify" not in reduced.op_s   # modules are not ops


def test_idle_gaps_are_named_by_the_covering_annotation(reduced):
    b = reduced.breakdown()
    assert b["device_ops"][0] == ["wei_ladder_windowed_kernel",
                                  pytest.approx(2e-6)]
    gaps = dict((round(t * 1e9), n) for n, t in b["idle_gaps"])
    # TPU:0 idles 4000..11000 and TPU:1 2000..11000: flush covers 2000
    # ns of each, pump.tick 5000 — over half of either gap
    assert gaps == {7000: "pump.tick", 9000: "pump.tick"}


def test_op_names_keep_the_instruction_and_custom_call_target():
    hlo = ('%_unknown_.1 = (s32[22,4096]{1,0}) custom-call(s32[22,4096] '
           '%copy.11), custom_call_target="tpu_custom_call", '
           'frontend_attributes={kernel_metadata={}}')
    assert tracelib.op_name(hlo) == "%_unknown_.1 tpu_custom_call"
    assert tracelib.op_name("%while.252 = (s32[]) while(...)") == "%while.252"


def test_peak_table_refuses_an_unknown_chip():
    assert tracelib.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tracelib.peaks("TPU v9 imaginary")
