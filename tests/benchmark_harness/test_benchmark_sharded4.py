"""The four-shard cell `p256_cash.sharded4` at a CPU size on four
virtual devices: its answers frame for frame against a one-shard copy
of its configuration and against the plain reference, its per-layer
readers on a traced run, the control, and the readers of the wave
regions and of the most idle chip on synthetic captures."""

from __future__ import annotations

import json
import os
import shutil

import pytest

import benchmark_harness_util  # noqa: F401  (puts the repo on sys.path)
from benchmark import fixture, harness
from benchmark_harness_util import run_cell

CELL = "p256_cash.sharded4"
ONE_SHARD = "one_shard.backlog"
# runs of 8 frames per issuance, as the configuration routes them; a
# warm-up that reaches every shard's device before the window
SIZE = {"shape": {"issue_fanout": 8}, "traffic": {"warmup_frames": 256}}


def _add_one_shard_copy(root):
    """The cell's configuration at one shard and one partition, as a
    one-chip cell of its own (files and manifest entries only)."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(
        bench, "configs", "notary_p256_cash_sharded4.json"
    )) as fh:
        cfg = json.load(fh)
    cfg["name"] = "one_shard"
    cfg["notary"]["shards"] = 1
    cfg["store"]["n_shards"] = 1
    with open(os.path.join(bench, "configs", "one_shard.json"), "w") as fh:
        json.dump(cfg, fh)
    man_p = os.path.join(root, "BENCHMARK.json")
    with open(man_p) as fh:
        man = json.load(fh)
    man["configs"].append({
        "name": "one_shard", "source": "https://example.org/x",
        "file": "benchmark/configs/one_shard.json",
        "reduced": ["committed_states"], "why": "test"})
    man["workloads"].append({
        "name": ONE_SHARD, "config": "one_shard", "traffic": "backlog",
        "chips": 1, "why": "test"})
    with open(man_p, "w") as fh:
        json.dump(man, fh)


class _HostVerifier:
    """The plain reference's EC standing in for a shard's chip in the
    traced run: a CPU capture of the ladders' XLA ops runs to
    gigabytes."""

    def __init__(self, ref, device):
        self.device = device
        self._verify = ref.verify

    def verify_batch(self, reqs):
        return [self._verify(r.key.scheme_id, r.key.data, r.signature,
                             r.message) for r in reqs]


def _host_verifiers(root):
    ref = harness.Cell(root, CELL).reference()

    def make(cfg, devices):
        return _HostVerifier(ref, None), [
            _HostVerifier(ref, devices[k % len(devices)])
            for k in range(cfg["notary"]["shards"])
        ]

    return make


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The cell and its one-shard copy on one seed, keeping every
    answered frame's kind, and a traced run of the cell."""
    repo = benchmark_harness_util.REPO
    root = tmp_path_factory.mktemp("sharded4") / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(repo, "benchmark"), root / "benchmark",
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    root = str(root)
    _add_one_shard_copy(root)
    real_check = harness.check
    out = {"root": root}
    for cell in (CELL, ONE_SHARD):
        kinds: dict = {}

        def check(ref, frames, recs, *a, _kinds=kinds, **kw):
            for rec in recs:
                for k in range(rec.fed):
                    if rec.answered[k]:
                        _kinds[rec.lo + k] = harness.classify(rec.answers[k])
            _kinds["frames"] = frames
            return real_check(ref, frames, recs, *a, **kw)

        harness.check = check
        try:
            result = run_cell(root, cell, overrides=SIZE)
        finally:
            harness.check = real_check
        out[cell] = (result, kinds.pop("frames"), kinds)
    real_verifiers = harness.make_verifiers
    harness.make_verifiers = _host_verifiers(root)
    try:
        # host verification serves far faster: a deeper pool
        out["traced"] = run_cell(
            root, CELL, seconds=1.0, trace=1,
            overrides=dict(SIZE, workers=2, config={"pool_per_s": 20000}),
        )
    finally:
        harness.make_verifiers = real_verifiers
    return out


def test_sharded_cell_is_correct_on_four_devices(runs):
    result, _, kinds = runs[CELL]
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4
    assert set(result["answers"]) == {fixture.VALID, fixture.TAMPER,
                                      fixture.CONFLICT}
    assert len(kinds) == result["attempted"] + SIZE["traffic"][
        "warmup_frames"]


def test_answers_match_one_shard_frame_for_frame(runs):
    """The same frames (one seed, one shape) served on four shards and
    on one: every frame both runs answered got the same kind, and that
    kind is the one construction gave it."""
    _, frames, four = runs[CELL]
    result, frames_one, one = runs[ONE_SHARD]
    assert result["correct"], result["checks"]
    assert frames.ids == frames_one.ids
    both = set(four) & set(one)
    assert len(both) >= 512
    for i in both:
        assert four[i] == one[i] == frames.kinds[i], i


def test_answers_match_the_reference(runs):
    """Every answered frame of the four-shard run, held to the plain
    reference: its own EC over the frame's signatures decides an
    invalid signature, a re-spend of a state committed before the
    window is a conflict, and the rest are signed."""
    _, frames, four = runs[CELL]
    ref = harness.Cell(runs["root"], CELL).reference()
    committed = set(frames.conflict_refs())
    for i, got in four.items():
        if not ref.frame_signatures_valid(frames.ids[i], frames.sigs[i]):
            want = fixture.TAMPER
        elif any(inp in committed for inp in frames.inputs[i]):
            want = fixture.CONFLICT
        else:
            want = fixture.VALID
        assert got == want, i


def test_traced_run_reads_every_metric_the_cell_lists(runs):
    """The wave readers read numbers; so does every per-layer metric
    the cell was appended to that a CPU capture of host-verified
    shards can give (no ladder runs there, and chip_idle_max needs a
    TPU plane)."""
    result = runs["traced"]
    assert result["correct"], result["checks"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 1.0 <= m["wave_skew.backlog"] <= 4.0
    assert m["wave_us_per_tx.backlog"] > 0
    listed = harness.Cell(runs["root"], CELL).metrics(trace=True)
    ladder = {"ec_kernel_us_per_sig.backlog", "ladder_fill_share.backlog",
              "chip_idle_max.backlog"}
    assert {x["name"] for x in listed} - ladder == set(m)
    assert m["flush_depth_mean.backlog"] > 0


def test_control_fault_turns_the_cell_incorrect(bench_root):
    from benchmark import faults

    # with signatures unchecked the CPU serves far faster: a deeper pool
    out = run_cell(bench_root, CELL, seconds=1.0,
                   overrides=dict(SIZE, drain_s=3.0, workers=2,
                                  config={"pool_per_s": 40000}),
                   fault=faults.FAULTS["accept_all_signatures"])
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


# -- the readers on synthetic captures -----------------------------------------

# the harness's window over 10 us; one flush phase (so the capture is a
# program's that marks regions), two waves of a 4-shard plane (40
# frames with at most 10 on a shard, then 20 all on one shard) and one
# of an 8-shard plane (16 frames, at most 8 on a shard)
WAVES = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000
      stats { metadata_id: 4 int64_value: 4 }
      stats { metadata_id: 5 int64_value: 40 }
      stats { metadata_id: 6 int64_value: 10 }
      stats { metadata_id: 7 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000
      stats { metadata_id: 4 int64_value: 1 }
      stats { metadata_id: 5 int64_value: 20 }
      stats { metadata_id: 6 int64_value: 20 }
      stats { metadata_id: 7 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000
      stats { metadata_id: 4 int64_value: 2 }
      stats { metadata_id: 5 int64_value: 16 }
      stats { metadata_id: 6 int64_value: 8 }
      stats { metadata_id: 7 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "notary.stage" } }
  event_metadata { key: 3 value { id: 3 name: "notary.wave" } }
  stat_metadata { key: 4 value { id: 4 name: "shards" } }
  stat_metadata { key: 5 value { id: 5 name: "frames" } }
  stat_metadata { key: 6 value { id: 6 name: "max_frames" } }
  stat_metadata { key: 7 value { id: 7 name: "n_shards" } }
}
"""

# the window over 10 us on the host; three chips busy 6, 4 and 1 us of
# it (chip 2's second op lies outside the window, chip 0's two ops
# overlap)
PLANES = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "ladder" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "ladder" } }
}
planes {
  id: 4 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "ladder" } }
}
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )


def _reader(name):
    return __import__(f"benchmark.metrics.{name}", fromlist=["read"])


def test_wave_readers_on_a_synthetic_capture(monkeypatch):
    """wave_skew is each wave's shard count times its deepest shard's
    frames, over all frames ((4 x 10 + 4 x 20 + 8 x 8) / 76);
    wave_us_per_tx the waves' seconds over their frames (4 us / 76); a
    capture without waves reads nothing."""
    from benchmark import regions

    class Ctx:
        trace = object()

    for text, want in ((WAVES, (184 / 76, 4.0 / 76)),
                       (WAVES.replace('"notary.wave"', '"flush"'),
                        (None, None))):
        r = regions.from_profile(_profile(text))
        monkeypatch.setattr(regions, "load", lambda ctx, f, _r=r: _r)
        got = (_reader("wave_skew").read(Ctx()),
               _reader("wave_us_per_tx").read(Ctx()))
        if want[0] is None:
            assert got == want
        else:
            assert got == pytest.approx(want)


def test_chip_idle_max_reads_the_most_idle_chip():
    """Idle shares 0.4, 0.6 and 0.9: the reader gives 0.9 where the
    mean over the planes (device_idle_share's reading) is 0.633."""
    from benchmark import trace as tracelib

    reader = _reader("chip_idle_max")
    pd = _profile(PLANES)
    idle = reader.idle_by_plane(pd)
    assert idle == pytest.approx({"/device:TPU:0": 0.4,
                                  "/device:TPU:1": 0.6,
                                  "/device:TPU:2": 0.9})
    reduced = tracelib.reduce_profile(pd)
    assert 1 - reduced.busy_s / reduced.window_s == pytest.approx(1.9 / 3)

    class Untraced:
        trace = None

    assert reader.read(Untraced()) is None
