"""chip_smoke.py at a tiny size on the CPU, and its refusal to report
anything without a TPU.

The phases are the ones the chip run drives (tests/conftest.py pins
the CPU, so the ladders run as XLA, not Pallas): a 64-spend flush in
16-signature chunks over a 1,000-state commit-log store, the CPU
cross-check, Cash flows through FinalityFlow, and the three-scheme
selfcheck. The four-chip path runs on four virtual CPU devices, marked
slow (every per-device verifier compiles its own ladder).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


TINY = chip_smoke.Sizes(spends=64, chunk=16, states=1000, selfcheck_n=12)


def test_schedule_rejects_by_construction():
    kinds = chip_smoke.schedule(16384, 21)
    assert kinds.count(chip_smoke.TAMPER) == 1024
    assert kinds.count(chip_smoke.CONFLICT) == 1024
    assert kinds == chip_smoke.schedule(16384, 21)   # seeded
    # rejects land in every 4096 chunk
    for c in range(4):
        chunk = kinds[c * 4096:(c + 1) * 4096]
        assert chip_smoke.TAMPER in chunk and chip_smoke.CONFLICT in chunk


def test_crosscheck_rows_cover_every_chunk_and_residue():
    rows = chip_smoke.crosscheck_rows(16384, 4096, 1024)
    assert len(rows) == len(set(rows)) == 1024
    for c in range(4):
        mine = [r for r in rows if c * 4096 <= r < (c + 1) * 4096]
        assert {r % chip_smoke.TILE for r in mine} == set(range(128))


def test_smoke_phases_tiny_on_cpu(tmp_path):
    lines = []
    chip_smoke.run_one_chip(TINY, str(tmp_path), verbose=lines.append,
                            allow_cpu=True)
    text = "\n".join(lines)
    assert ("flush: 64 answers == construction (accepts 56, invalid "
            "signature 4, conflicts 4); Notary.DegradedFlushes = 0") in text
    assert "store: commitlog, 1004 committed states" in text
    assert "cpu cross-check: 64 rows over 4 chunks" in text
    assert "3 payments notarised through FinalityFlow" in text
    assert "p256/k1/ed25519 bit-exact" in text
    assert os.listdir(tmp_path) == []   # the store's directory is gone


@pytest.mark.slow
def test_four_chip_path_on_virtual_devices(tmp_path):
    lines = []
    chip_smoke.run_four_chips(TINY, str(tmp_path), jax.devices()[:4],
                              verbose=lines.append)
    text = "\n".join(lines)
    assert "64 answers == construction over 4 shards" in text
    assert "mesh verify: 64 rows over 4 devices == construction" in text


def test_driver_run_is_the_deployment_size():
    """The script's only option is --chips: nothing on its command line
    can shrink a chip run below the deployment and still print ok."""
    assert chip_smoke.Sizes() == chip_smoke.Sizes(
        spends=16384, chunk=4096, states=1_000_000, selfcheck_n=256
    )
    assert chip_smoke.parse_args([]).chips == 1
    for flag in ("--spends", "--chunk", "--states", "--seed"):
        with pytest.raises(SystemExit):
            chip_smoke.parse_args([flag, "64"])


def test_main_fails_without_a_tpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out


def test_script_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo, the script exits non-zero and prints no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
