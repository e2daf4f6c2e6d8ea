"""API-surface scanner (gradle-plugins/api-scanner analogue)."""

import os

import pytest

# the scan imports every public module; utils.x509 pulls the optional
# `cryptography` package at import time, so a container without it
# cannot scan — skip rather than fail (api-current.txt is still the
# committed review artifact; see CHANGES PR 5 on splicing)
pytest.importorskip("cryptography")

from corda_tpu.tools import api_scanner


def test_scan_contains_known_surface():
    text = api_scanner.scan()
    for needle in (
        "class corda_tpu.flows.api.FlowLogic",
        "class corda_tpu.crypto.batch_verifier.BatchSignatureVerifier",
        "def corda_tpu.crypto.schemes.generate_keypair",
        "class corda_tpu.finance.cash.CashState",
        "class corda_tpu.testing.mock_network.MockNetwork",
    ):
        assert needle in text, f"missing from API scan: {needle}"
    # internals stay out
    assert "corda_tpu.node." not in text


def test_api_surface_matches_committed_file():
    """The committed api-current.txt is the reviewed API. If this
    fails, the public surface changed: review the diff and refresh
    with `python -m corda_tpu.tools.api_scanner --write`."""
    assert os.path.exists(api_scanner.default_path())
    diff = api_scanner.check()
    assert not diff, "\n".join(diff)


def test_classes_bound_at_run_time_stay_out():
    """A class built inside a function and bound to a module global
    (utils/health.py's canary types) is not surface: the scan must not
    depend on which code ran first in the process."""
    import types

    mod = types.ModuleType("corda_tpu._scan_probe")

    def install():
        class Local:
            def method(self):
                pass

        Local.__module__ = mod.__name__
        mod.Bound = Local

    install()
    assert list(api_scanner._public_members(mod)) == []
