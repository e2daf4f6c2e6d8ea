"""Auxiliary subsystems: X.509 hierarchy, audit service, progress
rendering, determinism audit (SURVEY §2.2/§5 + experimental/)."""

import io

import pytest

# utils.x509 builds real certificates on the `cryptography` package —
# absent (it's an optional dep), this module cannot even import, so
# skip at collection instead of erroring the whole tier-1 collect
pytest.importorskip("cryptography")

from corda_tpu.experimental import determinism
from corda_tpu.flows.api import ProgressTracker
from corda_tpu.node.audit import InMemoryAuditService, PersistentAuditService
from corda_tpu.utils import x509 as x509lib
from corda_tpu.utils.progress_render import ProgressRenderer, render


# -- X.509 -------------------------------------------------------------------


def test_cert_hierarchy_and_chain_validation():
    bundle = x509lib.dev_certificate_hierarchy("BankA")
    chain = (
        bundle["identity"].cert,
        bundle["node_ca"].cert,
        bundle["intermediate"].cert,
        bundle["root"].cert,
    )
    assert x509lib.validate_chain(*chain)
    # wrong order fails
    assert not x509lib.validate_chain(*reversed(chain))
    # foreign leaf fails against this chain
    other = x509lib.dev_certificate_hierarchy("Mallory")
    assert not x509lib.validate_chain(
        other["identity"].cert,
        bundle["node_ca"].cert,
        bundle["intermediate"].cert,
        bundle["root"].cert,
    )
    # PEM round trip
    assert b"BEGIN CERTIFICATE" in bundle["tls"].cert_pem
    assert b"PRIVATE KEY" in bundle["tls"].key_pem


# -- audit -------------------------------------------------------------------


def test_audit_services(tmp_path):
    mem = InMemoryAuditService()
    mem.record("rpc", "mallory", "bad password", attempts="3")
    mem.record("flow", "alice", "started CashPaymentFlow")
    assert len(mem.events()) == 2
    assert len(mem.events("rpc")) == 1
    assert mem.events("rpc")[0].context == (("attempts", "3"),)

    from corda_tpu.node.persistence import NodeDatabase

    db = NodeDatabase(str(tmp_path / "a.db"))
    try:
        aud = PersistentAuditService(db)
        aud.record("notary", "Bob", "double-spend attempt", ref="abc")
        aud.record("notary", "Bob", "second attempt")
        got = aud.events("notary")
        assert [e.description for e in got] == [
            "double-spend attempt", "second attempt",
        ]
        assert got[0].context == (("ref", "abc"),)
    finally:
        db.close()


# -- progress rendering ------------------------------------------------------


def test_progress_render():
    tracker = ProgressTracker("collect", "notarise", "broadcast")
    tracker.set_step("collect")
    tracker.set_step("notarise")
    out = render(tracker, ansi=False)
    lines = out.splitlines()
    assert lines[0].startswith("✓ collect")
    assert lines[1].startswith("▶ notarise")
    assert lines[2].startswith("  broadcast")


def test_progress_renderer_streams():
    tracker = ProgressTracker("a", "b")
    buf = io.StringIO()
    r = ProgressRenderer(tracker, buf)
    tracker.set_step("a")
    tracker.set_step("b")
    text = buf.getvalue()
    assert "▶ a" in text and "▶ b" in text
    r.close()
    tracker.set_step("a")
    assert buf.getvalue() == text   # detached


# -- determinism audit -------------------------------------------------------


def test_shipped_contracts_pass_determinism_audit():
    # importing finance/samples registers their contracts
    import corda_tpu.finance  # noqa: F401
    import corda_tpu.samples.irs_demo  # noqa: F401

    from corda_tpu.core.contracts import _CONTRACT_REGISTRY

    # only what the package ships: tests/test_docs_snippets.py registers
    # docs.* contracts from exec'd source, which has no file to audit
    offenders = {
        name: v
        for name, v in determinism.audit_registered_contracts().items()
        if getattr(_CONTRACT_REGISTRY[name], "__module__", "").startswith(
            "corda_tpu"
        )
    }
    assert offenders == {}, offenders


def test_determinism_audit_catches_nondeterminism():
    bad = """
    def verify(self, ltx):
        import time
        if time.time() > 100:
            pass
        while True:
            break
        try:
            x = 1
        except:
            pass
    """
    violations = determinism.audit_source(bad)
    messages = " | ".join(v.message for v in violations)
    assert "time" in messages
    assert "while" in messages
    assert "bare except" in messages


def test_determinism_audit_raises_on_contract_object():
    class EvilContract:
        def verify(self, ltx):
            import random
            return random.random()

    with pytest.raises(determinism.DeterminismError, match="random"):
        determinism.audit_contract(EvilContract())


def test_path_length_constraint_enforced():
    """A path_len=0 node CA must not mint a validating sub-CA chain
    (review finding)."""
    root = x509lib.create_root_ca()
    inter = x509lib.create_intermediate_ca(root)
    node_ca = x509lib.create_node_ca(inter, "Corp")   # path_len=0
    rogue_sub = x509lib._build("Rogue Sub CA", node_ca, is_ca=True, path_len=0)
    victim_leaf = x509lib.create_leaf(rogue_sub, "VictimBank")
    assert not x509lib.validate_chain(
        victim_leaf.cert, rogue_sub.cert, node_ca.cert,
        inter.cert, root.cert,
    )
    # the legitimate depth still validates
    leaf = x509lib.create_leaf(node_ca, "Corp")
    assert x509lib.validate_chain(
        leaf.cert, node_ca.cert, inter.cert, root.cert
    )


def test_expiry_enforced():
    import datetime

    root = x509lib.create_root_ca()
    future = datetime.datetime(2290, 1, 1, tzinfo=datetime.timezone.utc)
    assert not x509lib.validate_chain(root.cert, at=future)
    past = datetime.datetime(2019, 1, 1, tzinfo=datetime.timezone.utc)
    assert not x509lib.validate_chain(root.cert, at=past)


def test_render_dedupes_repeated_offlist_steps():
    tracker = ProgressTracker("a")
    tracker.set_step("a")
    tracker.set_step("resolving")
    tracker.set_step("resolving")
    out = render(tracker, ansi=False)
    assert out.count("resolving") == 1


def test_node_webserver_serves_metrics(tmp_path):
    import threading
    import urllib.request

    from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    node = Node(
        NodeConfig(
            name="Solo",
            base_dir=str(tmp_path / "solo"),
            rpc_users=(RpcUserConfig("u", "p", ("ALL",)),),
        ),
        batch_verifier=CpuBatchVerifier(),
    ).start()
    t = threading.Thread(target=node.run, daemon=True)
    t.start()
    try:
        node.metrics.counter("node.test.counter").inc(3)
        web = node.webserver("u", "p")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{web.port}/metrics", timeout=10
            ) as r:
                assert "node_test_counter 3" in r.read().decode()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{web.port}/api/status", timeout=30
            ) as r:
                import json

                assert json.loads(r.read())["identity"]["name"] == "Solo"
        finally:
            web.stop()
    finally:
        node.stop()
        t.join(timeout=5)
