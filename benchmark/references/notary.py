"""Plain reference of a validating notary's answers.

Independent of the program: it imports nothing of corda_tpu and takes
only bytes — what construction put on the wire, and the raw fields of
the answers. Signatures are checked with the `cryptography` package
(OpenSSL), Merkle paths with hashlib, and the signed payload is written
out byte for byte from the canonical encoding of
SignableData(tx_id, SignatureMetadata(platform_version, scheme_id))
(Corda SignableData.kt; corda_tpu/crypto/tx_signature.py).

The expected answer of each frame follows from construction alone: a
tampered signature is refused as an invalid signature, a re-spend of a
state committed before the window is a conflict, and everything else is
signed by the notary. Uniqueness is first-wins in arrival order, so the
answer of a frame never depends on how the notary batches.
"""

from __future__ import annotations

import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, ed25519

P256, ED25519, K1 = 3, 4, 2   # Crypto.kt scheme ids (schemes.py)


def _field(name: str) -> bytes:
    b = name.encode()
    return b"\x06" + bytes([len(b)]) + b


def _obj(name: str) -> bytes:
    b = name.encode()
    return b"\x09" + bytes([len(b)]) + b


def _small_int(v: int) -> bytes:
    if not 0 <= v < 128:
        raise ValueError(f"reference encodes ints 0..127, got {v}")
    return b"\x03" + bytes([v])


def signable(tx_id: bytes, platform_version: int, scheme_id: int) -> bytes:
    """The canonical bytes a transaction signature covers."""
    if len(tx_id) != 32:
        raise ValueError("tx id is 32 bytes")
    return (
        _obj("SignableData") + b"\x02"
        + _field("tx_id") + _obj("Hash") + b"\x05\x20" + tx_id
        + _field("metadata") + _obj("SignatureMetadata") + b"\x02"
        + _field("platform_version") + _small_int(platform_version)
        + _field("scheme_id") + _small_int(scheme_id)
    )


def verify(scheme_id: int, pub: bytes, sig: bytes, msg: bytes) -> bool:
    try:
        if scheme_id == ED25519:
            ed25519.Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        elif scheme_id in (P256, K1):
            curve = ec.SECP256R1() if scheme_id == P256 else ec.SECP256K1()
            ec.EllipticCurvePublicKey.from_encoded_point(curve, pub).verify(
                sig, msg, ec.ECDSA(hashes.SHA256())
            )
        else:
            raise ValueError(f"reference has no scheme {scheme_id}")
    except (InvalidSignature, ValueError):
        return False
    return True


def merkle_root(leaf: bytes, index: int, tree_size: int, path: bytes) -> bytes:
    """Root of a binary SHA-256 tree from one leaf and its sibling path
    (bottom-up, 32 bytes per level)."""
    depth = tree_size.bit_length() - 1
    if tree_size <= 0 or tree_size & (tree_size - 1) \
            or len(path) != 32 * depth or not 0 <= index < tree_size:
        raise ValueError("malformed inclusion proof")
    h = leaf
    for d in range(depth):
        sib = path[32 * d:32 * (d + 1)]
        h = hashlib.sha256(h + sib if index % 2 == 0 else sib + h).digest()
        index //= 2
    return h


def frame_signatures_valid(tx_id: bytes, sigs) -> bool:
    """Whether every signature a frame carries verifies over its id:
    `sigs` = [(scheme id, public key bytes, signature bytes)]."""
    return all(
        verify(sid, pub, sig, signable(tx_id, 1, sid))
        for sid, pub, sig in sigs
    )


def notary_signature_valid(tx_id: bytes, reply, notary_pub: bytes,
                           cache: dict) -> bool:
    """A signed reply: (signature, public key bytes, key scheme id,
    metadata platform version, metadata scheme id, proof), with proof
    None or (leaf index, tree size, sibling path). It must be the notary's
    key, and verify over the root the proof ties the transaction id to.
    `cache` holds verdicts per (root, signature): one batch signature
    covers a whole flush."""
    sig, pub, sid, version, meta_sid, proof = reply
    if pub != notary_pub or meta_sid != sid:
        return False
    try:
        root = tx_id if proof is None else merkle_root(tx_id, *proof)
    except (TypeError, ValueError):
        return False
    key = (root, sig, sid, version)
    if key not in cache:
        cache[key] = verify(sid, pub, sig, signable(root, version, sid))
    return cache[key]
