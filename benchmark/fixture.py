"""The frame pool: signed wire frames made from --seed in worker processes.

Every frame is one canonical CTS-encoded SignedTransaction, built by the
configuration's transaction shape (benchmark/shapes/<tx_shape>.py).
Exactly 1/16 of the frames carry a tampered signature and 1/16 re-spend a
state committed before the window (PR 21's schedule, chip_smoke.py), at
positions shuffled by the seed, so every seed does the same work in
another order.

The pool is built by a `spawn` process pool whose workers are pinned to
the CPU backend (JAX_PLATFORMS=cpu): the program's crypto modules import
jax when they are imported, so a worker that touched a backend without
the pin would contend for the chip. Workers start before the harness
touches the chip and sign while it builds the store and compiles.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import multiprocessing
import os
import random
from typing import Optional

TAMPER, CONFLICT, VALID = "invalid-signature", "conflict", "signed"

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def schedule(n: int, seed: int) -> list[str]:
    """The construction-known outcome per frame: exactly n//16 tampered
    signatures and n//16 re-spends, at seeded positions."""
    kinds = [TAMPER] * (n // 16) + [CONFLICT] * (n // 16)
    kinds += [VALID] * (n - len(kinds))
    random.Random(seed).shuffle(kinds)
    return kinds


_SHAPES: dict = {}


def load_shape(name: str, bench_dir: str = BENCH_DIR):
    """<bench_dir>/shapes/<name>.py, loaded once per process."""
    path = os.path.join(bench_dir, "shapes", f"{name}.py")
    if path in _SHAPES:
        return _SHAPES[path]
    if not os.path.exists(path):
        raise FileNotFoundError(f"no transaction shape {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"_bench_shape_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _SHAPES[path] = mod
    return mod


def keypair(seed: int, role: str, scheme_id: int):
    """A party's key pair, a pure function of the seed and its role:
    workers and the harness derive the same parties independently."""
    from corda_tpu.crypto import schemes

    rng = random.Random(f"{seed}/{role}")
    return schemes.generate_keypair(scheme_id, seed=rng.getrandbits(256))


def notary_party(seed: int, scheme_id: int):
    from corda_tpu.core.identity import Party

    kp = keypair(seed, "notary", scheme_id)
    return Party("O=Notary,L=Zurich,C=CH", kp.public), kp


@dataclasses.dataclass
class Chunk:
    """One worker's share of the pool, in frame order.

    `issues` are the encoded transactions that created the states the
    frames consume (recorded into the notary's storage in set-up).
    Per frame: the wire blob, the transaction id as built, the inputs
    as (txhash bytes, index), and each signature as (scheme id, public
    key bytes, signature bytes) as it went on the wire."""

    start: int
    issues: list
    blobs: list
    ids: list
    inputs: list
    sigs: list


def _tamper(sig_bytes: bytes) -> bytes:
    raw = bytearray(sig_bytes)
    raw[-3] ^= 0x01      # inside s (DER stays well-formed) / inside S
    return bytes(raw)


def make_chunk(bench_dir: str, shape_name: str, params: dict, seed: int,
               start: int, kinds: list) -> Chunk:
    """Build frames start .. start+len(kinds) of the pool (worker side)."""
    import dataclasses as dc

    from corda_tpu.core import serialization as ser
    from corda_tpu.core.transactions import SignedTransaction

    shape = load_shape(shape_name, bench_dir)
    txs, issues = shape.build(params, seed, start, len(kinds))
    out = Chunk(start, [ser.encode(i) for i in issues], [], [], [], [])
    for stx, kind in zip(txs, kinds):
        if kind == TAMPER:
            first = stx.sigs[0]
            stx = SignedTransaction(
                stx.wtx,
                (dc.replace(first, signature=_tamper(first.signature)),)
                + tuple(stx.sigs[1:]),
            )
        out.blobs.append(ser.encode(stx))
        out.ids.append(stx.wtx.id.bytes_)
        out.inputs.append(
            tuple((r.txhash.bytes_, r.index) for r in stx.wtx.inputs)
        )
        out.sigs.append(
            tuple((s.by.scheme_id, s.by.data, s.signature) for s in stx.sigs)
        )
    return out


class FramePool:
    """Starts the workers at construction; `result()` waits for them.

    `chunk` frames share one issuing transaction per state kind, so a
    chunk is the unit of work handed to a worker."""

    def __init__(self, root: str, shape: str, params: dict, seed: int,
                 n: int, workers: int, chunk: int):
        self.kinds = schedule(n, seed)
        # the pin is inherited by spawned children; restored right away
        old = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            ctx = multiprocessing.get_context("spawn")
            # spawned workers inherit sys.path, so they import this
            # checkout's benchmark and corda_tpu
            self._pool = ctx.Pool(max(1, workers))
        finally:
            if old is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = old
        self._asyncs = [
            self._pool.apply_async(
                make_chunk,
                (os.path.join(root, "benchmark"), shape, params, seed, off,
                 self.kinds[off:off + chunk]),
            )
            for off in range(0, n, chunk)
        ]

    def chunks(self, timeout: Optional[float] = None):
        """Each chunk in frame order, as soon as it is built: the caller
        records one chunk's issuing transactions while the workers sign
        the next ones."""
        try:
            for a in self._asyncs:
                yield a.get(timeout)
        finally:
            self.close()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None


@dataclasses.dataclass
class Frames:
    """The whole pool, flattened in frame order."""

    kinds: list
    blobs: list
    ids: list
    inputs: list
    sigs: list

    @classmethod
    def join(cls, kinds: list, chunks, on_issues) -> "Frames":
        """Flatten `chunks` (in frame order); `on_issues` takes each
        chunk's encoded issuing transactions as it arrives."""
        f = cls(kinds, [], [], [], [])
        for c in chunks:
            on_issues(c.issues)
            f.blobs += c.blobs
            f.ids += c.ids
            f.inputs += c.inputs
            f.sigs += c.sigs
        if len(f.blobs) != len(kinds):
            raise RuntimeError(
                f"pool built {len(f.blobs)} frames, {len(kinds)} asked"
            )
        return f

    def conflict_refs(self, lo: int = 0, hi: Optional[int] = None) -> list:
        """The inputs of every re-spend frame: committed before the
        window, so the notary must answer each such frame `conflict`."""
        hi = len(self.kinds) if hi is None else hi
        return [
            ref
            for i in range(lo, hi) if self.kinds[i] == CONFLICT
            for ref in self.inputs[i]
        ]
