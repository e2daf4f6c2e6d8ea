"""Faults planted in the timed path, for the control and the tests.

Each takes the objects a run serves with (the notary service, its
services hub and the uniqueness store) just before the warm-up, breaks
one thing in place, and returns an optional hook the harness applies
to the window's ingest ring. None of them is reachable from run.py's
command line.

- accept_all_signatures: the control. The notary stops checking
  signatures — a broken `validating` guarantee, the step that would
  tempt a later PR — so every tampered frame is signed.
- skip_uniqueness: the store acknowledges every commit and keeps none
  (a step that returns its state unchanged): re-spends are signed and
  nothing reads back.
- alter_answer: every notary signature has one byte flipped where it
  is produced.
- no_fsync: the commit log is written and never fsynced (a store opened
  with fsync=False): the fsync-per-flush guarantee broken.
- fsync_after_reply: the commit log is fsynced only once the flush has
  sent its replies.
- drop_half: every other request of each batch the ring hands the
  notary is left out and never answered.
"""

from __future__ import annotations

import dataclasses
import os


class _AllValid:
    """A verify handle on which every signature reads valid."""

    streamed = False

    def __init__(self, n: int):
        self.n = n

    def result(self):
        return [True] * self.n


def accept_all_signatures(svc, services, store):
    shards = getattr(svc, "_shards", None) or []
    for v in [services.batch_verifier] + [s.verifier for s in shards]:
        if v is not None:
            v.verify_batch_async = lambda reqs: _AllValid(len(reqs))
            v.verify_batch = lambda reqs: [True] * len(reqs)


def skip_uniqueness(svc, services, store):
    svc.uniqueness.commit_many = lambda entries: [None] * len(entries)


def alter_answer(svc, services, store):
    km = services.key_management
    sign_batch = km.sign_batch

    def flipped(tx_ids, key):
        out = []
        for s in sign_batch(tx_ids, key):
            raw = bytearray(s.signature)
            raw[0] ^= 0x01
            out.append(dataclasses.replace(s, signature=bytes(raw)))
        return out

    km.sign_batch = flipped


def no_fsync(svc, services, store):
    for part in store._stores:
        part._fsync = False


def fsync_after_reply(svc, services, store):
    no_fsync(svc, services, store)
    flush = svc.flush

    def flush_then_sync():
        flush()
        for part in store._stores:
            if part._active_fh is not None:
                os.fsync(part._active_fh.fileno())

    svc.flush = flush_then_sync


def drop_half(svc, services, store):
    def hook(ring):
        drain = ring.drain
        ring.drain = lambda: [batch[::2] for batch in drain()]
    return hook


FAULTS = {
    f.__name__: f
    for f in (accept_all_signatures, skip_uniqueness, alter_answer,
              no_fsync, fsync_after_reply, drop_half)
}
