"""The uniqueness store a run serves from.

A deployment's commit log already holds what the notary committed
before: `states` random state refs, folded into the mmap index. That
base is built once per checkout from a fixed base seed under
benchmark/.cache/, keyed by a hash of node/statestore.py (a PR that
changes the format rebuilds it), and each run serves from a copy of
it: sealed snapshot files are immutable once published, so they are
hard-linked; the manifest, layout and log segments are copied.
"""

from __future__ import annotations

import hashlib
import os
import random
import shutil

BASE_SEED = 1_000_003
ROWS_PER_COMMIT = 32 * 2048   # 2,048 transactions of 32 inputs per commit


class Requester:
    def __init__(self, name: str):
        self.name = name


def _key(states: int, n_shards: int) -> str:
    from corda_tpu.node import statestore

    with open(statestore.__file__, "rb") as fh:
        h = hashlib.sha256(fh.read())
    h.update(f"{states}/{n_shards}/{BASE_SEED}".encode())
    return h.hexdigest()[:16]


def preload(path: str, states: int, n_shards: int, seed: int = BASE_SEED):
    """A commit-log store of `n_shards` partitions holding `states`
    committed states (random refs), folded into its index
    (chip_smoke.preload_store)."""
    from corda_tpu.core.contracts import StateRef
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.node.statestore import ShardedCommitLogUniquenessProvider

    rng = random.Random(seed)
    store = ShardedCommitLogUniquenessProvider(path, n_shards, fsync=True)
    who = Requester("O=Preload")
    try:
        for off in range(0, states, ROWS_PER_COMMIT):
            n = min(ROWS_PER_COMMIT, states - off)
            refs = [StateRef(SecureHash(rng.randbytes(32)), 0)
                    for _ in range(n)]
            entries = [
                (refs[i:i + 32], SecureHash(rng.randbytes(32)), who)
                for i in range(0, n, 32)
            ]
            if any(store.commit_many(entries)):
                raise RuntimeError("base store preload conflicted")
        store.compact_all()
    finally:
        store.close()


def base(cache: str, states: int, n_shards: int) -> tuple[str, bool]:
    """(path of the base store, whether this call built it)."""
    path = os.path.join(cache, f"store-{_key(states, n_shards)}")
    if os.path.exists(os.path.join(path, "READY")):
        return path, False
    tmp = path + ".building"
    shutil.rmtree(tmp, ignore_errors=True)
    preload(tmp, states, n_shards)
    with open(os.path.join(tmp, "READY"), "w") as fh:
        fh.write(f"{states}\n")
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)
    return path, True


def copy(base_path: str, dst: str) -> None:
    """The run's own store: snapshot files linked, the rest copied."""
    shutil.rmtree(dst, ignore_errors=True)

    def link_or_copy(src, d):
        if os.path.basename(src).startswith("snapshot-"):
            try:
                os.link(src, d)
                return d
            except OSError:
                pass
        return shutil.copy2(src, d)

    shutil.copytree(base_path, dst, copy_function=link_or_copy)


def open_store(path: str, n_shards: int, fsync: bool):
    from corda_tpu.node.statestore import ShardedCommitLogUniquenessProvider

    return ShardedCommitLogUniquenessProvider(path, n_shards, fsync=fsync)


def commit_spent(store, refs) -> None:
    """Commit the refs the seed's re-spend frames consume, each by a
    transaction that is not in the pool."""
    from corda_tpu.core.contracts import StateRef
    from corda_tpu.crypto.hashes import SecureHash

    if not refs:
        return
    who = Requester("O=EarlierSpender")
    spender = SecureHash(hashlib.sha256(b"earlier spender").digest())
    entries = [([StateRef(SecureHash(h), i)], spender, who) for h, i in refs]
    if any(store.commit_many(entries)):
        raise RuntimeError("committing the re-spent refs conflicted")
