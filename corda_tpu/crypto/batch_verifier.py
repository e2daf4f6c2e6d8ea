"""BatchSignatureVerifier SPI — the north-star verification seam.

The reference verifies signatures one at a time on the JVM inside
`SignedTransaction.verifyRegularTransaction` -> `Crypto.doVerify`
(core/.../transactions/SignedTransaction.kt:143-149, crypto/Crypto.kt:
439-503), and only offloads *contract* execution through its
`TransactionVerifierService` SPI. Here the signature check itself is the
SPI: callers accumulate (key, signature, message) triples and drain them
through `verify_batch`, which the TPU implementation pads into fixed
batch shapes and dispatches as one jitted XLA program per scheme —
optionally sharded over a device mesh (ICI data parallelism).

Implementations:
  * CpuBatchVerifier  — pure-python reference semantics (bit-exactness
    anchor; also the fallback for non-batchable schemes).
  * TpuBatchVerifier  — jitted limb kernels, per-scheme bucketing,
    power-of-two padding, optional jax.sharding mesh.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel import mesh as meshlib
from ..utils import device_telemetry as devlib
from ..utils import perf as perflib
from ..utils import tracing
from . import encodings, schemes
from .curves import SECP256K1, SECP256R1
from .ecdsa import ecdsa_verify_batch, ecdsa_verify_packed
from .eddsa import ed25519_verify_batch, ed25519_verify_packed


@dataclass(frozen=True)
class VerificationRequest:
    """One signature check: does `signature` by `key` cover `message`?"""

    key: schemes.PublicKey
    signature: bytes
    message: bytes


class _AotLadder:
    """Lazy AOT wrapper around one jitted ladder program.

    First call loads the program's export artifact (crypto/aot_store)
    — skipping the minutes of tracing + lowering a fresh process
    otherwise pays — or, when no artifact exists, exports through the
    jit fn (the ONE trace it would have done anyway) and saves the
    artifact for every later process. Any failure anywhere falls back
    permanently to the plain jit path; CORDA_TPU_AOT=0 bypasses the
    store entirely. `origin` says which path the first call took:
    "aot-hit" (artifact loaded), "aot-saved" (exported and saved
    now) or "jit"."""

    def __init__(self, fn, scheme_id: int, batch: int):
        self._fn = fn
        self._scheme_id = scheme_id
        self._batch = batch
        self._callable = None
        self.origin: Optional[str] = None

    def _build(self, staged):
        from . import aot_store

        self.origin = "jit"
        if not aot_store.enabled():
            return self._fn
        from jax import export as jexport

        exp = aot_store.load(self._scheme_id, self._batch)
        if exp is None:
            try:
                exp = jexport.export(self._fn)(**staged)
                aot_store.save(exp, self._scheme_id, self._batch)
            except Exception:
                return self._fn
            self.origin = "aot-saved"
        else:
            self.origin = "aot-hit"
        call = jax.jit(exp.call)

        def run(**kw):
            return call(**kw)

        return run

    def __call__(self, **staged):
        if self._callable is None:
            try:
                self._callable = self._build(staged)
            except Exception:
                # "any failure anywhere falls back": _build itself may
                # raise (no jax.export on this jax, store path errors)
                self._callable = self._fn
        try:
            return self._callable(**staged)
        except Exception:
            if self._callable is self._fn:
                raise
            # poisoned/incompatible artifact path: pin the jit fallback
            self._callable = self._fn
            self.origin = "jit"
            return self._fn(**staged)


class BatchSignatureVerifier:
    """SPI: verify a batch of signature requests, preserving order."""

    def verify_batch(self, requests: Sequence[VerificationRequest]) -> list[bool]:
        raise NotImplementedError


class CpuBatchVerifier(BatchSignatureVerifier):
    """Reference semantics, one at a time on the host."""

    def verify_batch(self, requests: Sequence[VerificationRequest]) -> list[bool]:
        return [
            schemes.verify_one(r.key, r.signature, r.message) for r in requests
        ]


class TpuBatchVerifier(BatchSignatureVerifier):
    """Batched JAX/TPU verification with per-scheme bucketing.

    Requests are grouped by scheme, padded up to the next configured
    batch size (so jit caches stay warm across calls), verified on
    device, and scattered back into request order. Schemes without a
    batch kernel (RSA, SPHINCS — host hash-tree machinery, not MXU work) fall back to the CPU path.
    """

    def __init__(
        self,
        batch_sizes: tuple[int, ...] = (128, 1024, 4096),
        mesh: Optional[object] = None,
        donate: bool = True,
        device: Optional[object] = None,
        perf=None,
    ):
        """`device` pins every dispatch to ONE jax device (the sharded
        notary's per-device verify path: shard k's whole batch lands on
        device k instead of data-parallel-sharding one batch over the
        mesh). Mutually exclusive with `mesh` — a pinned verifier runs
        the unsharded single-device program on its device.

        `perf`: a utils/perf.KernelAccounting this verifier records
        its per-(scheme, batch-shape) compile-vs-execute timings,
        retraces and host→device transfer bytes into; None records
        into the process default (perf.get_kernel_accounting()) — the
        node's PerfPlane installs its own there, so GET /perf carries
        the split without per-verifier wiring."""
        if device is not None and mesh is not None:
            raise ValueError("device= and mesh= are mutually exclusive")
        self.batch_sizes = tuple(sorted(batch_sizes))
        self.mesh = mesh
        self.device = device
        self.perf = perf
        self._cpu = CpuBatchVerifier()
        self._kernels = {}
        # first-call-per-shape is judged per VERIFIER, not on the
        # (possibly process-shared) accounting: jit caches live on
        # THIS instance's wrappers, so with per-shard verifiers each
        # instance's first dispatch per shape really does pay its own
        # trace+lower (or AOT load) and must record as a compile —
        # keyed on the shared ledger it would masquerade as a
        # multi-second "execute" and dodge the retrace counter
        self._warm_shapes: set = set()
        # per-DEVICE attribution key (utils/device_telemetry): the
        # pinned device's id, or the default device's, resolved lazily
        # (jax.devices() initialises the backend); -1 marks a mesh
        # dispatch — one data-parallel program over every mesh device,
        # not attributable to a single chip
        self._device_id: Optional[int] = None
        del donate  # reserved
        # the EC ladder kernels cost 20-350 s to compile per (scheme,
        # batch, backend); every process constructing this verifier
        # (nodes, verifier workers, driver children) must share the
        # persistent cache or pay that per boot
        from ..utils import jaxenv

        jaxenv.enable_compile_cache()

    # -- kernel plumbing ----------------------------------------------------

    def _kernel(self, scheme_id: int, batch: int):
        key = (scheme_id, batch)
        if key not in self._kernels:
            ed = scheme_id == schemes.EDDSA_ED25519_SHA512
            if ed:
                inner = ed25519_verify_packed
            else:
                curve = {
                    schemes.ECDSA_SECP256K1_SHA256: SECP256K1,
                    schemes.ECDSA_SECP256R1_SHA256: SECP256R1,
                }[scheme_id]
                inner = partial(ecdsa_verify_packed, curve)
            if self.mesh is None:
                # AOT wrapper: tracing + lowering the ladder costs
                # minutes per (scheme, batch); the wrapper loads a
                # serialized export when one exists (crypto/aot_store)
                # and pays the one trace otherwise
                fn = _AotLadder(
                    jax.jit(partial(inner, use_pallas=None)),
                    scheme_id, batch,
                )
            else:
                # GSPMD has no partitioning rule for Mosaic custom
                # calls, but shard_map sidesteps GSPMD: the kernel runs
                # per-shard, so each device keeps the fast Pallas
                # ladder instead of regressing to the XLA one. The
                # whole verify program is elementwise over the batch
                # axis — every operand shards on it (over EVERY mesh
                # axis: 1-D ICI or 2-D dcn×ici), no collectives.
                B = meshlib.batch_spec_axes(self.mesh)
                if ed:
                    in_specs = (P(B, None), P(B), P(B), P(B))
                    arg_order = ("packed", "a_sign", "exp_sign", "valid_in")
                else:
                    in_specs = (P(B, None), P(B))
                    arg_order = ("packed", "valid_in")
                # the pallas auto-policy keys on the process-global
                # default backend — wrong under a mesh in a process
                # where a TPU backend initialised but THIS mesh lives
                # on virtual CPU devices (dryrun_multichip after real-
                # chip work): decide from the mesh's own devices
                mesh_on_tpu = all(
                    d.platform == "tpu"
                    for d in self.mesh.devices.flat
                )
                # None (not True) on TPU meshes: the auto policy
                # resolves to Pallas there AND still honors the
                # CORDA_TPU_NO_PALLAS kill switch; a hard True would
                # bypass it
                mesh_use_pallas = None if mesh_on_tpu else False
                # check_vma off: the scan carries in modmath start from
                # replicated constants and become shard-varying, which
                # the VMA checker rejects; the program is collective-
                # free so the check buys nothing here
                smapped = jax.shard_map(
                    partial(inner, use_pallas=mesh_use_pallas),
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=P(B),
                    check_vma=False,
                )
                fn = jax.jit(
                    lambda _o=arg_order, _f=smapped, **kw: _f(
                        *[kw[k] for k in _o]
                    )
                )
            self._kernels[key] = fn
        return self._kernels[key]

    def kernel_origins(self) -> dict:
        """{(scheme_id, batch): "aot-hit" | "aot-saved" | "jit" |
        "mesh"} for every program this verifier has dispatched."""
        return {
            key: getattr(fn, "origin", None) or "mesh"
            for key, fn in self._kernels.items()
        }

    def _pick_batch(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    def _dispatch_device_id(self) -> int:
        if self._device_id is None:
            if self.device is not None:
                self._device_id = int(getattr(self.device, "id", 0))
            elif self.mesh is not None:
                self._device_id = -1
            else:
                try:
                    self._device_id = int(jax.devices()[0].id)
                except Exception:
                    self._device_id = 0
        return self._device_id

    def _dispatch(self, scheme_id: int, items: list, idxs) -> list:
        """Stage + launch one scheme bucket, chunking at the largest
        batch size. Returns [(device_result, idxs_slice, n)] WITHOUT
        forcing: jax dispatch is async, so the caller's later staging
        (the host-bound 30-40% of the wall) overlaps device compute of
        the chunks already in flight; everything syncs at the end of
        verify_batch."""
        max_b = self.batch_sizes[-1]
        pending = []
        t_entry = time.perf_counter()
        dev_id = self._dispatch_device_id()
        devacct = devlib.get_device_accounting()
        for off in range(0, len(items), max_b):
            chunk = items[off : off + max_b]
            batch = self._pick_batch(len(chunk))
            if scheme_id == schemes.EDDSA_ED25519_SHA512:
                packed, a_signs, r_signs, valid = (
                    encodings.stage_ed25519_packed(chunk, batch)
                )
                staged = {
                    "packed": packed,
                    "a_sign": a_signs,
                    "exp_sign": r_signs,
                    "valid_in": valid,
                }
            else:
                curve = {
                    schemes.ECDSA_SECP256K1_SHA256: SECP256K1,
                    schemes.ECDSA_SECP256R1_SHA256: SECP256R1,
                }[scheme_id]
                packed, valid = encodings.stage_ecdsa_packed(
                    curve, chunk, batch
                )
                staged = {"packed": packed, "valid_in": valid}
            # perf attribution (utils/perf.py): the staged operand
            # payload headed over the link, and the call wall split
            # compile-vs-execute — the FIRST call per (scheme, batch)
            # key in this process is where jax traces+lowers (or loads
            # the AOT artifact); every later call is the async
            # dispatch. A first call on an already-warm accounting is
            # a RETRACE — the jit cache miss the perf alert pages on.
            acct = (
                self.perf if self.perf is not None
                else perflib.get_kernel_accounting()
            )
            nbytes = sum(
                int(getattr(v, "nbytes", 0) or 0) for v in staged.values()
            )
            if self.mesh is not None:
                staged = {
                    k: meshlib.shard_operand(
                        self.mesh, v, batch_axis=0 if k == "packed" else -1
                    )
                    for k, v in staged.items()
                }
            else:
                # commit the operands to the dispatch device — THIS
                # verifier's pinned chip (sharded notary: N shard
                # pipelines keep N chips busy concurrently instead of
                # queueing on the default device), or the default
                # device on an unpinned verifier. The explicit
                # transfer is timed into the accounting EITHER way:
                # device_put is where the link cost is visible to the
                # host, and the old unpinned path (implicit transfer
                # inside the jit call) recorded transfer bytes with
                # zero transfer seconds, so single-device rigs
                # reported a transfer_bytes_per_sec that lied.
                t_put = time.perf_counter()
                staged = {
                    k: jax.device_put(v, self.device)
                    for k, v in staged.items()
                }
                put_s = time.perf_counter() - t_put
                acct.record_transfer(scheme_id, batch, nbytes, put_s)
                devacct.record_transfer(dev_id, nbytes, put_s)
                nbytes = 0   # charged above, not again on the call row
            # TraceAnnotation (null context off-jax-profiler): names
            # this kernel launch in an XLA profiler capture so the
            # host-side dispatch spans line up with device timelines
            first = (scheme_id, batch) not in self._warm_shapes
            t_call = time.perf_counter()
            with tracing.annotate(
                f"corda_tpu.verify_dispatch.s{scheme_id}.b{batch}"
            ):
                res = self._kernel(scheme_id, batch)(**staged)
            self._warm_shapes.add((scheme_id, batch))
            call_s = time.perf_counter() - t_call
            acct.record_call(
                scheme_id, batch, call_s,
                first=first, transfer_bytes=nbytes,
            )
            # per-device attribution: the launch wall as device busy
            # (the windowed busy-fraction feed) and the host-side
            # dispatch-queue wait — wall from bucket entry to this
            # chunk's launch, the serialization a chunk pays behind
            # earlier chunks' staging + launches on the same device
            devacct.record_dispatch(
                dev_id, len(chunk), call_s,
                queue_wait_seconds=t_call - t_entry,
            )
            pending.append((res, idxs[off : off + len(chunk)], len(chunk)))
        return pending

    # -- SPI ---------------------------------------------------------------

    def verify_batch_async(
        self, requests: Sequence[VerificationRequest]
    ) -> "PendingVerification":
        """Stage + dispatch every request without forcing the results:
        jax dispatch is async, so the caller can do host work (Merkle
        proofs, contract checks, staging the next batch) while the
        device computes, then collect with `.result()`."""
        out: list[Optional[bool]] = [None] * len(requests)
        buckets: dict[int, tuple[list, list]] = {}
        cpu_idx: list[int] = []
        for i, req in enumerate(requests):
            sid = req.key.scheme_id
            if sid in SCHEME_KERNELS:
                items, idxs = buckets.setdefault(sid, ([], []))
                items.append((req.key.data, req.signature, req.message))
                idxs.append(i)
            else:
                cpu_idx.append(i)
        pending = []
        for sid, (items, idxs) in buckets.items():
            pending.extend(self._dispatch(sid, items, idxs))
        # queue device->host transfers NOW: each chunk's result pushes
        # to the host as its compute completes, so a later per-chunk
        # consumer (PendingVerification.chunks) never pays a separate
        # link round trip per chunk — only wait-for-compute
        streamed = True
        for res, _, _ in pending:
            try:
                res.copy_to_host_async()
            except Exception:   # noqa: BLE001 - optional acceleration
                streamed = False
                break
        if cpu_idx:
            # CPU fallbacks also overlap the in-flight device chunks
            cpu_res = self._cpu.verify_batch([requests[i] for i in cpu_idx])
            for i, ok in zip(cpu_idx, cpu_res):
                out[i] = ok
        return PendingVerification(out, pending, streamed)

    def verify_batch(self, requests: Sequence[VerificationRequest]) -> list[bool]:
        return self.verify_batch_async(requests).result()


class PendingVerification:
    """Handle for an in-flight TpuBatchVerifier dispatch."""

    def __init__(self, out, pending, streamed: bool = False):
        self._out = out
        self._pending = pending
        self._done = False
        # True when every chunk's device->host transfer was queued at
        # dispatch (copy_to_host_async): per-chunk consumption then
        # costs wait-for-compute only, no per-chunk link round trip
        self.streamed = streamed

    def skeleton(self) -> list:
        """A copy of the result rows known WITHOUT waiting on the
        device: CPU-fallback rows filled, device rows None. Streaming
        consumers seed from this and fill from chunks()."""
        return list(self._out)

    def chunks(self):
        """Yield (request_indices, [bool]) per device chunk in dispatch
        order, as each chunk's compute completes — the streaming form
        of result() (notary flush: validate+commit chunk k's
        transactions while the device still runs chunk k+1). CPU
        fallback rows are already present in the `out` skeleton before
        the first yield. Only sensible on a `streamed` handle; on a
        non-streamed one each yield pays a link round trip."""
        for res, chunk_idxs, n in self._pending or ():
            arr = np.asarray(res)
            yield chunk_idxs, [bool(v) for v in arr[:n].tolist()]

    def result(self) -> list[bool]:
        if not self._done:
            out, pending = self._out, self._pending
            if pending and self.streamed:
                # transfers were queued at dispatch: per-chunk reads
                # are free once compute finishes
                for chunk_idxs, vals in self.chunks():
                    for j, ok in zip(chunk_idxs, vals):
                        out[j] = ok
            elif pending:
                # ONE device->host fetch for all chunks instead of a
                # blocking np.asarray round trip per chunk
                flat = np.asarray(
                    jnp.concatenate([res for res, _, _ in pending])
                )
                off = 0
                for res, chunk_idxs, n in pending:
                    arr = flat[off : off + res.shape[0]]
                    off += res.shape[0]
                    for j, ok in enumerate(arr[:n].tolist()):
                        out[chunk_idxs[j]] = bool(ok)
            # only mark done once the fetch succeeded: a transient
            # device failure must surface on retry, not hand back None
            # rows
            self._out = [bool(v) for v in out]
            self._pending = None
            self._done = True
        return self._out


SCHEME_KERNELS = frozenset(
    {
        schemes.ECDSA_SECP256K1_SHA256,
        schemes.ECDSA_SECP256R1_SHA256,
        schemes.EDDSA_ED25519_SHA512,
    }
)


class DeviceFaultError(RuntimeError):
    """A device/kernel dispatch failed (XLA error, device lost, link
    down). The batching notary's degraded-mode seam catches exactly
    this class of failure: retry once on the device, then fall back to
    the CPU reference verifier for the flush."""


class DispatchFaultInjector(BatchSignatureVerifier):
    """First-class fault seam at the verify dispatch (the chaos plane's
    `device_fault` event arms it; bench/tests use it directly): while
    armed, the next `failures_left` dispatches raise a DeviceFaultError
    instead of reaching the device — after that every call passes
    through to the wrapped verifier untouched, which is what lets the
    notary's auto-recovery probe re-arm the device path. Never
    monkeypatching: the injector IS the installed verifier, so the
    production guard code runs exactly as a real XLA failure would
    drive it."""

    def __init__(self, inner: BatchSignatureVerifier):
        self.inner = inner
        self.failures_left = 0
        self.faults_raised = 0
        self._exc_factory = None

    def arm(self, failures: int = 1, exc_factory=None) -> None:
        """The next `failures` dispatches raise (DeviceFaultError by
        default, or `exc_factory()`); later ones pass through."""
        self.failures_left = int(failures)
        self._exc_factory = exc_factory

    def disarm(self) -> None:
        self.failures_left = 0

    @property
    def armed(self) -> bool:
        return self.failures_left > 0

    def _maybe_fault(self) -> None:
        if self.failures_left > 0:
            self.failures_left -= 1
            self.faults_raised += 1
            raise (
                self._exc_factory()
                if self._exc_factory is not None
                else DeviceFaultError(
                    "injected device fault (dispatch seam)"
                )
            )

    def verify_batch(self, requests: Sequence[VerificationRequest]) -> list[bool]:
        self._maybe_fault()
        return self.inner.verify_batch(requests)

    def verify_batch_async(self, requests: Sequence[VerificationRequest]):
        self._maybe_fault()
        inner_async = getattr(self.inner, "verify_batch_async", None)
        if inner_async is not None:
            return inner_async(requests)
        # sync inner: wrap the completed results in a handle so callers
        # written against the async SPI see one code path
        return PendingVerification(self.inner.verify_batch(requests), [])


def per_shard_verifiers(
    n_shards: int,
    batch_sizes: tuple[int, ...] = (128, 1024, 4096),
    devices: Optional[Sequence] = None,
) -> list[TpuBatchVerifier]:
    """One device-pinned TpuBatchVerifier per commit-plane shard
    (notary.py BatchingNotaryService shard_verifiers=): shard k pins to
    device k mod len(devices), so N shard flush pipelines drive N chips
    concurrently — the per-device half of the round-6 sharded notary.
    With ONE device every shard shares it (dispatches still interleave
    usefully: shard k+1's staging overlaps shard k's device compute).
    Compiled programs are shared across the verifiers per (scheme,
    batch) via the persistent compile cache, so N shards do not pay N
    cold compiles."""
    if devices is None:
        devices = jax.devices()
    if not devices:
        raise RuntimeError("no jax devices for per-shard verifiers")
    out = []
    for k in range(max(1, n_shards)):
        dev = devices[k % len(devices)]
        out.append(
            TpuBatchVerifier(
                batch_sizes=batch_sizes,
                device=dev if len(devices) > 1 else None,
            )
        )
    return out


_default: Optional[BatchSignatureVerifier] = None


def default_verifier() -> BatchSignatureVerifier:
    """Process-wide verifier: TPU-backed, constructed on first use."""
    global _default
    if _default is None:
        _default = TpuBatchVerifier()
    return _default


def set_default_verifier(v: BatchSignatureVerifier) -> None:
    global _default
    _default = v
